"""Idle time by engine phase (``phases``), on a made-up trace with known
answers and on the slice of a chip trace (testdata/)."""

import _paths  # noqa: F401

import numpy as np
import pytest
from jax.profiler import ProfileData
from test_chipbench_trace import SLICE, _plane

from benchmarks.chip import phases, xtrace


def _made_up():
    """The harness's spans of ``test_chipbench_trace._made_up`` with the
    engine's inside them: one decode step, one flush, one prefill."""
    host = _plane(1, "/host:CPU", {"python3": [
        ("bench.window", 100, 1000),
        ("bench.decode", 100, 400), ("engine.decode", 110, 380),
        ("engine.decode.dispatch", 110, 90),
        ("engine.decode.readback", 200, 200),
        ("engine.decode.bookkeep", 400, 80),
        ("bench.telemetry", 500, 100), ("engine.flush", 505, 90),
        ("engine.flush.observe", 510, 50), ("engine.flush.advance", 560, 30),
        ("bench.admit", 600, 300), ("engine.admit", 605, 290),
        ("bench.prefill", 650, 200), ("engine.prefill", 655, 190),
        ("engine.prefill.dispatch", 660, 40),
        ("engine.prefill.insert", 700, 60),
        ("engine.prefill.readback", 760, 80)]})
    dev = _plane(2, "/device:TPU:0", {
        "XLA Modules": [("jit_decode_step(7)", 150, 300),
                        ("jit_prefill_one(9)", 700, 100),
                        ("jit_decode_step(7)", 1200, 50)],   # after window
        "XLA Ops": [("fusion.1", 150, 200), ("fusion.2", 300, 150),
                    ("fusion.3", 700, 100), ("fusion.4", 1200, 50)]})
    return ProfileData.from_text_proto(host + "\n" + dev)


# idle: 100-150, 450-700 and 800-1100, each piece under the innermost span
IDLE_NS = {"decode": 20, "engine.decode.dispatch": 40,
           "engine.decode.readback": 0, "engine.decode.bookkeep": 30,
           "engine.decode": 10,
           "telemetry": 10, "engine.flush": 10, "engine.flush.observe": 50,
           "engine.flush.advance": 30, "admit": 10, "engine.admit": 90,
           "prefill": 10, "engine.prefill": 10,
           "engine.prefill.dispatch": 40, "engine.prefill.insert": 0,
           "engine.prefill.readback": 40,
           "none": 200}


def test_idle_is_split_over_every_phase_a_stretch_crosses():
    ph = phases.reduce(_made_up())
    assert ph.window_s == pytest.approx(1000e-9)
    assert ph.busy_s == pytest.approx(400e-9) and ph.devices == 1
    assert ph.idle_by_phase == {k: pytest.approx(v * 1e-9)
                                for k, v in IDLE_NS.items()}
    assert sum(ph.idle_by_phase.values()) == \
        pytest.approx(ph.window_s - ph.busy_s, rel=1e-9)


def test_the_midpoint_rule_is_unchanged_beside_it():
    s = xtrace.summarize(_made_up())
    assert s.idle_by_span == {"decode": pytest.approx(50e-9),
                              "telemetry": pytest.approx(250e-9),
                              "none": pytest.approx(300e-9)}
    assert s.busy_s == pytest.approx(phases.reduce(_made_up()).busy_s)


def test_spans_and_longest_read_the_engine_events():
    ph = phases.reduce(_made_up())
    assert set(ph.spans) == {k for k in IDLE_NS if k.startswith("engine.")}
    assert ph.spans["engine.decode"] == [pytest.approx(380e-9), 1]
    assert ph.spans["engine.prefill.insert"] == [pytest.approx(60e-9), 1]
    # the device ran 150-450 and 700-800
    assert ph.longest["engine.decode.readback"] == \
        [pytest.approx(200e-9), pytest.approx(1.0)]
    assert ph.longest["engine.prefill.readback"] == \
        [pytest.approx(80e-9), pytest.approx(0.5)]
    assert ph.longest["engine.flush"] == [pytest.approx(90e-9), 0.0]
    assert ph.longest["engine.decode"][1] == pytest.approx(300 / 380)


def test_the_clock_check_pairs_programs_with_steps_in_order():
    # the made-up decode program runs 150-450: its dispatch began at 110,
    # its readback ended at 400, before the program did
    assert phases.reduce(_made_up()).clock_ms == {
        "lead": [pytest.approx(40e-6)] * 2,
        "lag": [pytest.approx(-50e-6)] * 2}
    progs = [(100, 200), (300, 400), (500, 600)]
    dispatch = [(90, 95), (280, 290), (505, 510)]
    readback = [(150, 230), (310, 390), (520, 640)]
    got = phases.clock_ms(progs, dispatch, readback)
    assert got == {"lead": [pytest.approx(-5e-6), pytest.approx(10e-6)],
                   "lag": [pytest.approx(-10e-6), pytest.approx(30e-6)]}
    # a step missing from one side gives nothing rather than a wrong pair
    assert phases.clock_ms(progs, dispatch[1:], readback) == {}


def test_per_step_readings_have_their_known_values():
    m = phases.reduce(_made_up()).metrics()
    # one decode step: 40 + 30 + 10 ns under engine.decode, 90 under flush
    assert m["decode_host_idle_ms"] == pytest.approx(80e-6)
    assert m["monitor_idle_ms"] == pytest.approx(90e-6)
    assert m["prefill_host_ms"] == pytest.approx(190e-6)


def test_without_engine_spans_only_the_harness_phases_remain():
    """The chip slice was recorded before the engine had spans."""
    pd = xtrace.load(str(SLICE))
    ph, s = phases.reduce(pd), xtrace.summarize(pd)
    assert ph.busy_s == pytest.approx(s.busy_s, rel=1e-12)
    assert sum(ph.idle_by_phase.values()) == \
        pytest.approx(ph.window_s - ph.busy_s, rel=1e-6)
    assert set(ph.idle_by_phase) <= set(s.idle_by_span) | {
        "admit", "decode", "prefill", "telemetry", "submit", "idle"}
    assert ph.spans == {} and ph.longest == {} and ph.clock_ms == {}
    assert ph.metrics() == {"decode_host_idle_ms": None,
                            "monitor_idle_ms": None,
                            "prefill_host_ms": None}


def test_busy_before_counts_partial_intervals():
    iv = np.array([[10, 20], [30, 50]], float)
    got = phases.busy_before(iv, np.array([0, 10, 15, 25, 40, 60], float))
    assert got.tolist() == [0, 0, 5, 10, 20, 30]


def test_a_trace_without_the_window_span_is_refused():
    dev = _plane(1, "/device:TPU:0", {"XLA Modules": [("jit_a", 0, 5)]})
    with pytest.raises(ValueError, match="bench.window"):
        phases.reduce(ProfileData.from_text_proto(dev))
