"""The trace reduction, on a made-up trace with known answers and on a
slice of a trace recorded on a TPU v5e (testdata/)."""

import _paths  # noqa: F401

import numpy as np
import pytest
from jax.profiler import ProfileData

from benchmarks.chip import spec, stats, xtrace
from benchmarks.chip.serve_loop import Record, Step

SLICE = spec.HERE / "testdata" / "qwen3-0.6b.chat-poisson.slice.xspace.gz"


def _plane(pid, name, lines):
    """lines: {line name: [(event name, start_ns, dur_ns)]}."""
    names = sorted({e[0] for evs in lines.values() for e in evs})
    ids = {n: i + 1 for i, n in enumerate(names)}
    body = []
    for k, (ln, evs) in enumerate(lines.items()):
        ev = " ".join(f"events {{ metadata_id: {ids[n]} offset_ps: "
                      f"{s * 1000} duration_ps: {d * 1000} }}"
                      for n, s, d in evs)
        body.append(f'lines {{ id: {k + 1} name: "{ln}" timestamp_ns: 0 '
                    f"{ev} }}")
    meta = " ".join(f'event_metadata {{ key: {i} value {{ id: {i} name: '
                    f'"{n}" }} }}' for n, i in ids.items())
    return f'planes {{ id: {pid} name: "{name}" ' + " ".join(body) + \
        f" {meta} }}"


def _made_up():
    host = _plane(1, "/host:CPU", {"python3": [
        ("bench.window", 100, 1000), ("bench.decode", 100, 400),
        ("bench.telemetry", 500, 100), ("bench.admit", 600, 300),
        ("bench.prefill", 650, 200)]})
    dev = _plane(2, "/device:TPU:0", {
        "XLA Modules": [("jit_decode_step(7)", 150, 300),
                        ("jit_prefill_one(9)", 700, 100),
                        ("jit_decode_step(7)", 1200, 50)],   # after window
        "XLA Ops": [("fusion.1", 150, 200), ("fusion.2", 300, 150),
                    ("fusion.3", 700, 100), ("fusion.4", 1200, 50)]})
    return ProfileData.from_text_proto(host + "\n" + dev)


def test_union_merges_overlaps():
    iv = np.array([[5, 9], [0, 3], [2, 4], [9, 10], [12, 13]], float)
    assert xtrace._union(iv).tolist() == [[0, 4], [5, 10], [12, 13]]


def test_made_up_trace_reduces_to_the_known_answers():
    s = xtrace.summarize(_made_up())
    assert s.window_s == pytest.approx(1000e-9)
    assert s.devices == 1
    # ops 150-450 and 700-800 inside the window [100, 1100)
    assert s.busy_s == pytest.approx(400e-9)
    assert s.program("decode_step") == (pytest.approx(300e-9), 1)
    assert s.program("prefill_one") == (pytest.approx(100e-9), 1)
    # idle: 100-150 (decode), 450-700 (midpoint 575: telemetry), 800-1100
    # (midpoint 950: none)
    assert s.idle_by_span == {"decode": pytest.approx(50e-9),
                              "telemetry": pytest.approx(250e-9),
                              "none": pytest.approx(300e-9)}
    b = s.breakdown()
    assert b["device_ops"][0][0] == "jit_decode_step"
    assert b["idle_gaps"][0] == ["none", pytest.approx(300e-9)]


def test_a_trace_without_the_window_span_is_refused():
    dev = _plane(1, "/device:TPU:0", {"XLA Modules": [("jit_a", 0, 5)]})
    with pytest.raises(ValueError, match="bench.window"):
        xtrace.summarize(ProfileData.from_text_proto(dev))


def test_chip_slice_reduces_consistently():
    """A quarter second of qwen3-0.6b.chat-poisson on one TPU v5e: three
    prefills and four decode steps of 16 slots."""
    s = xtrace.summarize(xtrace.load(str(SLICE)))
    assert s.devices == 1 and s.window_s == pytest.approx(0.25)
    idle = sum(s.idle_by_span.values())
    assert s.busy_s + idle == pytest.approx(s.window_s, rel=1e-6)
    sec, calls = s.program("decode_step")
    assert calls == 4 and 0.02 < sec / calls < 0.06
    assert s.program("prefill_one")[1] == 3
    # the readers take their numbers from the same summary
    rec = Record(requests=[], seconds=0.25, stop=0.25,
                 steps=[Step(0.0, 0.04, [600] * 16)] * 4)
    run = stats.Run(record=rec, setup_s=0.0,
                    config=spec.config("qwen3-0.6b"),
                    peak=spec.peaks("TPU v5 lite"), trace=s)
    assert spec.reader("decode_step_ms.chat")(run) == \
        pytest.approx(sec / calls * 1e3)
    share = spec.reader("decode_roofline.chat")(run)
    assert 0 < share < 100
    assert spec.reader("device_idle_share.chat")(run) == \
        pytest.approx(100 * idle / s.window_s)
