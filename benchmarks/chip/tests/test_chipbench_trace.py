"""The trace reduction, on a made-up trace with known answers and on a
slice of a trace recorded on a TPU v5e (testdata/)."""

import _paths  # noqa: F401

import time

import jax
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


def test_a_replicas_idle_time_is_put_down_to_its_own_thread():
    """Two replicas, one on each device: device n's idle time goes to the
    spans of the thread that serves it (``bench.d<n>.*``), and to spans of
    no replica, never to the other replica's."""
    host = _plane(1, "/host:CPU", {"python3": [
        ("bench.window", 100, 1000), ("bench.d0.decode", 100, 500),
        ("bench.d1.telemetry", 300, 200), ("bench.d1.admit", 600, 400),
        ("bench.submit", 900, 50)]})
    dev0 = _plane(2, "/device:TPU:0", {
        "XLA Modules": [("jit_decode_step(7)", 150, 300)],
        "XLA Ops": [("fusion.1", 150, 300)]})
    dev1 = _plane(3, "/device:TPU:1", {
        "XLA Modules": [("jit_decode_step(7)", 200, 50)],
        "XLA Ops": [("fusion.1", 200, 50)]})
    s = xtrace.summarize(ProfileData.from_text_proto(
        "\n".join((host, dev0, dev1))))
    assert s.devices == 2 and s.busy_s == pytest.approx(175e-9)
    assert s.program("decode_step") == (pytest.approx(350e-9), 2)
    # device 0 idle: 100-150 (decode), 450-1100 (midpoint 775: none of its
    # own; d1.admit is the other thread's).  Device 1 idle: 100-200
    # (midpoint 150: none of its own), 250-1100 (midpoint 675: admit)
    assert s.idle_by_span == {"decode": pytest.approx(50e-9),
                              "none": pytest.approx(750e-9),
                              "admit": pytest.approx(850e-9)}
    assert s.phases.devices == 2


def test_a_part_of_the_block_is_traced_with_its_own_window(tmp_path):
    """``traced(part=(start, end))``: the profiler starts ``start`` seconds
    into the block; the trace's window span covers [start, end], and a
    span the block opened before the start is not in it."""
    keep = tmp_path / "part.xplane.pb.gz"
    with xtrace.traced(str(keep), part=(0.3, 0.6)) as holder:
        with jax.profiler.TraceAnnotation("bench.early"):
            time.sleep(0.1)
        time.sleep(0.4)
        with jax.profiler.TraceAnnotation("bench.late"):
            time.sleep(0.1)
        time.sleep(0.2)
    names = {ev.name for plane in xtrace.load(str(keep)).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for ev in line.events}
    assert "bench.late" in names and "bench.early" not in names
    assert 0.25 < holder.summary.window_s < 0.35
