"""Reduction of a profiler trace (``.xplane.pb``) to the per-layer numbers.

Device planes are ``/device:TPU:<n>``.  A device is busy while any event of
its ``XLA Ops`` line runs (the union of their intervals); a program's time
is the sum of its events on the ``XLA Modules`` line.  Only what falls
inside the harness's ``bench.window`` span counts.  Each idle stretch of a
device is put down to the innermost ``bench.*`` span the host had open at
its midpoint (``none`` where it had none): that is what the host was doing
while the device waited.
"""

from __future__ import annotations

import bisect
import contextlib
import glob
import gzip
import os
import re
import shutil
import tempfile
from dataclasses import dataclass, field

import jax
import numpy as np

WINDOW = "bench.window"
_SUFFIX = re.compile(r"\(\d+\)$")


@dataclass
class TraceSummary:
    window_s: float
    busy_s: float                         # mean over the devices used
    devices: int
    modules: dict = field(default_factory=dict)   # name -> [seconds, calls]
    idle_by_span: dict = field(default_factory=dict)  # name -> seconds

    def program(self, part: str) -> tuple[float, int]:
        """Total seconds and calls of programs whose name holds ``part``,
        summed over the devices."""
        s, n = 0.0, 0
        for name, (sec, calls) in self.modules.items():
            if part in name:
                s, n = s + sec, n + calls
        return s, n

    def breakdown(self, top: int = 10) -> dict:
        per = max(self.devices, 1)
        ops = sorted(((k, v[0] / per) for k, v in self.modules.items()),
                     key=lambda kv: -kv[1])[:top]
        gaps = sorted(((k, v / per) for k, v in self.idle_by_span.items()),
                      key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in gaps]}


def _union(iv: np.ndarray) -> np.ndarray:
    """Merge (n, 2) [start, end) intervals; returns the merged ones."""
    if len(iv) == 0:
        return iv
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    ends = np.maximum.accumulate(iv[:, 1])
    new = np.ones(len(iv), bool)
    new[1:] = iv[1:, 0] > ends[:-1]
    starts = iv[new, 0]
    idx = np.flatnonzero(new)
    last = np.append(idx[1:] - 1, len(iv) - 1)
    return np.stack([starts, ends[last]], axis=1)


def _host_segments(spans: list[tuple[float, float, str]]):
    """Elementary host segments labelled by the innermost open span."""
    cuts = sorted({t for s, e, _ in spans for t in (s, e)})
    labels = []
    order = sorted(spans)
    active: list[tuple[float, float, str]] = []
    k = 0
    for a, b in zip(cuts[:-1], cuts[1:]):
        while k < len(order) and order[k][0] <= a:
            active.append(order[k])
            k += 1
        active = [s for s in active if s[1] > a]
        labels.append(max(active)[2] if active else "none")
    return cuts, labels


def summarize(pd) -> TraceSummary:
    spans, window = [], None
    devices: dict[str, dict] = {}
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == WINDOW:
                        window = (ev.start_ns, ev.end_ns)
                    elif ev.name.startswith("bench."):
                        spans.append((ev.start_ns, ev.end_ns, ev.name[6:]))
        elif re.fullmatch(r"/device:TPU:\d+", plane.name):
            lines = {ln.name: ln for ln in plane.lines}
            devices[plane.name] = lines
    if window is None:
        raise ValueError(f"the trace has no {WINDOW} span")
    w0, w1 = window
    cuts, labels = _host_segments(spans)
    used, busy_total = 0, 0.0
    modules: dict[str, list] = {}
    idle: dict[str, float] = {}
    for lines in devices.values():
        mods = lines.get("XLA Modules")
        ops = lines.get("XLA Ops") or mods
        if mods is None:
            continue
        n_mod = 0
        for ev in mods.events:
            if ev.start_ns < w0 or ev.start_ns >= w1:
                continue
            name = _SUFFIX.sub("", ev.name)
            m = modules.setdefault(name, [0.0, 0])
            m[0] += ev.duration_ns * 1e-9
            m[1] += 1
            n_mod += 1
        if not n_mod:
            continue
        used += 1
        iv = np.array([(max(e.start_ns, w0), min(e.end_ns, w1))
                       for e in ops.events
                       if e.end_ns > w0 and e.start_ns < w1], float)
        iv = _union(iv.reshape(-1, 2))
        busy_total += float(np.sum(iv[:, 1] - iv[:, 0])) * 1e-9
        # idle stretches: before the first op, between ops, after the last
        edges = np.concatenate([[w0], iv.reshape(-1), [w1]]).reshape(-1, 2)
        for a, b in edges:
            if b <= a:
                continue
            i = bisect.bisect_right(cuts, (a + b) / 2) - 1
            lab = labels[i] if 0 <= i < len(labels) else "none"
            idle[lab] = idle.get(lab, 0.0) + (b - a) * 1e-9
    return TraceSummary(window_s=(w1 - w0) * 1e-9,
                        busy_s=busy_total / max(used, 1), devices=used,
                        modules=modules, idle_by_span=idle)


def load(path: str):
    from jax.profiler import ProfileData
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            return ProfileData.from_serialized_xspace(f.read())
    return ProfileData.from_file(path)


@contextlib.contextmanager
def traced(keep: str | None = None):
    """Profile the block; yields a holder whose ``summary`` is filled in
    once the block ends.  The trace goes to a temporary directory that is
    removed, or is copied, gzipped, to ``keep``."""
    holder = type("Holder", (), {"summary": None})()
    tmp = tempfile.mkdtemp(prefix="benchtrace")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    try:
        jax.profiler.start_trace(tmp, profiler_options=opts)
        try:
            yield holder
        finally:
            jax.profiler.stop_trace()
        path = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                         recursive=True)[0]
        if keep:
            with open(path, "rb") as src, gzip.open(keep, "wb") as dst:
                shutil.copyfileobj(src, dst)
        holder.summary = summarize(load(path))
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
