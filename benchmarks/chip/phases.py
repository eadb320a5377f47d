"""Device idle time put down to the engine's own phases, from a trace.

``xtrace.summarize`` gives each idle stretch of the device whole to the
``bench.*`` span of the harness open at its midpoint.  The engine writes
spans of its own into the same profiler trace (``engine.*``, listed in
``repro.serving.engine.SPANS``), on the device trace's clock.  This
reduction reads both families inside ``bench.window``:

- ``spans``: per ``engine.*`` name, its seconds and count;
- ``idle_by_phase``: each idle stretch of a device split over the host
  segments it overlaps, each segment labelled by the innermost span open
  over it (``engine.*`` by its full name, ``bench.*`` by its short name,
  ``none`` where neither is open).  The parts add up to window - busy;
- ``longest``: per ``engine.*`` name, its longest event's seconds and the
  share of that event in which the device was busy.  Near 1 the host
  waited on the device; near 0 the device waited on the host;
- ``clock_ms``: how well the device's events line up with the host's,
  from the k-th decode program and the k-th decode step's spans: ``lead``,
  program start less ``engine.decode.dispatch`` start, and ``lag``,
  ``engine.decode.readback`` end less program end, each as [least,
  median].  On one clock neither can be negative (a program starts after
  its dispatch, its tokens reach the host after it ends).  A device drawn
  early by d moves d from ``lead`` to ``lag``, and idle time near a
  phase's edge then falls to the phase before it.

Device busy time is counted as ``xtrace`` counts it.  On a trace that
``run.py --trace 1 --keep-trace <file>`` kept:

  python3 -m benchmarks.chip.phases <file>
"""

from __future__ import annotations

import argparse
import json
import re
import sys
from dataclasses import dataclass, field

import numpy as np

from benchmarks.chip import xtrace

ENGINE = "engine."
DECODE = "engine.decode"        # one span per decode step
PROGRAM = "decode_step"         # the decode program, as decode_step_ms


@dataclass
class Phases:
    window_s: float
    busy_s: float                         # mean over the devices used
    devices: int
    spans: dict = field(default_factory=dict)          # name -> [s, count]
    idle_by_phase: dict = field(default_factory=dict)  # label -> s, summed
    longest: dict = field(default_factory=dict)  # name -> [s, busy share]
    clock_ms: dict = field(default_factory=dict)   # lead, lag -> [ms, ms]

    def idle_under(self, name: str) -> float:
        """Idle seconds of a device while ``name`` or one of its children
        was the innermost span."""
        s = sum(v for k, v in self.idle_by_phase.items()
                if k == name or k.startswith(name + "."))
        return s / max(self.devices, 1)

    def per_step_ms(self, name: str) -> float | None:
        """Idle under ``name`` per decode step, in ms."""
        steps = self.spans.get(DECODE, (0.0, 0))[1]
        if not steps or not self.devices:
            return None
        return self.idle_under(name) / steps * 1e3

    def mean_ms(self, name: str) -> float | None:
        sec, n = self.spans.get(name, (0.0, 0))
        return sec / n * 1e3 if n else None

    def metrics(self) -> dict:
        """Device idle per decode step while the host is inside ``_step``
        and inside ``_flush_telemetry``, and the mean host time of a
        prefill (dispatch, cache insert, first-token readback)."""
        return {"decode_host_idle_ms": self.per_step_ms(DECODE),
                "monitor_idle_ms": self.per_step_ms("engine.flush"),
                "prefill_host_ms": self.mean_ms("engine.prefill")}


def busy_before(iv: np.ndarray, t: np.ndarray) -> np.ndarray:
    """Busy time before each of ``t``, given merged, sorted intervals."""
    if not len(iv):
        return np.zeros(len(t))
    cum = np.concatenate([[0.0], np.cumsum(iv[:, 1] - iv[:, 0])])
    k = np.searchsorted(iv[:, 0], t, side="right")
    after = np.where(k > 0, iv[np.maximum(k - 1, 0), 1] - t, 0.0)
    return cum[k] - np.maximum(after, 0.0)


def segments(spans: list, lo: float, hi: float):
    """Cut [lo, hi] at every edge of a span inside it; label each piece by
    the innermost span open over it (the latest to start, the shorter on
    a tie), ``none`` where none is.  Returns the cuts and the labels."""
    cuts = sorted({lo, hi} | {t for s, e, _ in spans for t in (s, e)
                              if lo < t < hi})
    order = sorted(spans, key=lambda sp: sp[0])
    active: list = []
    labels = []
    k = 0
    for a in cuts[:-1]:
        while k < len(order) and order[k][0] <= a:
            active.append(order[k])
            k += 1
        active = [sp for sp in active if sp[1] > a]
        labels.append(max(active, key=lambda sp: (sp[0], -sp[1]))[2]
                      if active else "none")
    return np.array(cuts, float), labels


def reduce(pd) -> Phases:
    window, phases, engine, tpus = None, [], [], []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == xtrace.WINDOW:
                        window = (ev.start_ns, ev.end_ns)
                    elif ev.name.startswith("bench."):
                        phases.append((ev.start_ns, ev.end_ns, ev.name[6:]))
                    elif ev.name.startswith(ENGINE):
                        phases.append((ev.start_ns, ev.end_ns, ev.name))
                        engine.append(phases[-1])
        elif re.fullmatch(r"/device:TPU:\d+", plane.name):
            tpus.append(plane)
    if window is None:
        raise ValueError(f"the trace has no {xtrace.WINDOW} span")
    w0, w1 = window
    ivs, progs = [], []
    for plane in tpus:
        lines = {ln.name: ln for ln in plane.lines}
        mods = lines.get("XLA Modules")
        ops = lines.get("XLA Ops") or mods
        # a device counts where a program started inside the window
        if mods is None or not any(w0 <= e.start_ns < w1
                                   for e in mods.events):
            continue
        iv = np.array([(max(e.start_ns, w0), min(e.end_ns, w1))
                       for e in ops.events
                       if e.end_ns > w0 and e.start_ns < w1], float)
        ivs.append(xtrace._union(iv.reshape(-1, 2)))
        if len(ivs) == 1:
            progs = sorted((e.start_ns, e.end_ns) for e in mods.events
                           if PROGRAM in e.name and w0 <= e.start_ns < w1)
    cuts, labels = segments(phases, w0, w1)
    idle: dict[str, float] = {}
    for iv in ivs:
        gaps = np.diff(cuts) - np.diff(busy_before(iv, cuts))
        for lab, g in zip(labels, gaps):
            idle[lab] = idle.get(lab, 0.0) + float(g) * 1e-9
    spans: dict[str, list] = {}
    longest: dict[str, list] = {}
    for s, e, name in engine:
        if not w0 <= s < w1:
            continue
        tot = spans.setdefault(name, [0.0, 0])
        tot[0] += (e - s) * 1e-9
        tot[1] += 1
        if name not in longest or e - s > longest[name][0] * 1e9:
            edges = np.array([s, e], float)
            share = [float(np.diff(busy_before(iv, edges))[0]) / (e - s)
                     if e > s else 0.0 for iv in ivs]
            longest[name] = [(e - s) * 1e-9,
                             sum(share) / len(share) if share else 0.0]
    busy_s = sum(float(np.sum(iv[:, 1] - iv[:, 0])) for iv in ivs) * 1e-9
    step = {n: sorted((s, e) for s, e, name in engine
                      if name == n and w0 <= s < w1)
            for n in ("engine.decode.dispatch", "engine.decode.readback")}
    return Phases(window_s=(w1 - w0) * 1e-9,
                  busy_s=busy_s / max(len(ivs), 1), devices=len(ivs),
                  spans=spans, idle_by_phase=idle, longest=longest,
                  clock_ms=clock_ms(progs, step["engine.decode.dispatch"],
                                    step["engine.decode.readback"]))


def clock_ms(progs: list, dispatch: list, readback: list) -> dict:
    """``lead`` and ``lag`` as [least, median] in ms, pairing the k-th
    program with the k-th step's spans; empty unless all three count the
    same steps."""
    if not progs or not len(progs) == len(dispatch) == len(readback):
        return {}
    p, d, r = (np.array(x, float) for x in (progs, dispatch, readback))
    out = {}
    for key, gap in (("lead", p[:, 0] - d[:, 0]), ("lag", r[:, 1] - p[:, 1])):
        out[key] = [float(gap.min()) * 1e-6, float(np.median(gap)) * 1e-6]
    return out


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("trace", help="an .xplane.pb, or one gzipped")
    args = ap.parse_args(argv)
    ph = reduce(xtrace.load(args.trace))
    per = max(ph.devices, 1)
    idle = sum(ph.idle_by_phase.values()) / per
    print(f"[trace] window {ph.window_s!r} s, busy {ph.busy_s!r} s, idle "
          f"by phase adds up to {idle!r} s against window - busy "
          f"{ph.window_s - ph.busy_s!r} s")
    print("[trace] engine spans: name count mean_ms idle_s")
    for name in sorted(ph.spans):
        sec, n = ph.spans[name]
        print(f"  {name} {n} {sec / n * 1e3:.4f} "
              f"{ph.idle_by_phase.get(name, 0.0) / per:.4f}")
    print(f"[trace] clock, ms [least, median]: {ph.clock_ms}")
    print("[trace] longest: name ms device_busy_%")
    for name in sorted(ph.longest):
        sec, share = ph.longest[name]
        print(f"  {name} {sec * 1e3:.3f} {share * 100:.1f}")
    print(json.dumps({"window_s": ph.window_s, "busy_s": ph.busy_s,
                      "devices": ph.devices, "metrics": ph.metrics(),
                      "idle_by_phase": {k: v / per for k, v in sorted(
                          ph.idle_by_phase.items(), key=lambda kv: -kv[1])},
                      "spans": ph.spans, "longest": ph.longest,
                      "clock_ms": ph.clock_ms}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
