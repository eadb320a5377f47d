"""Readings that set a cell's correctness limit: the served path's widest
gap and that of its control, the reference with fp8 weights.

  python3 benchmarks/chip/control.py --workload <cell> --seconds <s> \
      --seeds 101,102,103

For each seed, in one process: a whole run of the cell (``run.run_cell``)
with the control in the served path's place: at the same positions of the
same sampled requests, the gap of the token that the fp8-weight (e4m3,
per-tensor scaled) reference puts first, judged by the run's own rule and
limit, so ``correct`` has to read false.  The served path's reading and
judgement come beside it.  The limit lies between the served path's
largest reading over a dozen seeds or more and the control's smallest.
The benchmark's own runs never run the control.  One JSON line per seed.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
if sys.path and Path(sys.path[0] or ".").resolve() == ROOT / "benchmarks" \
        / "chip":
    sys.path.pop(0)
for _p in (ROOT / "src", ROOT):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

from benchmarks.chip import run, spec  # noqa: E402


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    bench = spec.benchmark(ROOT)
    cell = spec.workload(bench, args.workload)
    cfg = spec.config(cell["config"])
    mix = spec.traffic(cell["traffic"])
    devices = run.devices_for(cell, args.workload, "control")
    if devices is None:
        return 2
    run.use_compile_cache()
    for seed in (int(s) for s in args.seeds.split(",")):
        res = run.run_cell(cell, cfg, mix, seed=seed, seconds=args.seconds,
                           trace=False, devices=devices, bench=bench,
                           t_start=time.perf_counter(), control=True,
                           log=lambda *a: print(*a, file=sys.stderr))
        for name, c in res["checks"].items():
            print(f"[check] seed {seed} control {name} {c['value']!r} "
                  f"limit {c['limit']!r}", file=sys.stderr)
        print(json.dumps({"seed": seed, "correct": res["correct"],
                          "control_gap": res["checks"]["widest_gap"]["value"],
                          "served_correct": res["served"]["correct"],
                          "served_gap": res["served"]["widest_gap"],
                          "compared": res["checks"]["compared_tokens"]
                          ["value"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
