"""One point of a cell's knee sweep: its engine at one offered rate.

  for r in 2.0 2.4 2.8 3.2; do python3 benchmarks/chip/sweep.py \
      --workload <cell> --seed <n> --seconds 30 --rate $r; done

Builds the cell's engines as a run does (one replica on each of its chips,
behind the mix's router), offers its traffic mix (Poisson arrivals, the
mix's lengths) at ``--rate`` for ``--seconds``, and prints one line:
requests due, first tokens inside the window, the queue left when it
closed, the tails of time to first token and of the gaps between tokens,
and tokens per second.  One process per rate, so that no rate inherits the
monitor's state.  The knee is the highest rate whose window ends with no
queue and a ``ttft_p90_ms`` within twice the lowest rate's; the cell's
rate is fixed from it once, and runs never search.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
if sys.path and Path(sys.path[0] or ".").resolve() == ROOT / "benchmarks" \
        / "chip":
    sys.path.pop(0)          # run as a script: its directory shadows nothing
for _p in (ROOT / "src", ROOT):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

from benchmarks.chip import run, spec, stats, traffic  # noqa: E402


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rate", type=float, required=True)
    args = ap.parse_args(argv)
    bench = spec.benchmark(ROOT)
    cell = spec.workload(bench, args.workload)
    cfg = spec.config(cell["config"])
    mix = dict(spec.traffic(cell["traffic"]), arrival="poisson")
    devices = run.devices_for(cell, args.workload, "sweep")
    if devices is None:
        return 2
    run.use_compile_cache()
    engines = run.build_engines(cfg, args.seed, devices[:cell["chips"]])
    loop = run.open_loop(engines, mix, args.seed)
    loop.warm_up(list(engines[0].sched.cfg.prefill_buckets))
    print(f"[sweep] set-up {time.perf_counter() - T_START:.1f} s")
    mix["rate_per_s"] = args.rate
    reqs = traffic.generate(mix, args.seed, args.seconds, cfg["vocab_size"])
    rec = loop.run(reqs, args.seconds, "none")
    got = [r for r in reqs if r.tokens]
    ttft = [r.token_times[0] - r.due for r in got]
    print(json.dumps({
        "rate": args.rate, "due": len(reqs), "first_tokens": len(got),
        "queue_at_close": sum(1 for r in reqs if r.submitted >= 0
                              and r.prefill_start < 0),
        "routed": [sum(r.replica == i for r in reqs)
                   for i in range(len(engines))],
        "ttft_p50_ms": (stats.quantile(ttft, 0.5) or 0) * 1e3,
        "ttft_p90_ms": (stats.quantile(ttft, 0.9) or 0) * 1e3,
        "itl_p95_ms": (stats.quantile(stats.token_gaps(rec), 0.95)
                       or 0) * 1e3,
        "tokens_per_s": stats.tokens_in_window(rec) / args.seconds,
        "steps": len(rec.steps),
        "occupancy": (sum(len(s.contexts) for s in rec.steps)
                      / max(len(rec.steps), 1)),
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
