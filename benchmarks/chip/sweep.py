"""One point of a cell's knee sweep: its engine at one offered rate.

  for r in 2.0 2.4 2.8 3.2; do python3 benchmarks/chip/sweep.py \
      --workload <cell> --seed <n> --seconds 30 --rate $r; done

Builds the cell's engine, offers its traffic mix (Poisson arrivals, the
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

import jax  # noqa: E402

from benchmarks.chip import run, spec, stats, traffic  # noqa: E402
from benchmarks.chip.serve_loop import OpenLoop  # noqa: E402


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
    device = jax.devices()[0]
    if device.platform != "tpu" or cell["chips"] != 1:
        print("sweep: needs a TPU and a one-chip cell", file=sys.stderr)
        return 2
    run.use_compile_cache()
    engine = run.build_engine(cfg, args.seed, device)
    loop = OpenLoop(engine)
    loop.warm_up(list(engine.sched.cfg.prefill_buckets))
    print(f"[sweep] set-up {time.perf_counter() - T_START:.1f} s")
    mix["rate_per_s"] = args.rate
    reqs = traffic.generate(mix, args.seed, args.seconds, cfg["vocab_size"])
    rec = loop.run(reqs, args.seconds, "none")
    got = [r for r in reqs if r.tokens]
    ttft = [r.token_times[0] - r.due for r in got]
    print(json.dumps({
        "rate": args.rate, "due": len(reqs), "first_tokens": len(got),
        "queue_at_close": len(engine.sched.queue),
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
