"""Serving launcher: continuous-batching engine + DPU-analog telemetry.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-0.6b \
      --requests 24 --rate 200 --report

Without ``--full-config`` the model is the reduced CPU smoke config; with
it, the published widths (qwen3-0.6b fits one TPU v5e chip whole):

  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-0.6b \
      --full-config --slots 8 --max-seq 2048
"""

from __future__ import annotations

import argparse
import json
import os
import random
from pathlib import Path

import jax

from repro.configs import ARCHS
from repro.models import build_model
from repro.models.config import ModelConfig
from repro.serving import EngineConfig, InferenceEngine, ServeRequest

CHECKOUT = Path(__file__).resolve().parents[3]


def enable_compile_cache(root: Path = CHECKOUT) -> None:
    """Persistent compilation cache for an entry point.

    JAX itself reads ``JAX_COMPILATION_CACHE_DIR`` when it is set; only
    otherwise is the cache put in ``<root>/.jax_cache``.  The path is part
    of what a later run must find again, so it is fixed: no temp name, pid
    or timestamp.  Called from entry points only, never at import."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(root / ".jax_cache"))


def build_engine(cfg: ModelConfig, *, seed: int = 0, device=None,
                 slots: int = 4, max_seq: int = 128, page_size: int = 16,
                 **engine_kw) -> InferenceEngine:
    """Model, seeded random params and engine for ``cfg``.

    The params are put on ``device`` (default: JAX's default device) and
    the engine places its caches and inputs beside them.  The page pool
    holds ``slots`` full ``max_seq`` sequences.  The params are made op by
    op: at published width a jitted init takes a minute to compile for a
    TPU, the ops a few seconds."""
    model = build_model(cfg)
    params = jax.device_put(model.init(jax.random.key(seed)), device)
    return InferenceEngine(model, params, EngineConfig(
        max_slots=slots, max_seq=max_seq, page_size=page_size,
        n_pages=max_seq * slots // page_size, **engine_kw))


def make_requests(n: int, vocab: int, *, seed: int = 0,
                  rate: float = 250.0, prompt_len: tuple[int, int],
                  new_tokens: tuple[int, int]) -> list[ServeRequest]:
    """Seeded Poisson arrivals; prompt lengths and new-token budgets drawn
    uniformly from the half-open ranges ``[lo, hi)``."""
    rng = random.Random(seed)
    t = 0.0
    reqs = []
    for i in range(n):
        reqs.append(ServeRequest(
            req_id=i, arrival=t,
            prompt=[rng.randrange(vocab)
                    for _ in range(rng.randrange(*prompt_len))],
            max_new_tokens=rng.randrange(*new_tokens)))
        t += rng.expovariate(rate)
    return reqs


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b", choices=sorted(ARCHS))
    ap.add_argument("--full-config", action="store_true",
                    help="serve the published widths instead of the "
                         "reduced smoke config")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--rate", type=float, default=250.0,
                    help="request arrivals per second")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=128)
    ap.add_argument("--static-batching", action="store_true",
                    help="start in the pathological no-remap mode")
    ap.add_argument("--no-mitigate", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--report", action="store_true",
                    help="dump the full JSON report")
    args = ap.parse_args()

    enable_compile_cache()
    cfg = ARCHS[args.arch]
    if not args.full_config:
        cfg = cfg.reduced()
    engine = build_engine(cfg, seed=args.seed, slots=args.slots,
                          max_seq=args.max_seq,
                          mitigate=not args.no_mitigate)
    if args.static_batching:
        engine.sched.set_continuous(False)

    reqs = make_requests(args.requests, cfg.vocab, seed=args.seed,
                         rate=args.rate,
                         prompt_len=(8, args.max_seq // 3),
                         new_tokens=(4, args.max_seq // 4))
    rep = engine.run(reqs, max_steps=args.requests * args.max_seq)
    print(f"[serve] {cfg.name}: {rep['completed']}/{args.requests} done, "
          f"{rep['tokens_per_step']:.2f} tok/step, "
          f"p50 {rep['p50_latency'] * 1e3:.1f} ms, "
          f"p99 {rep['p99_latency'] * 1e3:.1f} ms, "
          f"ttft p50 {rep['p50_ttft'] * 1e3:.1f} ms")
    tel = rep.get("telemetry", {})
    print(f"[telemetry] {tel.get('events', 0)} events, "
          f"findings {tel.get('findings_by_row', {})}, "
          f"actions {[a for _, a, _ in tel.get('actions', [])]}")
    if args.report:
        print(json.dumps(rep, indent=1, default=str))


if __name__ == "__main__":
    main()
