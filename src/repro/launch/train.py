"""Training launcher: ``--arch`` selects any registry architecture.

CPU smoke by default (reduced config); on a TPU pod the same driver takes
``--mesh data,model`` extents and shards via MeshRules (scale is config).

  PYTHONPATH=src python -m repro.launch.train --arch llama3.2-3b \
      --steps 20 --batch 8 --seq 64 --ckpt /tmp/ck
"""

from __future__ import annotations

import argparse

import jax

from repro.configs import ARCHS
from repro.data import DataConfig, Prefetcher, SyntheticCorpus, pack_documents
from repro.models import build_model
from repro.training import AdamWConfig, TrainConfig, Trainer


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-3b",
                    choices=sorted(ARCHS))
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--micro", type=int, default=2)
    ap.add_argument("--ckpt", default="")
    ap.add_argument("--compress-grads", action="store_true")
    ap.add_argument("--full-config", action="store_true",
                    help="use the full published config (needs a pod)")
    ap.add_argument("--mesh", default="",
                    help="data,model extents for a sharded run, e.g. 4,2")
    args = ap.parse_args()

    cfg = ARCHS[args.arch]
    if not args.full_config:
        cfg = cfg.reduced()
    model = build_model(cfg)
    shard = None
    if args.mesh:
        from repro.launch.mesh import make_mesh
        from repro.parallel.sharding import MeshRules
        d, m = (int(x) for x in args.mesh.split(","))
        mesh = make_mesh((d, m), ("data", "model"))
        shard = MeshRules(mesh)
    params = model.init(jax.random.key(0))
    print(f"[train] {cfg.name}: ~{cfg.param_count():.2e} params, "
          f"{args.steps} steps")

    dcfg = DataConfig(vocab=cfg.vocab, seq_len=args.seq, batch=args.batch)
    data = Prefetcher(pack_documents(SyntheticCorpus(dcfg),
                                     args.steps + 4))
    tcfg = TrainConfig(
        steps=args.steps, n_micro=args.micro,
        compress_grads=args.compress_grads, ckpt_dir=args.ckpt,
        ckpt_every=max(args.steps // 4, 1),
        optimizer=AdamWConfig(warmup_steps=max(args.steps // 10, 1),
                              total_steps=args.steps))
    trainer = Trainer(model, params, tcfg, shard=shard)
    if trainer.maybe_restore():
        print(f"[train] resumed at step {trainer.step}")
    hist = trainer.run(data)
    for h in hist[:: max(len(hist) // 8, 1)]:
        print(f"  step {h['step']:4d} loss {h['loss']:.4f} "
              f"gnorm {h['grad_norm']:.2f} {h['sec'] * 1e3:.0f} ms")
    if hist:
        print(f"[train] done: loss {hist[0]['loss']:.3f} -> "
              f"{hist[-1]['loss']:.3f}")


if __name__ == "__main__":
    main()
