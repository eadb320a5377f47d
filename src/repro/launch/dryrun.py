import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell on the
production meshes using ShapeDtypeStruct inputs (no allocation), then record
memory_analysis / cost_analysis / collective-bytes for the roofline.

The two lines above MUST stay first: jax locks the device count on first
init, and only the dry-run wants 512 placeholder devices.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch llama3.2-3b \
      --shape train_4k --mesh single
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both \
      --out artifacts/dryrun
"""

import argparse
import json
import re
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs import ARCHS, SHAPES, cells
from repro.launch.mesh import make_production_mesh
from repro.models import build_model
from repro.parallel.sharding import MeshRules
from repro.training.optimizer import AdamWConfig, adamw_init, adamw_update

COLLECTIVE_RE = re.compile(
    r"=\s+(\S+)\[([\d,]*)\][^=]*?\b"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")

DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "u64": 8,
    "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1,
    "f8e4m3fn": 1, "f8e5m2": 1,
}


def collective_bytes(hlo_text: str) -> dict:
    """Sum result-shape bytes of every collective op in the partitioned HLO."""
    out: dict[str, float] = {}
    for m in COLLECTIVE_RE.finditer(hlo_text):
        dt, dims, op = m.group(1), m.group(2), m.group(3)
        nbytes = DTYPE_BYTES.get(dt, 4)
        for d in dims.split(","):
            if d:
                nbytes *= int(d)
        out[op] = out.get(op, 0.0) + nbytes
    return out


def _spec_tree(rules: MeshRules, shape_tree, spec_fn):
    specs = spec_fn(shape_tree)
    return rules.shardings_of(specs)


def lower_cell(arch: str, shape_name: str, mesh, **rules_kw) -> dict:
    """Build + lower + compile one cell; returns the roofline record."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    cfg = ARCHS[arch]
    shape = SHAPES[shape_name]
    model = build_model(cfg)
    rules = MeshRules(mesh, **{"fsdp": True, **rules_kw})
    key = jax.random.key(0)
    t0 = time.time()

    params_shape = jax.eval_shape(model.init, key)
    param_sh = _spec_tree(rules, params_shape, rules.param_specs)
    specs = model.input_specs(shape)
    opt_cfg = AdamWConfig()

    if shape.kind == "train":
        batch_shape = specs["batch"]
        batch_sh = rules.shardings_of(rules.batch_specs(batch_shape))
        opt_shape = jax.eval_shape(adamw_init, params_shape)
        opt_sh = {
            "m": _spec_tree(rules, params_shape, rules.param_specs),
            "v": _spec_tree(rules, params_shape, rules.param_specs),
            "step": NamedSharding(mesh, P()),
        }

        def train_step(params, opt_state, batch):
            loss, grads = jax.value_and_grad(
                lambda p: model.loss(p, batch, shard=rules))(params)
            params, opt_state, metrics = adamw_update(
                opt_cfg, grads, opt_state, params)
            return params, opt_state, loss, metrics

        fn = jax.jit(
            train_step,
            in_shardings=(param_sh, opt_sh, batch_sh),
            out_shardings=(param_sh, opt_sh, NamedSharding(mesh, P()),
                           NamedSharding(mesh, P())),
            donate_argnums=(0, 1),
        )
        with mesh:
            lowered = fn.lower(params_shape, opt_shape, batch_shape)
    elif shape.kind == "prefill":
        cache_shape = specs["cache"]
        cache_sh = rules.shardings_of(rules.cache_specs(cache_shape))
        tok_sh = rules.shardings_of(rules.batch_specs(
            {"tokens": specs["tokens"]}))["tokens"]
        frontend = specs.get("frontend")

        def prefill(params, tokens, cache, frontend=None):
            return model.prefill(params, tokens, cache, shard=rules,
                                 frontend=frontend)

        fe_sh = None
        if frontend is not None:
            fe_sh = rules.shardings_of(
                rules.batch_specs({"frontend": frontend}))["frontend"]
        fn = jax.jit(
            prefill,
            in_shardings=(param_sh, tok_sh, cache_sh, fe_sh),
            out_shardings=None,
            donate_argnums=(2,),
        )
        with mesh:
            lowered = fn.lower(params_shape, specs["tokens"], cache_shape,
                               frontend)
    else:  # decode
        cache_shape = specs["cache"]
        cache_sh = rules.shardings_of(rules.cache_specs(cache_shape))
        tok_sh = rules.shardings_of(rules.batch_specs(
            {"tokens": specs["tokens"]}))["tokens"]

        def step(params, tokens, cache):
            return model.decode_step(params, tokens, cache, shard=rules)

        fn = jax.jit(
            step,
            in_shardings=(param_sh, tok_sh, cache_sh),
            out_shardings=None,
            donate_argnums=(2,),
        )
        with mesh:
            lowered = fn.lower(params_shape, specs["tokens"], cache_shape)

    lower_s = time.time() - t0
    t1 = time.time()
    compiled = lowered.compile()
    compile_s = time.time() - t1

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    coll = collective_bytes(hlo)

    n_dev = mesh.devices.size
    record = {
        "arch": arch,
        "shape": shape_name,
        "mesh": "x".join(str(s) for s in mesh.devices.shape),
        "n_devices": int(n_dev),
        "ok": True,
        "lower_s": round(lower_s, 1),
        "compile_s": round(compile_s, 1),
        # cost_analysis of the partitioned executable = per-device program
        "flops_per_device": float(cost.get("flops", 0.0)),
        "bytes_per_device": float(cost.get("bytes accessed", 0.0)),
        "collective_bytes": coll,
        "memory": {
            "argument_bytes": int(getattr(mem, "argument_size_in_bytes", 0)),
            "output_bytes": int(getattr(mem, "output_size_in_bytes", 0)),
            "temp_bytes": int(getattr(mem, "temp_size_in_bytes", 0)),
            "generated_code_bytes": int(
                getattr(mem, "generated_code_size_in_bytes", 0)),
        },
        "rules": rules_kw,
        "params_total": ARCHS[arch].param_count(),
        "params_active": ARCHS[arch].active_param_count(),
        "tokens": SHAPES[shape_name].global_batch * (
            SHAPES[shape_name].seq_len
            if SHAPES[shape_name].kind == "train" else
            (SHAPES[shape_name].seq_len
             if SHAPES[shape_name].kind == "prefill" else 1)),
    }
    return record


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="artifacts/dryrun")
    ap.add_argument("--list", action="store_true")
    args = ap.parse_args()

    if args.list:
        for arch, shape, status in cells():
            print(f"{arch:24s} {shape:12s} {status}")
        return

    os.makedirs(args.out, exist_ok=True)
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    todo = []
    if args.all:
        todo = [(a, s) for a, s, st in cells() if st == "run"]
    else:
        todo = [(args.arch, args.shape)]

    n_fail = 0
    for multi in meshes:
        mesh = make_production_mesh(multi_pod=multi)
        tag = "multi" if multi else "single"
        for arch, shape in todo:
            out_path = os.path.join(args.out, f"{arch}__{shape}__{tag}.json")
            if os.path.exists(out_path):
                print(f"[skip-cached] {arch} {shape} {tag}")
                continue
            print(f"[dryrun] {arch} {shape} mesh={tag} ...", flush=True)
            try:
                rec = lower_cell(arch, shape, mesh)
                print(f"  ok: compile={rec['compile_s']}s "
                      f"flops/dev={rec['flops_per_device']:.3e} "
                      f"coll={ {k: f'{v:.2e}' for k, v in rec['collective_bytes'].items()} }",
                      flush=True)
            except Exception as e:  # noqa: BLE001 — record and continue
                rec = {"arch": arch, "shape": shape, "mesh": tag,
                       "ok": False, "error": f"{type(e).__name__}: {e}",
                       "trace": traceback.format_exc()[-2000:]}
                n_fail += 1
                print(f"  FAIL: {rec['error']}", flush=True)
            with open(out_path, "w") as f:
                json.dump(rec, f, indent=1)
    print(f"done; failures={n_fail}")
    if n_fail:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
