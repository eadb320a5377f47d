"""Chip smoke test: qwen3-0.6b at its published widths, served on a TPU.

  python chip_smoke.py                # one chip
  python chip_smoke.py --replicas 4   # four one-chip replicas (4 chips)

Everything runs in this one process (a chip belongs to one process).
Without ``--replicas`` the phases are:

  kernels    the flash and paged attention Pallas kernels, compiled for
             the chip (interpret=False), at qwen3-0.6b shapes against the
             jnp oracles in kernels/ref.py
  serve      8 seeded requests (prompts of 64-1000 tokens, 16-64 new
             tokens) through InferenceEngine.run, built by the serving
             launcher's build_engine: all 28 layers, d_model 1024, vocab
             151936, bf16, seeded random weights; 8 slots x 2048 tokens,
             telemetry on, control "dpu"
  reference  the prefill and every decode logits row of two served
             requests against a float32 full forward pass (no cache,
             params cast to float32, matmul precision "highest") of the
             exact tokens the engine consumed, left pads included; then a
             copy of the engine with fp8 weights must fail that comparison

``--replicas N`` runs only the replica path and what it is compared with:
the same requests served by one engine, then routed by a ReplicaSet
(join_shortest_queue) over N engines whose params, caches and inputs sit
on devices 0..N-1; every request's logits must match the one-replica run.

Each phase raises on failure, so the process exits nonzero.  Only when
every phase passed is the last line of stdout
{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}.
Without a TPU it exits 1 before any phase.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import statistics
import sys
import time
from functools import partial
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import ARCHS  # noqa: E402
from repro.kernels import ref  # noqa: E402
from repro.kernels.flash_attention import flash_attention_kernel  # noqa: E402
from repro.kernels.paged_attention import paged_attention_kernel  # noqa: E402
from repro.launch.serve import (  # noqa: E402
    build_engine,
    enable_compile_cache,
    make_requests,
)
from repro.models import build_model  # noqa: E402
from repro.serving import ReplicaSet  # noqa: E402

ARCH = "qwen3-0.6b"


@dataclasses.dataclass(frozen=True)
class Traffic:
    slots: int = 8
    max_seq: int = 2048
    requests: int = 8
    prompt_len: tuple[int, int] = (64, 1001)     # [lo, hi)
    new_tokens: tuple[int, int] = (16, 65)
    rate: float = 250.0                          # arrivals per second


# --- tolerances -------------------------------------------------------------
# Served logits (bf16 engine) against the float32 reference.  The weights
# are the same bf16 values in both, so the difference is the engine's bf16
# rounding of activations and of its KV cache (unit roundoff 2^-9) summed
# over 28 layers, plus the bf16 rounding of the logits themselves.
# rel_l2 = ||engine - reference|| / ||reference|| per logits row.  The
# same comparison on the CPU at full depth (28 layers, d_model 256 and 512,
# vocab 8192) gave 1.7e-2 for bf16 and 1.3e-1 for fp8 (e4m3) weights, which
# round to 2^-4: the bound sits between them, so the stated bf16 passes and
# a lower precision fails (the reference phase checks the fp8 side).  A
# float16 engine is more precise than bf16 (2.4e-3 there) and passes.
REL_L2_TOL = 4e-2
# max |engine - reference| over a row, in units of the reference row's
# standard deviation: bf16 gave 8e-2 and fp8 weights 5.3e-1 on that run;
# a single logit thrown off by a wrong mask or cache slot exceeds it.
MAX_ABS_TOL = 0.25
# Kernel outputs (bf16) against the jnp oracles (float32 inside, bf16
# out): two bf16 roundings of the output (2^-9 each) and the kernels'
# float32 online softmax leave ~0.3%; a wrong mask, page or block is O(1).
KERNEL_REL_TOL = 1e-2


def rel_err(got, want) -> float:
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def logit_error(got: np.ndarray, want: np.ndarray) -> dict:
    """Worst rel_l2 and max-abs/std over the rows of (rows, vocab)."""
    d = got - want
    rel = np.linalg.norm(d, axis=-1) / np.linalg.norm(want, axis=-1)
    mx = np.abs(d).max(axis=-1) / want.std(axis=-1)
    return {"rows": len(got), "rel_l2": float(rel.max()),
            "max_abs_over_std": float(mx.max())}


def within_tolerance(err: dict) -> bool:
    return err["rel_l2"] <= REL_L2_TOL \
        and err["max_abs_over_std"] <= MAX_ABS_TOL


class LogitTap:
    """Engine ``on_logits`` observer: for the chosen requests, every token
    the engine consumed (the bucketed prompt, then each decode input) and
    every logits row it computed (prefill, then one per decode step)."""

    def __init__(self, req_ids, max_rows: int | None = None) -> None:
        self.tokens = {i: [] for i in req_ids}
        self.logits = {i: [] for i in req_ids}
        self.max_rows = max_rows

    def __call__(self, rows: dict, tokens: np.ndarray, logits) -> None:
        for r, req in rows.items():
            seen = self.logits.get(req.req_id)
            if seen is None or (self.max_rows is not None
                                and len(seen) >= self.max_rows):
                continue
            self.tokens[req.req_id].extend(int(t) for t in tokens[r])
            seen.append(np.asarray(logits[r], np.float32))

    def rows(self, req_id: int) -> np.ndarray:
        return np.stack(self.logits[req_id])


def _timed(fn, *args, reps: int = 5) -> tuple[float, float]:
    """(first call, median of ``reps`` later calls), each to completion."""
    t = time.perf_counter()
    jax.block_until_ready(fn(*args))
    first = time.perf_counter() - t
    times = []
    for _ in range(reps):
        t = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t)
    return first, statistics.median(times)


def check_params_are_arguments(engine) -> None:
    """The decode program's ``main`` takes every weight as an argument:
    none is folded into the program as a constant."""
    toks = jax.ShapeDtypeStruct((engine.cfg.max_slots, 1, 1), jnp.int32)
    text = engine._decode.lower(engine.params, toks,
                                engine.slot_cache).as_text()
    main = next(line for line in text.splitlines()
                if "func.func public @main" in line)
    n_args = len(jax.tree.leaves((engine.params, toks, engine.slot_cache)))
    embed = engine.params["embed"]
    embed_type = (f"tensor<{embed.shape[0]}x{embed.shape[1]}x"
                  f"{jnp.dtype(embed.dtype).name.replace('float', 'f')}>")
    if main.count("%arg") != n_args or embed_type not in main:
        raise AssertionError("decode's main does not take the weights as "
                             f"its {n_args} arguments: {main[:400]}")
    print(f"[build] decode main takes {n_args} arguments, the "
          f"{len(jax.tree.leaves(engine.params))} weight arrays among them "
          f"({embed_type})")


def warm_up(engine, buckets) -> None:
    """Compile the decode step and every prefill bucket before serving, so
    no compile lands inside the served run; print compile and step times."""
    dev = engine.device
    toks = jax.device_put(np.zeros((engine.cfg.max_slots, 1, 1), np.int32),
                          dev)
    cache = [engine.slot_cache]

    def decode():
        # the step donates the cache it is given: pass on the one it returns
        logits, cache[0] = engine._decode(engine.params, toks, cache[0])
        return logits

    first, step = _timed(decode)
    engine.slot_cache = cache[0]
    print(f"[compile] decode ({engine.cfg.max_slots} slots x "
          f"{engine.cfg.max_seq}): first call {first:.2f} s, then "
          f"{step * 1e3:.2f} ms/step (median of 5)")
    for b in buckets:
        first, step = _timed(engine._prefill_fn(b), engine.params,
                             jax.device_put(np.zeros((1, b), np.int32), dev))
        print(f"[compile] prefill bucket {b}: first call {first:.2f} s, "
              f"then {step * 1e3:.2f} ms (median of 5)")


# --- phases -------------------------------------------------------------------

def kernel_phase(cfg, seed: int) -> None:
    """Compiled Pallas kernels at qwen3-0.6b head shapes vs the oracles."""
    if jax.default_backend() != "tpu":
        raise RuntimeError("the kernel phase runs compiled TPU kernels")
    hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    ks = jax.random.split(jax.random.key(seed), 6)
    bf = jnp.bfloat16

    def check(name, fn, oracle, args):
        compiled = jax.jit(fn).lower(*args).compile()
        if "tpu_custom_call" not in compiled.as_text():
            raise AssertionError(f"{name}: no Mosaic kernel in the program")
        out = compiled(*args)
        with jax.default_matmul_precision("highest"):
            want = oracle(*args)
        err = rel_err(out, want)
        print(f"[kernel] {name}: rel_l2 {err:.3e} (tol {KERNEL_REL_TOL})")
        if not err <= KERNEL_REL_TOL:
            raise AssertionError(f"{name}: rel_l2 {err} > {KERNEL_REL_TOL}")

    s = 1024                       # the largest prefill bucket
    q = jax.random.normal(ks[0], (1, s, hq, hd), bf)
    k = jax.random.normal(ks[1], (1, s, hkv, hd), bf)
    v = jax.random.normal(ks[2], (1, s, hkv, hd), bf)
    check(f"flash_attention ({s} tokens, {hq}/{hkv} heads of {hd})",
          partial(flash_attention_kernel, causal=True),
          partial(ref.flash_attention_ref, causal=True), (q, k, v))

    # decode over the engine's page pool: 8 sequences of up to 2048 tokens
    # in 16-token pages, scattered over the pool, ragged lengths
    b, page, per_seq = 8, 16, 2048 // 16
    n_pages = b * per_seq
    qd = jax.random.normal(ks[3], (b, hq, hd), bf)
    kp = jax.random.normal(ks[4], (n_pages, page, hkv, hd), bf)
    vp = jax.random.normal(ks[5], (n_pages, page, hkv, hd), bf)
    table = jax.random.permutation(jax.random.key(seed + 1), n_pages) \
        .reshape(b, per_seq).astype(jnp.int32)
    lengths = jnp.array([2048, 1, 17, 1000, 1536, 63, 512, 2047], jnp.int32)
    check(f"paged_attention ({b} seqs, pages of {page}, {n_pages} pages)",
          paged_attention_kernel, ref.paged_attention_ref,
          (qd, kp, vp, table, lengths))


def serve_phase(cfg, traffic: Traffic, *, seed: int, device=None,
                track: int = 2):
    """Serve the seeded requests through InferenceEngine.run; returns the
    engine and a LogitTap holding ``track`` requests' tokens and logits."""
    t = time.perf_counter()
    engine = build_engine(cfg, seed=seed, device=device, slots=traffic.slots,
                          max_seq=traffic.max_seq, control="dpu")
    jax.block_until_ready(engine.params)
    n_params = sum(a.size for a in jax.tree.leaves(engine.params))
    print(f"[build] {cfg.name}: {cfg.n_layers} layers, d_model "
          f"{cfg.d_model}, {cfg.n_heads}/{cfg.n_kv_heads} heads of "
          f"{cfg.hd}, d_ff {cfg.d_ff}, vocab {cfg.vocab}, {cfg.dtype}: "
          f"{n_params} params on {engine.device} in "
          f"{time.perf_counter() - t:.2f} s")
    check_params_are_arguments(engine)
    reqs = requests(cfg, traffic, seed)
    warm_up(engine, sorted({engine.sched.bucket_len(r.prompt_len)
                            for r in reqs}))
    # the shortest and the longest prompt: the most and the fewest pads
    by_len = sorted(reqs, key=lambda r: r.prompt_len)
    tap = LogitTap([r.req_id for r in by_len[:track // 2]
                    + by_len[len(by_len) - (track - track // 2):]])
    engine.on_logits = tap
    t = time.perf_counter()
    rep = engine.run(reqs, max_steps=20 * traffic.max_seq)
    wall = time.perf_counter() - t
    engine.on_logits = None
    tel = rep.get("telemetry", {})
    print(f"[serve] completed {rep['completed']}/{len(reqs)}, "
          f"{rep['tokens']} tokens in {rep['steps']} decode steps, "
          f"{wall:.2f} s wall (logits of {len(tap.logits)} requests "
          f"copied to the host every step)")
    print(f"[serve] telemetry: {tel.get('events', 0)} events, findings "
          f"{tel.get('findings_by_row', {})}, actions "
          f"{[a for _, a, _ in tel.get('actions', [])]}")
    if rep["completed"] != len(reqs):
        raise AssertionError(f"only {rep['completed']}/{len(reqs)} "
                             "requests completed")
    return engine, tap


def requests(cfg, traffic: Traffic, seed: int):
    return make_requests(traffic.requests, cfg.vocab, seed=seed,
                         rate=traffic.rate, prompt_len=traffic.prompt_len,
                         new_tokens=traffic.new_tokens)


def reference_phase(engine, tap: LogitTap) -> None:
    """Served logits vs the float32 full forward pass of the consumed
    tokens; then the same check must fail for an fp8-weight engine."""
    reference = Reference(engine)
    for rid in tap.logits:
        got = tap.rows(rid)
        err = logit_error(got, reference(tap.tokens[rid], len(got)))
        print(f"[reference] request {rid}: {len(tap.tokens[rid])} tokens "
              f"consumed, prefill + {len(got) - 1} decode rows: rel_l2 "
              f"{err['rel_l2']:.3e} (tol {REL_L2_TOL}), max|d|/std "
              f"{err['max_abs_over_std']:.3e} (tol {MAX_ABS_TOL})")
        if not within_tolerance(err):
            raise AssertionError(f"request {rid}: served logits off the "
                                 f"float32 reference: {err}")

    # the bound is tight: the same engine with fp8 (e4m3) weights, a
    # lower precision than the configuration states, must fail it
    rid = min(tap.logits)
    req = next(r for r in engine.completed if r.req_id == rid)
    probe = LogitTap([rid])
    bf16_params = engine.params
    engine.params = jax.tree.map(_fp8_round, bf16_params)
    engine.on_logits = probe
    try:
        engine.run([dataclasses.replace(req, tokens_out=0, first_token=-1.0,
                                        finished=-1.0, admitted=-1.0,
                                        arrival=engine.clock)])
    finally:
        engine.params = bf16_params
        engine.on_logits = None
    got = probe.rows(rid)
    err = logit_error(got, reference(probe.tokens[rid], len(got)))
    print(f"[reference] fp8-weight probe, request {rid}: rel_l2 "
          f"{err['rel_l2']:.3e}, max|d|/std {err['max_abs_over_std']:.3e} "
          "(must exceed a tolerance)")
    if within_tolerance(err):
        raise AssertionError("the tolerance passes an fp8-weight engine: "
                             f"{err}")


def _fp8_round(a):
    """Per-tensor scaled float8_e4m3 round trip of a weight matrix."""
    if a.ndim < 2:
        return a
    scale = jnp.max(jnp.abs(a.astype(jnp.float32))) / 448.0
    q = (a.astype(jnp.float32) / scale).astype(jnp.float8_e4m3fn)
    return (q.astype(jnp.float32) * scale).astype(a.dtype)


class Reference:
    """The float32 full forward pass of an engine's model: no cache, params
    cast to float32, matmul precision "highest" (on a TPU a float32 matmul
    otherwise runs in bf16 passes)."""

    def __init__(self, engine) -> None:
        model = build_model(dataclasses.replace(engine.model.cfg,
                                                dtype="float32"))
        self.p32 = jax.tree.map(lambda a: a.astype(jnp.float32),
                                engine.params)

        def fwd(p, tokens, start):
            logits, _ = model.forward(p, {"tokens": tokens[None]})
            return logits[0, start:]

        self._fwd = jax.jit(fwd, static_argnums=2)

    def __call__(self, tokens, rows: int) -> np.ndarray:
        """The last ``rows`` logits rows for the token sequence."""
        with jax.default_matmul_precision("highest"):
            out = self._fwd(self.p32, jnp.asarray(tokens, jnp.int32),
                            len(tokens) - rows)
            return np.asarray(out, np.float32)


def replica_phase(cfg, traffic: Traffic, devices, *, seed: int) -> None:
    """The requests served by one engine, then routed over one engine per
    device; each request's logits must match the one-replica run."""
    engines = []
    for i, dev in enumerate(devices):
        engine = build_engine(cfg, seed=seed, device=dev,
                              slots=traffic.slots, max_seq=traffic.max_seq,
                              control="dpu", node=i)
        warm_up(engine, sorted({engine.sched.bucket_len(r.prompt_len)
                                for r in requests(cfg, traffic, seed)}))
        engines.append(engine)
    ids = [r.req_id for r in requests(cfg, traffic, seed)]
    rows = 9                                   # prefill + 8 decode steps

    one = LogitTap(ids, max_rows=rows)
    engines[0].on_logits = one
    rep = engines[0].run(requests(cfg, traffic, seed),
                         max_steps=20 * traffic.max_seq)
    engines[0].on_logits = None
    print(f"[replicas] one replica: completed {rep['completed']}/{len(ids)}")

    many = LogitTap(ids, max_rows=rows)
    rs = ReplicaSet(engines, policy="join_shortest_queue")
    routed = [rs.submit(r, now=r.arrival)
              for r in requests(cfg, traffic, seed)]
    done = []
    for engine in engines:
        engine.on_logits = many
        done.append(len(engine.completed))
        engine.run([], max_steps=20 * traffic.max_seq)
        done[-1] = len(engine.completed) - done[-1]
        engine.on_logits = None
    print(f"[replicas] {len(engines)} replicas: routed {routed}, "
          f"completed per replica {done}")
    if sum(done) != len(ids) or rep["completed"] != len(ids):
        raise AssertionError("not every request completed")
    if min(done) == 0:
        raise AssertionError(f"a replica served nothing: {done}")

    placed = []
    for engine in engines:
        devs = {a.device for a in jax.tree.leaves((engine.params,
                                                   engine.slot_cache))}
        if len(devs) != 1:
            raise AssertionError(f"an engine spans devices {devs}")
        placed.append(devs.pop())
    print(f"[replicas] devices: {[str(d) for d in placed]}")
    if len(set(placed)) != len(engines):
        raise AssertionError(f"replicas share a device: {placed}")

    worst = {"rel_l2": 0.0, "max_abs_over_std": 0.0}
    compared = identical = 0
    for rid in ids:
        # row k follows the first len(tokens) - (rows - 1) + k tokens;
        # rows after the runs consumed different tokens are not comparable
        a, b = many.tokens[rid], one.tokens[rid]
        same = next((i for i, (x, y) in enumerate(zip(a, b)) if x != y),
                    len(a))
        n = min(rows, same - (len(a) - rows))
        if n < 1:
            raise AssertionError(f"request {rid}: prompts differ")
        got, want = many.rows(rid)[:n], one.rows(rid)[:n]
        err = logit_error(got, want)
        if not within_tolerance(err):
            raise AssertionError(f"request {rid}: replica logits off the "
                                 f"one-replica run: {err}")
        worst = {k: max(worst[k], err[k]) for k in worst}
        compared += n
        identical += int(np.sum(np.all(got == want, axis=-1)))
    print(f"[replicas] logits vs one replica: {compared} rows of "
          f"{len(ids)} requests ({identical} bit-identical), worst rel_l2 "
          f"{worst['rel_l2']:.3e}, max|d|/std "
          f"{worst['max_abs_over_std']:.3e}")


def peak_bytes(devices) -> list:
    return [(d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in devices]


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--replicas", type=int, default=1,
                    help="serve through N one-chip replicas (needs N chips)"
                         " instead of the one-chip phases")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found "
              f"{devices[0].platform!r}", file=sys.stderr)
        return 1
    if len(devices) < args.replicas:
        print(f"chip_smoke: --replicas {args.replicas} needs as many "
              f"chips; found {len(devices)}", file=sys.stderr)
        return 1
    enable_compile_cache()
    cfg = ARCHS[ARCH]
    traffic = Traffic()
    if args.replicas > 1:
        replica_phase(cfg, traffic, devices[:args.replicas], seed=args.seed)
    else:
        kernel_phase(cfg, args.seed)
        engine, tap = serve_phase(cfg, traffic, seed=args.seed,
                                  device=devices[0])
        reference_phase(engine, tap)
    print(f"[memory] peak bytes in use per device: {peak_bytes(devices)}")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
