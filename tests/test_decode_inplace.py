"""The engine's in-place decode step against the vmapped single-sequence
step it replaces, and the structure of its compiled program.

The in-place step (``Model.decode_step_inplace``) scans over layers with
the slots inside, over a layer-major cache (L, slots, Hkv, S, hd), and
writes one position per slot into the donated cache.  The vmapped
``Model.decode_step`` over a slot-major cache is the reference: the same
prefills, the same tokens, the same logits and cache contents.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS
from repro.models import build_model
from repro.serving import EngineConfig, InferenceEngine, ServeRequest
from repro.serving.engine import decode_fn, slot_cache

SLOTS = 4

# (config, max_seq, prompt lengths by slot with None for an empty slot,
# whether the ring wraps); prompts fill 64-token buckets, then 10 steps
CASES = {
    # every prompt and its outputs fit the ring
    "qwen3": (ARCHS["qwen3-0.6b"].reduced(), 128, [5, 40, None, 64], False),
    # the ring wraps during decode (64 + 10 > 72)
    "qwen3-wraps": (ARCHS["qwen3-0.6b"].reduced(), 72, [3, None, 60, 64],
                    True),
    # sliding-window config; the bucket overfills the 32-position ring
    "danube-swa": (ARCHS["h2o-danube-3-4b"].reduced(), 32,
                   [None, 9, 33, 64], True),
    # a window shorter than max_seq: the ring is the 16-position window
    "danube-window16": (dataclasses.replace(
        ARCHS["h2o-danube-3-4b"].reduced(), swa_window=16), 128,
        [12, 50, None, 2], True),
    # routed experts, one token per slot as before
    "moe": (ARCHS["granite-moe-3b-a800m"].reduced(), 96, [7, None, 31, 64],
            False),
}
STEPS = 10


def _engine(cfg, max_seq):
    model = build_model(cfg)
    params = model.init(jax.random.key(1))
    return InferenceEngine(model, params, EngineConfig(
        max_slots=SLOTS, max_seq=max_seq, n_pages=256, page_size=16,
        telemetry=False))


@pytest.mark.parametrize("case", sorted(CASES))
def test_inplace_decode_matches_vmapped_step(case):
    cfg, max_seq, prompts, wraps = CASES[case]
    eng = _engine(cfg, max_seq)
    model, params = eng.model, eng.params
    eng._slot_next_token = {}
    # the old slot-major cache, filled from the very same prefill caches
    old = jax.tree.map(
        lambda a: jnp.broadcast_to(a, (SLOTS,) + a.shape).copy(),
        model.init_cache(1, max_seq))
    seen = {}
    prefill_fn = eng._prefill_fn

    def spy(bucket):
        fn = prefill_fn(bucket)

        def call(p, toks):
            seen["out"] = fn(p, toks)
            return seen["out"]
        return call

    eng._prefill_fn = spy
    rng = np.random.default_rng(7)
    for slot, n in enumerate(prompts):
        if n is None:
            continue
        req = ServeRequest(req_id=slot, arrival=0.0,
                           prompt=rng.integers(1, cfg.vocab, n).tolist(),
                           max_new_tokens=STEPS)
        eng._prefill(slot, req)
        old = jax.tree.map(lambda f, o: f.at[slot].set(o), old,
                           seen["out"][1])

    vmapped = jax.jit(jax.vmap(model.decode_step, in_axes=(None, 0, 0)))
    toks = np.zeros((SLOTS, 1, 1), np.int32)
    for s, t in eng._slot_next_token.items():
        toks[s, 0, 0] = t
    for _ in range(STEPS):
        want, old = vmapped(params, jnp.asarray(toks), old)
        got, eng.slot_cache = eng._decode(params, jnp.asarray(toks),
                                          eng.slot_cache)
        assert got.shape == want.shape == (SLOTS, 1, 1, cfg.vocab)
        np.testing.assert_allclose(got, want, rtol=0, atol=1e-5)
        toks = np.asarray(jnp.argmax(want, axis=-1)).astype(np.int32)

    new = eng.slot_cache
    np.testing.assert_array_equal(new["pos"], old["pos"])
    np.testing.assert_array_equal(new["kpos"], old["kpos"])
    assert (np.asarray(new["pos"]) > new["kpos"].shape[1]).any() == wraps
    for name in ("k", "v"):
        # slot-major (slots, L, 1, S, Hkv, hd) -> (L, slots, Hkv, S, hd)
        relaid = jnp.swapaxes(jnp.swapaxes(old[name][:, :, 0], 0, 1), 2, 3)
        np.testing.assert_allclose(new[name], relaid, rtol=0, atol=1e-5)


def _kv_bytes(cache) -> int:
    return sum(cache[n].size * cache[n].dtype.itemsize for n in ("k", "v"))


def test_decode_program_writes_the_cache_in_place():
    """The compiled step aliases the whole K/V cache to its output and
    keeps no cache-sized temporary.  The vmapped step kept 1.43 caches'
    worth here (a transpose to layer-major and back, the stacked
    per-layer output); the CPU backend still copies one layer's slice
    (0.32 here), the TPU's none."""
    model = build_model(ARCHS["qwen3-0.6b"].reduced(n_layers=4))
    params = jax.eval_shape(model.init, jax.random.key(0))
    cache = jax.eval_shape(lambda: slot_cache(model, SLOTS, 1024))
    toks = jax.ShapeDtypeStruct((SLOTS, 1, 1), jnp.int32)
    mem = decode_fn(model).lower(params, toks, cache).compile() \
        .memory_analysis()
    kv = _kv_bytes(cache)
    assert mem.alias_size_in_bytes >= kv, (mem.alias_size_in_bytes, kv)
    assert mem.temp_size_in_bytes < kv / 2, (mem.temp_size_in_bytes, kv)


@pytest.mark.parametrize("arch,inplace", [("qwen3-0.6b", True),
                                          ("xlstm-125m", False)])
def test_inplace_steps_counts_the_in_place_path(arch, inplace):
    eng = _engine(ARCHS[arch].reduced(), 96)
    reqs = [ServeRequest(req_id=i, arrival=0.0, prompt=[3] * n,
                         max_new_tokens=4) for i, n in enumerate([8, 70])]
    rep = eng.run(reqs, max_steps=50)
    assert rep["completed"] == 2
    assert eng.stats["steps"] > 0
    assert eng.stats["inplace_steps"] == (eng.stats["steps"] if inplace
                                          else 0)
