"""Weights from the seed and the plain reference, at a CPU size."""

import _paths
from _paths import DATA

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.chip import reference, run, spec, weights

DENSE = spec.arch("dense")


def _tiny(**kw):
    return dict(spec.load_json(DATA / "tiny.json"), **kw)


def test_per_layer_draw_equals_the_stacked_one():
    cfg = _tiny()
    params = weights.make_params(cfg, 2**35 + 11, jax.devices()[0])
    maker = weights.LayerMaker(cfg, 2**35 + 11)
    one = maker.layer_f32("layers", 1)
    stacked = jax.tree.map(lambda a: np.asarray(a[1], np.float32),
                           params["layers"])
    jax.tree.map(np.testing.assert_array_equal, stacked,
                 jax.tree.map(np.asarray, one))
    np.testing.assert_array_equal(
        np.asarray(params["embed"], np.float32),
        np.asarray(maker.outer_f32(("embed",))))


def test_seeds_differ_and_layout_is_the_programs():
    cfg = _tiny()
    dev = jax.devices()[0]
    a = weights.make_params(cfg, 1, dev)
    b = weights.make_params(cfg, 1 + 2**32, dev)
    assert not np.array_equal(np.asarray(a["embed"]), np.asarray(b["embed"]))
    from repro.models import build_model
    weights.check_layout(a, build_model(DENSE.program_config(cfg)))
    other = build_model(DENSE.program_config(_tiny(intermediate_size=96)))
    with pytest.raises(ValueError, match="layout"):
        weights.check_layout(a, other)


@pytest.mark.parametrize("over", [{}, {"tie_word_embeddings": False,
                                       "qk_norm": False,
                                       "sliding_window": 8}])
def test_reference_matches_the_programs_float32_forward(over):
    """Written apart from the program, the reference computes the same
    function: the program's own full forward in float32 agrees."""
    cfg = _tiny(**over)
    seed = 5
    from repro.models import build_model
    pcfg = dataclasses.replace(DENSE.program_config(cfg), dtype="float32")
    params = jax.tree.map(lambda a: a.astype(jnp.float32),
                          weights.make_params(cfg, seed, jax.devices()[0]))
    toks = np.random.default_rng(0).integers(0, cfg["vocab_size"], (2, 32))
    with jax.default_matmul_precision("highest"):
        want, _ = build_model(pcfg).forward(params, {"tokens": toks})
    ref = reference.Reference(cfg, seed, block=2, length=32)
    h = ref._run(jnp.asarray(toks, jnp.int32), control=False)
    got = ref.logits(h, *ref._final(h, control=False))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)
    # the gap of the reference's own first choices is zero
    top = np.asarray(jnp.argmax(got, -1))
    gaps = ref.block_gaps(toks, top)
    assert gaps.max() == 0.0


def test_pack_puts_each_served_token_after_what_preceded_it():
    consumed = [0, 0, 7, 8, 9, 41, 42]           # bucket 5, then 2 served
    served = [41, 42, 43]
    (toks, tgts), = reference.pack([(consumed, served)], block=2, length=8)
    assert list(toks[0]) == consumed + [0]
    # served[j] follows consumed[:5 + j]: predicted at position 4 + j
    assert list(tgts[0]) == [-1, -1, -1, -1, 41, 42, 43, -1]
    assert (tgts[1] == -1).all()
    with pytest.raises(ValueError):
        reference.pack([(list(range(9)), [1])], block=1, length=8)


def _done(rid, n_prompt, n_tokens):
    from benchmarks.chip.traffic import Request
    return Request(rid=rid, due=0.0, prompt=[3] * n_prompt,
                   max_new_tokens=n_tokens - 1,
                   tokens=[4] * n_tokens, finished=True)


def test_sample_takes_the_longest_and_every_replica():
    """The sample is the longest finished request, one of every replica
    that served any, and the rest drawn from the seed; with one replica,
    the longest and k - 1 others."""
    reqs = [_done(i, 10 + i, 5) for i in range(20)]
    reqs.append(_done(99, 900, 300))
    reqs.append(_done(100, 5, 5))
    reqs[-1].finished = False
    for r in reqs:
        r.replica = 0
    picked = run.sample(reqs, 7, 3)
    assert picked[0].rid == 99 and len(picked) == 3
    assert all(r.finished for r in picked)
    assert [r.rid for r in run.sample(reqs, 7, 3)] == \
        [r.rid for r in picked]
    for r in reqs:
        r.replica = r.rid % 4
    picked = run.sample(reqs, 7, 3)
    assert picked[0].rid == 99
    assert {r.replica for r in picked} == {0, 1, 2, 3}
    assert len(picked) == 4 and all(r.finished for r in picked)
    assert len(run.sample(reqs, 7, 8)) == 8


def test_consumed_is_the_left_padded_prompt_and_all_but_the_last_token():
    r = _done(1, 3, 4)
    r.prompt, r.tokens = [11, 12, 13], [21, 22, 23, 24]
    assert run.consumed(r, 8) == [0] * 5 + [11, 12, 13, 21, 22, 23]
