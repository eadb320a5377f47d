"""Architectures are found by name from ``archs/<arch>.py``: a new one goes
in as new files alone, and the dense one makes what it made before it
moved there (weights bit for bit, reference gaps, work counts)."""

import _paths
from _paths import DATA

import hashlib
import json
import textwrap

import jax
import numpy as np
import pytest

from benchmarks.chip import reference, spec, weights, work
from benchmarks.chip.serve_loop import Step

SEED = 2**33 + 17

# A mixture-of-experts decoder through the program's own MoE model: the
# dense module's attention, then softmax-routed SwiGLU experts, the router
# held in float32 as the program holds it.
STUB = textwrap.dedent('''
    import dataclasses

    import jax
    import jax.numpy as jnp

    from benchmarks.chip import spec
    from benchmarks.chip.reference import mm

    DENSE = spec.arch("dense")


    def _dense(cfg):
        return dict(cfg, intermediate_size=1)


    def program_config(cfg):
        return dataclasses.replace(
            DENSE.program_config(_dense(cfg)),
            n_experts=cfg["num_local_experts"],
            top_k=cfg["num_experts_per_tok"],
            expert_d_ff=cfg["moe_intermediate_size"])


    def outer_leaves(cfg):
        return DENSE.outer_leaves(_dense(cfg))


    def stacks(cfg):
        d, e = cfg["hidden_size"], cfg["num_local_experts"]
        f = cfg["moe_intermediate_size"]
        n, leaves = DENSE.stacks(_dense(cfg))["layers"]
        leaves = {p: v for p, v in leaves.items() if p[0] != "mlp"}
        leaves.update({
            ("moe", "router"): ((d, e), d ** -0.5, "float32"),
            ("moe", "w_gate"): ((e, d, f), d ** -0.5),
            ("moe", "w_up"): ((e, d, f), d ** -0.5),
            ("moe", "w_down"): ((e, f, d), f ** -0.5)})
        return {"layers": (n, leaves)}


    def embed(get, tokens, cfg):
        return DENSE.embed(get, tokens, cfg)


    def layer_forward(w, x, cfg, index=None):
        d = cfg["hidden_size"]
        none = {"w_gate": jnp.zeros((d, 1)), "w_up": jnp.zeros((d, 1)),
                "w_down": jnp.zeros((1, d))}
        x = DENSE.layer_forward(dict(w, mlp=none), x, _dense(cfg))
        h = DENSE.rms(x, w["ln2"]["scale"], cfg["rms_norm_eps"])
        m = w["moe"]
        p = jax.nn.softmax(mm(h, m["router"]), axis=-1)
        top, idx = jax.lax.top_k(p, cfg["num_experts_per_tok"])
        gate = jnp.sum(jax.nn.one_hot(idx, p.shape[-1]) * top[..., None], -2)
        g = jnp.einsum("btd,edf->btef", h, m["w_gate"])
        u = jnp.einsum("btd,edf->btef", h, m["w_up"])
        y = jnp.einsum("btef,efd->bted", jax.nn.silu(g) * u, m["w_down"])
        return x + jnp.einsum("bte,bted->btd", gate, y)


    def head(get, cfg):
        return DENSE.head(get, cfg)


    def norm(h, w, cfg):
        return DENSE.norm(h, w, cfg)


    def _active(cfg):
        d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
        attn = DENSE.layer_params(_dense(cfg)) - 3 * d
        per = attn + 3 * d * f * cfg["num_experts_per_tok"]
        return cfg["num_hidden_layers"] * per + d * cfg["vocab_size"]


    def decode_step(cfg, step):
        n = len(step.contexts)
        return 2.0 * _active(cfg) * n, 2.0 * _active(cfg)


    def request_flops(cfg, prompt_len, generated):
        return 2.0 * _active(cfg) * (prompt_len + max(generated - 1, 0))
''')

STUB_CFG = {
    "name": "tiny-moe", "arch": "stub", "registry": "granite-moe-3b-a800m",
    "hidden_size": 64, "num_hidden_layers": 2, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "vocab_size": 512,
    "num_local_experts": 4, "num_experts_per_tok": 2,
    "moe_intermediate_size": 32, "rope_theta": 10000, "rms_norm_eps": 1e-6,
    "tie_word_embeddings": True, "sliding_window": None,
    "torch_dtype": "bfloat16", "qk_norm": False,
}


@pytest.fixture()
def stub_base(tmp_path):
    (tmp_path / "archs").mkdir()
    (tmp_path / "configs").mkdir()
    (tmp_path / "archs" / "stub.py").write_text(STUB)
    (tmp_path / "configs" / "tiny-moe.json").write_text(json.dumps(STUB_CFG))
    return tmp_path


def test_a_new_architecture_goes_in_as_new_files(stub_base):
    from repro.models import build_model
    cfg = spec.config("tiny-moe", stub_base)
    arch = spec.arch(cfg["arch"], stub_base)
    with pytest.raises(KeyError, match="architecture 'stub'"):
        spec.arch("stub")                        # not in the harness's own
    dev = jax.devices()[0]
    params = weights.make_params(cfg, SEED, dev, arch)
    weights.check_layout(params, build_model(arch.program_config(cfg)))
    assert params["layers"]["moe"]["router"].dtype == np.float32
    ref = reference.Reference(cfg, SEED, block=1, length=16, arch=arch)
    toks = np.random.default_rng(1).integers(0, 512, (1, 16))
    h = ref._run(jax.numpy.asarray(toks, jax.numpy.int32), control=False)
    top = np.asarray(ref.logits(h, *ref._final(h, False)).argmax(-1))
    assert ref.block_gaps(toks, top).max() == 0.0
    other = (top + 1) % 512
    gaps = ref.block_gaps(toks, other)
    assert np.isfinite(gaps).all() and gaps.max() > 0
    flops, nbytes = arch.decode_step(cfg, Step(0.0, 0.01, [5, 9]))
    peak = spec.peaks("TPU v5 lite")
    assert 0 < work.least_time(flops, nbytes, peak) < 1e-3
    assert arch.request_flops(cfg, 10, 3) > 0


def _digest(params) -> str:
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        a = np.asarray(leaf)
        h.update(jax.tree_util.keystr(path).encode())
        h.update(str(a.shape).encode() + str(a.dtype).encode())
        h.update(a.tobytes())
    return h.hexdigest()


# what the dense code made before it moved into archs/dense.py
WEIGHTS = {
    "tied_qknorm": ("0dd5b012dfced8757597681b63b4b8edf492089b61b040445e334cf"
                    "f4d5ccc10",
                    "743d5196907decd37b61f54b59f839184b89ddb465c135189fb1cc2"
                    "ec0366620"),
    "untied_window": ("cc8127f35010d8b03385f1ac213bb786167ad07e33a2c23102984"
                      "d315c65b9df",
                      "9cbc1fb3cc70d3aeb2b69d4a220ebf526ed741a8a18546957320b"
                      "23a6e074076"),
}
# (gaps compared, their sum, the widest), served and control
GAPS = {
    "tied_qknorm": ((43, 21.951983213424683, 0.8911008834838867),
                    (8, 0.0849604606628418, 0.025338798761367798)),
    "untied_window": ((44, 119.31324410438538, 4.416657447814941),
                      (7, 0.5443441867828369, 0.15729928016662598)),
}
OVER = {"tied_qknorm": {},
        "untied_window": {"tie_word_embeddings": False, "qk_norm": False,
                          "sliding_window": 8}}
CONTEXTS = [[1], [100, 2000], [2048] * 16, [5, 700, 1500, 1024, 33]]
REQUESTS = [(384, 64), (1024, 512), (32, 1), (100, 0)]
WORK = {
    "qwen3-0.6b": (
        [(1192198144.0, 1192083456.0), (2865627136.0, 1432813568.0),
         (26587693056.0, 4950065152.0), (6708068352.0, 1566081024.0)],
        [555776999424.0, 2100078837760.0, 38264111104.0, 120355225600.0]),
    "h2o-danube-3-4b": (
        [(7677911040.0, 7677634560.0), (16129228800.0, 7871078400.0),
         (134920273920.0, 10697441280.0), (39590215680.0, 7978168320.0)],
        [3468772638720.0, 12219609907200.0, 245875998720.0,
         769615872000.0]),
}


@pytest.mark.parametrize("case", sorted(WEIGHTS))
def test_dense_weights_are_the_same_bit_for_bit(case):
    cfg = dict(spec.load_json(DATA / "tiny.json"), **OVER[case])
    params = weights.make_params(cfg, SEED, jax.devices()[0])
    maker = weights.LayerMaker(cfg, SEED)
    assert (_digest(params), _digest(maker.layer_f32("layers", 1))) == \
        WEIGHTS[case]


@pytest.mark.parametrize("case", sorted(GAPS))
def test_dense_reference_gaps_are_the_same(case):
    cfg = dict(spec.load_json(DATA / "tiny.json"), **OVER[case])
    ref = reference.Reference(cfg, SEED, block=2, length=32)
    rng = np.random.default_rng(3)
    toks = rng.integers(0, cfg["vocab_size"], (2, 32))
    tgts = rng.integers(0, cfg["vocab_size"], (2, 32))
    tgts[:, :10] = -1
    for control, want in zip((False, True), GAPS[case]):
        g = ref.block_gaps(toks, tgts, control=control).reshape(-1)
        got = (int(np.count_nonzero(g)), float(np.sum(g.astype(np.float64))),
               float(g.max()))
        assert got[0] == want[0]
        assert got[1:] == pytest.approx(want[1:], rel=1e-6)


@pytest.mark.parametrize("name", sorted(WORK))
def test_dense_work_counts_are_the_same(name):
    cfg = spec.config(name)
    arch = spec.arch(cfg["arch"])
    steps, requests = WORK[name]
    assert [arch.decode_step(cfg, Step(0.0, 0.0, c)) for c in CONTEXTS] == \
        steps
    assert [arch.request_flops(cfg, p, g) for p, g in REQUESTS] == requests
