"""A dense decoder (Llama/Qwen3 style): everything the harness knows of it.

A configuration file names its architecture (``"arch": "dense"``) and
``spec.arch`` loads this module; nothing outside ``archs/`` knows a leaf,
a key or a count of one architecture.  An architecture module gives:

  program_config(cfg)        the program's ``ModelConfig`` for the file
  outer_leaves(cfg)          {path: (shape, std)} of the unstacked leaves
  stacks(cfg)                {name: (count, {path: (shape, std)})}: the
                             leaves stacked under ``params[name]``, one
                             per layer (std None: a norm scale, all ones;
                             a third item, a dtype, where the program
                             holds the leaf in other than bfloat16)
  embed(get, tokens, cfg)    the float32 hidden state of the tokens
  layer_forward(w, x, cfg, index)
                             one layer, float32, full causal sequence;
                             ``index`` (a traced int32) is the layer's
                             place in its stack
  head(get, cfg)             (final norm weights, output matrix (d, vocab))
  norm(h, w, cfg)            the final norm of hidden rows
  decode_step(cfg, step)     (operations, bytes) of one decode step
                             (``serve_loop.Step``), a sound lower bound
  request_flops(cfg, prompt_len, generated)
                             model operations of one request

``get(path)`` returns an outer leaf (or the subtree under a path) in
float32.  The reference's layers run in the order of ``stacks``.

The dense layer: pre-norm RMSNorm, rotary embeddings on the two halves of
each head, grouped-query causal attention with an optional sliding window,
per-head RMSNorm of q and k where the configuration says ``qk_norm``,
SwiGLU, final RMSNorm, tied or separate output head.  Weights are drawn
with the program's own initialiser scales: 1/sqrt(fan_in) for projections,
0.02 for the embedding, 1 for norm scales.

Work: the arithmetic of ``launch/roofline.py`` (2 x N operations per token
for N active parameters), counted from the lengths the traffic really had:

  decode step  every weight read once; for each running slot the keys and
               values of its consumed positions (at most the window) read
               and one position written; 2 x N operations per slot plus
               attention over its positions.
  model work   2 x N per prompt token and per generated token, plus
               attention over the live context, for utilisation.

A padded or dense program does more than this, never less, so a share of
the roofline computed from it stays at or under 100%.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from benchmarks.chip.reference import HI, mm

BYTES = 2                       # bfloat16 weights and cache

# configuration keys (Hugging Face names) -> the program's ModelConfig
PROGRAM_KEYS = {
    "num_hidden_layers": "n_layers", "hidden_size": "d_model",
    "num_attention_heads": "n_heads", "num_key_value_heads": "n_kv_heads",
    "intermediate_size": "d_ff", "vocab_size": "vocab",
    "head_dim": "head_dim", "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps", "tie_word_embeddings": "tie_embeddings",
    "qk_norm": "qk_norm",
}


def program_config(cfg: dict):
    """The registry's entry with the sizes of the configuration file."""
    from repro.configs import ARCHS
    kw = {v: cfg[k] for k, v in PROGRAM_KEYS.items()}
    kw["rope_theta"] = float(kw["rope_theta"])
    kw["swa_window"] = cfg.get("sliding_window") or 0
    kw["dtype"] = cfg["torch_dtype"]
    return dataclasses.replace(ARCHS[cfg["registry"]], **kw)


def dims(cfg: dict) -> dict:
    d = cfg["hidden_size"]
    hd = cfg.get("head_dim") or d // cfg["num_attention_heads"]
    return {"d": d, "hd": hd, "hq": cfg["num_attention_heads"],
            "hkv": cfg["num_key_value_heads"],
            "ff": cfg["intermediate_size"], "vocab": cfg["vocab_size"],
            "layers": cfg["num_hidden_layers"]}


# -- weights -------------------------------------------------------------

def _proj(fan_in: int) -> float:
    return 1.0 / math.sqrt(fan_in)


def layer_leaves(cfg: dict) -> dict:
    """Per-layer leaves: path -> (shape, std); std None is a norm."""
    m = dims(cfg)
    d, hd, hq, hkv, ff = m["d"], m["hd"], m["hq"], m["hkv"], m["ff"]
    leaves = {
        ("ln1", "scale"): ((d,), None),
        ("attn", "wq"): ((d, hq * hd), _proj(d)),
        ("attn", "wk"): ((d, hkv * hd), _proj(d)),
        ("attn", "wv"): ((d, hkv * hd), _proj(d)),
        ("attn", "wo"): ((hq * hd, d), _proj(hq * hd)),
        ("ln2", "scale"): ((d,), None),
        ("mlp", "w_gate"): ((d, ff), _proj(d)),
        ("mlp", "w_up"): ((d, ff), _proj(d)),
        ("mlp", "w_down"): ((ff, d), _proj(ff)),
    }
    if cfg.get("qk_norm"):
        leaves[("attn", "q_norm", "scale")] = ((hd,), None)
        leaves[("attn", "k_norm", "scale")] = ((hd,), None)
    return leaves


def outer_leaves(cfg: dict) -> dict:
    m = dims(cfg)
    leaves = {("embed",): ((m["vocab"], m["d"]), 0.02),
              ("ln_f", "scale"): ((m["d"],), None)}
    if not cfg.get("tie_word_embeddings"):
        leaves[("lm_head",)] = ((m["d"], m["vocab"]), _proj(m["d"]))
    return leaves


def stacks(cfg: dict) -> dict:
    return {"layers": (dims(cfg)["layers"], layer_leaves(cfg))}


# -- the float32 reference -------------------------------------------------

def rms(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def rope(x, theta):
    """x: (B, T, H, D), positions 0..T-1; pairs are (i, i + D/2)."""
    t, d = x.shape[1], x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def embed(get, tokens, cfg: dict):
    return get(("embed",))[tokens]


def layer_forward(w: dict, x, cfg: dict, index=None):
    """One decoder layer, float32, full causal sequence."""
    m = dims(cfg)
    b, t, _ = x.shape
    eps = cfg["rms_norm_eps"]
    h = rms(x, w["ln1"]["scale"], eps)
    q = mm(h, w["attn"]["wq"]).reshape(b, t, m["hq"], m["hd"])
    k = mm(h, w["attn"]["wk"]).reshape(b, t, m["hkv"], m["hd"])
    v = mm(h, w["attn"]["wv"]).reshape(b, t, m["hkv"], m["hd"])
    if cfg.get("qk_norm"):
        q = rms(q, w["attn"]["q_norm"]["scale"], eps)
        k = rms(k, w["attn"]["k_norm"]["scale"], eps)
    q, k = rope(q, cfg["rope_theta"]), rope(k, cfg["rope_theta"])
    g = m["hq"] // m["hkv"]
    k = jnp.repeat(k, g, axis=2)
    v = jnp.repeat(v, g, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HI) / math.sqrt(m["hd"])
    qi = jnp.arange(t)[:, None]
    ki = jnp.arange(t)[None, :]
    allowed = ki <= qi
    if cfg.get("sliding_window"):
        allowed &= ki > qi - cfg["sliding_window"]
    s = jnp.where(allowed, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v, precision=HI)
    x = x + mm(o.reshape(b, t, m["hq"] * m["hd"]), w["attn"]["wo"])
    h = rms(x, w["ln2"]["scale"], eps)
    ff = jax.nn.silu(mm(h, w["mlp"]["w_gate"])) * mm(h, w["mlp"]["w_up"])
    return x + mm(ff, w["mlp"]["w_down"])


def head(get, cfg: dict):
    if cfg.get("tie_word_embeddings"):
        return get(("ln_f", "scale")), get(("embed",)).T
    return get(("ln_f", "scale")), get(("lm_head",))


def norm(h, w, cfg: dict):
    return rms(h, w, cfg["rms_norm_eps"])


# -- work ------------------------------------------------------------------

def layer_params(cfg: dict) -> int:
    m = dims(cfg)
    d, hd = m["d"], m["hd"]
    attn = d * hd * (2 * m["hq"] + 2 * m["hkv"])
    return attn + 3 * d * m["ff"]


def matmul_params(cfg: dict) -> int:
    """Parameters a token multiplies by: every layer and the output head
    (the embedding row it looks up is a read, not a product)."""
    m = dims(cfg)
    return m["layers"] * layer_params(cfg) + m["d"] * m["vocab"]


def weight_bytes(cfg: dict) -> int:
    """Weights a decode step reads: the layers and the output head."""
    return BYTES * matmul_params(cfg)


def kv_bytes_per_position(cfg: dict) -> int:
    m = dims(cfg)
    return BYTES * 2 * m["layers"] * m["hkv"] * m["hd"]


def _attended(cfg: dict, ctx: int) -> int:
    window = cfg.get("sliding_window") or 0
    return min(window, ctx) if window else ctx


def attention_flops(cfg: dict, ctx: int) -> int:
    """Scores and weighted sum for one query over ``ctx`` positions."""
    m = dims(cfg)
    return 4 * m["layers"] * m["hq"] * m["hd"] * _attended(cfg, ctx)


def decode_step(cfg: dict, step) -> tuple[float, float]:
    """(operations, bytes) of one decode step; ``step.contexts[i]`` is the
    number of positions running slot i attends, the new one included."""
    contexts = step.contexts
    flops = sum(2 * matmul_params(cfg) + attention_flops(cfg, c)
                for c in contexts)
    kv = kv_bytes_per_position(cfg)
    read = sum(_attended(cfg, c - 1) for c in contexts) * kv
    written = len(contexts) * kv
    return float(flops), float(weight_bytes(cfg) + read + written)


def request_flops(cfg: dict, prompt_len: int, generated: int) -> float:
    """Model operations to prefill ``prompt_len`` tokens and generate
    ``generated`` more (the first from the prefill)."""
    n = 2 * matmul_params(cfg)
    # the prompt attends causally: position i sees i + 1 positions
    attn = sum(attention_flops(cfg, i + 1) for i in range(prompt_len))
    attn += sum(attention_flops(cfg, prompt_len + j + 1)
                for j in range(max(generated - 1, 0)))
    return float(n * (prompt_len + max(generated - 1, 0)) + attn)
