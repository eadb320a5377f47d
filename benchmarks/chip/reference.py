"""The plain reference: a dense decoder's forward pass in float32.

Written from the published architecture (Llama/Qwen3 style: pre-norm
RMSNorm, rotary embeddings on the two halves of each head, grouped-query
causal attention with an optional sliding window, per-head RMSNorm of q and
k where the configuration says ``qk_norm``, SwiGLU, final RMSNorm, tied or
separate output head).  It imports nothing of the program and takes no
array the program made: weights are rebuilt from the seed one layer at a
time (``weights.LayerMaker``), so a model whose float32 weights would not
fit beside anything else still runs.  Every matrix product runs at
``Precision.HIGHEST``.

``Reference.block_gaps`` is the comparison that decides ``correct``: for
each served token, how far its reference logit lies below the reference's
best logit at that position.  With ``control=True`` it is the same reading
for the token that the reference computed with fp8 (e4m3, per-tensor
scaled) weights puts first.
Sequences run in fixed blocks of ``block`` rows of ``length`` positions, so
the compiled shapes are the same in every run and come from the cache.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip.weights import LayerMaker, dims

HI = jax.lax.Precision.HIGHEST
HEAD_ROWS = 512             # rows of the output head computed at a time


def _mm(x, w):
    return jnp.matmul(x, w, precision=HI)


def _rms(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def _rope(x, theta):
    """x: (B, T, H, D), positions 0..T-1; pairs are (i, i + D/2)."""
    t, d = x.shape[1], x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def layer_forward(w: dict, x, cfg: dict):
    """One decoder layer, float32, full causal sequence."""
    m = dims(cfg)
    b, t, _ = x.shape
    eps = cfg["rms_norm_eps"]
    h = _rms(x, w["ln1"]["scale"], eps)
    q = _mm(h, w["attn"]["wq"]).reshape(b, t, m["hq"], m["hd"])
    k = _mm(h, w["attn"]["wk"]).reshape(b, t, m["hkv"], m["hd"])
    v = _mm(h, w["attn"]["wv"]).reshape(b, t, m["hkv"], m["hd"])
    if cfg.get("qk_norm"):
        q = _rms(q, w["attn"]["q_norm"]["scale"], eps)
        k = _rms(k, w["attn"]["k_norm"]["scale"], eps)
    q, k = _rope(q, cfg["rope_theta"]), _rope(k, cfg["rope_theta"])
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
    x = x + _mm(o.reshape(b, t, m["hq"] * m["hd"]), w["attn"]["wo"])
    h = _rms(x, w["ln2"]["scale"], eps)
    ff = jax.nn.silu(_mm(h, w["mlp"]["w_gate"])) * _mm(h, w["mlp"]["w_up"])
    return x + _mm(ff, w["mlp"]["w_down"])


def fp8(w):
    """Per-tensor scaled float8_e4m3 round trip of every matrix."""
    def one(a):
        if a.ndim < 2:
            return a
        scale = jnp.max(jnp.abs(a)) / 448.0
        return (a / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) \
            * scale
    return jax.tree.map(one, w)


def _head_rows(h, head, fn, rows):
    """Apply ``fn(logits)`` to the logits of h (R, d), ``rows`` at a time."""
    r = h.shape[0]
    chunks = h.reshape(r // rows, rows, h.shape[-1])
    return jax.lax.map(lambda c: fn(_mm(c, head)), chunks).reshape(r)


class Reference:
    """Float32 forward of fixed blocks of token sequences."""

    def __init__(self, cfg: dict, seed: int, *, block: int, length: int,
                 device=None) -> None:
        rows = HEAD_ROWS if (block * length) % HEAD_ROWS == 0 \
            else block * length
        self.cfg, self.block, self.length = cfg, block, length
        self.device = device
        self.maker = LayerMaker(cfg, seed)
        self._layer = jax.jit(partial(layer_forward, cfg=cfg))
        self._layer8 = jax.jit(lambda w, x: layer_forward(fp8(w), x, cfg))
        eps = cfg["rms_norm_eps"]

        def gap(h, ln_f, head, targets):
            h = _rms(h, ln_f, eps).reshape(-1, h.shape[-1])
            t = targets.reshape(-1)

            def per(logits, tt):
                best = jnp.max(logits, axis=-1)
                got = jnp.take_along_axis(
                    logits, jnp.maximum(tt, 0)[:, None], axis=-1)[:, 0]
                return jnp.where(tt >= 0, best - got, 0.0)
            r = h.shape[0]
            hc = h.reshape(r // rows, rows, h.shape[-1])
            tc = t.reshape(r // rows, rows)
            out = jax.lax.map(lambda a: per(_mm(a[0], head), a[1]), (hc, tc))
            return out.reshape(targets.shape)

        def top(h, ln_f, head):
            h = _rms(h, ln_f, eps).reshape(-1, h.shape[-1])
            return _head_rows(h, head, lambda lg: jnp.argmax(
                lg, axis=-1).astype(jnp.float32), rows).astype(jnp.int32) \
                .reshape(self.block, self.length)

        self._gap = jax.jit(gap)
        self._top = jax.jit(top)

    def _final(self, h, control: bool):
        ln_f = self.maker.outer_f32("ln_f")["scale"]
        name = "embed" if self.cfg.get("tie_word_embeddings") else "lm_head"
        head = self.maker.outer_f32(name)
        if name == "embed":
            head = head.T
        if control:
            head = fp8(head)
        return ln_f, head

    def _run(self, tokens, control: bool):
        embed = self.maker.outer_f32("embed")
        x = embed[tokens]
        del embed
        layer = self._layer8 if control else self._layer
        for i in range(dims(self.cfg)["layers"]):
            x = layer(self.maker.layer_f32(i), x)
        return x

    def block_gaps(self, tokens: np.ndarray, targets: np.ndarray,
                   control: bool = False) -> np.ndarray:
        """Per-position gaps of one block.

        tokens (block, length): the sequences as consumed, right-padded.
        targets (block, length): at position i the token served after
        consuming tokens[:i+1], or -1 where nothing is compared.  With
        ``control`` the compared token is instead the one the fp8-weight
        reference puts first, at the same positions."""
        with jax.default_device(self.device):
            tok = jnp.asarray(tokens, jnp.int32)
            h = self._run(tok, control=False)
            ln_f, head = self._final(h, control=False)
            tgt = jnp.asarray(targets, jnp.int32)
            if control:
                h8 = self._run(tok, control=True)
                ln8, head8 = self._final(h8, control=True)
                top = self._top(h8, ln8, head8)
                del h8, head8
                tgt = jnp.where(tgt >= 0, top, -1)
            return np.asarray(self._gap(h, ln_f, head, tgt))


def pack(seqs: list[tuple[list[int], list[int]]], block: int, length: int):
    """Blocks of (tokens, targets) from (consumed, served) pairs.

    consumed: every token the engine fed, in order (left-padded prompt,
    then each served token but the last); served: the tokens it produced,
    the first from the prefill.  served[j] follows consumed[:p + j + 1],
    where p + 1 is the prompt's length as fed."""
    blocks = []
    for i in range(0, len(seqs), block):
        toks = np.zeros((block, length), np.int32)
        tgts = np.full((block, length), -1, np.int32)
        for r, (consumed, served) in enumerate(seqs[i:i + block]):
            if len(consumed) > length:
                raise ValueError(f"sequence of {len(consumed)} tokens is "
                                 f"longer than the reference's {length}")
            toks[r, :len(consumed)] = consumed
            first = len(consumed) - len(served)
            tgts[r, first:len(consumed)] = served
        blocks.append((toks, tgts))
    return blocks
