"""Model stacks for all assigned families, built scan-over-layers so the
compiled HLO is O(1) in depth (512-device SPMD compiles stay tractable).

Families:
  dense   — pre-norm GQA attention + SwiGLU (llama/mistral/qwen/danube)
  moe     — attention + (shared + routed top-k experts)
  encdec  — bidirectional encoder + causal decoder w/ cross-attention
  vlm     — dense backbone consuming [patch-embeds ; token-embeds]
  hybrid  — zamba2: Mamba2 backbone, ONE shared attn+MLP block applied every
            k layers (super-block structure: scan over (k mamba + shared))
  ssm     — xLSTM: alternating mLSTM / sLSTM pairs

Every stack exposes: init / fwd (full sequence, optional caches for decode).
``Sharder`` is an optional activation-constraint hook (see parallel.sharding)
so the same code runs unsharded on CPU tests and fully sharded in the
dry-run.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig
from repro.models import ssm as S
from repro.models.layers import (
    apply_rope,
    attention_fwd,
    attention_init,
    dense_init,
    dtype_of,
    mlp_fwd,
    mlp_init,
    rmsnorm,
    rmsnorm_init,
)
from repro.models.moe import moe_fwd, moe_init


class NoSharder:
    """Default no-op activation sharder."""

    def act(self, x, kind: str):
        return x


NOSHARD = NoSharder()


# ----------------------------------------------------------------------
# layer-stacking helpers
# ----------------------------------------------------------------------

def stack_init(key, n: int, init_fn: Callable[[Any], dict]) -> dict:
    """Initialize n layers and stack leaves along a leading axis."""
    keys = jax.random.split(key, n)
    layers = [init_fn(k) for k in keys]
    return jax.tree.map(lambda *xs: jnp.stack(xs), *layers)


def _maybe_remat(fn, cfg: ModelConfig):
    return jax.checkpoint(fn) if cfg.remat else fn


def scan_layers(body, carry, xs, cfg: ModelConfig):
    """lax.scan over stacked layer params — or an unrolled Python loop when
    ``cfg.unroll_layers`` (roofline calibration: cost_analysis counts scan
    bodies once, unrolled copies are counted exactly)."""
    if not cfg.unroll_layers:
        return jax.lax.scan(body, carry, xs)
    n = jax.tree.leaves(xs)[0].shape[0]
    ys = []
    for i in range(n):
        x_i = jax.tree.map(lambda a: a[i], xs)
        carry, y = body(carry, x_i)
        ys.append(y)
    if ys and ys[0] is not None:
        stacked = jax.tree.map(lambda *zs: jnp.stack(zs), *ys)
    else:
        stacked = None
    return carry, stacked


# ----------------------------------------------------------------------
# dense / moe / vlm decoder-only stack
# ----------------------------------------------------------------------

def decoder_init(key, cfg: ModelConfig) -> dict:
    dt = dtype_of(cfg)
    k_embed, k_layers, k_head, k_shared = jax.random.split(key, 4)

    def layer_init(k):
        ka, kb = jax.random.split(k)
        p = {
            "ln1": rmsnorm_init(cfg.d_model, dt),
            "attn": attention_init(ka, cfg),
            "ln2": rmsnorm_init(cfg.d_model, dt),
        }
        if cfg.is_moe:
            p["moe"] = moe_init(kb, cfg)
        else:
            p["mlp"] = mlp_init(kb, cfg.d_model, cfg.d_ff, dt)
        return p

    params = {
        "embed": (jax.random.normal(k_embed, (cfg.vocab, cfg.d_model),
                                    jnp.float32) * 0.02).astype(dt),
        "layers": stack_init(k_layers, cfg.n_layers, layer_init),
        "ln_f": rmsnorm_init(cfg.d_model, dt),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(k_head, cfg.d_model, cfg.vocab, dt)
    return params


def ring_info(cache_pos, s_total: int, max_seq: int, old_kpos,
              shard=None):
    """Ring-buffer bookkeeping shared by every attention layer of a step."""
    q_pos = cache_pos + jnp.arange(s_total)
    if s_total >= max_seq:
        return {"q_pos": q_pos, "shard": shard}, q_pos[-max_seq:]
    slots = q_pos % max_seq
    new_kpos = old_kpos.at[slots].set(q_pos)
    return {"slots": slots, "kpos": new_kpos, "q_pos": q_pos,
            "shard": shard}, new_kpos


def _dense_layer_fwd(lp: dict, cfg: ModelConfig, x, positions, shard,
                     cache_k=None, cache_v=None, ring=None):
    """One decoder layer; returns (x, aux, new_k, new_v)."""
    h = rmsnorm(lp["ln1"], x, cfg.norm_eps)
    kv_cache = None
    if cache_k is not None:
        kv_cache = {"k": cache_k, "v": cache_v, **ring}
    attn_out, new_cache = attention_fwd(lp["attn"], cfg, h, positions,
                                        kv_cache=kv_cache)
    x = x + shard.act(attn_out, "act")
    h = rmsnorm(lp["ln2"], x, cfg.norm_eps)
    aux = jnp.zeros((), jnp.float32)
    if cfg.is_moe:
        out, aux = moe_fwd(lp["moe"], cfg, h, shard=shard)
    else:
        out = mlp_fwd(lp["mlp"], shard.act(h, "ffn_in"))
    x = x + shard.act(out, "act")
    nk = new_cache["k"] if new_cache else None
    nv = new_cache["v"] if new_cache else None
    return x, aux, nk, nv


def decoder_fwd(params: dict, cfg: ModelConfig, tokens: jax.Array,
                positions: jax.Array, shard=NOSHARD,
                prefix_embeds: jax.Array | None = None,
                cache: dict | None = None, last_only: bool = False
                ) -> tuple[jax.Array, jax.Array, dict | None]:
    """Returns (logits, aux_loss, new_cache).

    tokens: (B, S) int32.  prefix_embeds: (B, F, d) prepended (VLM/audio).
    cache: {"k": (L,B,max,Hkv,hd), "v": ..., "pos": scalar} for decode.
    """
    x = params["embed"].astype(dtype_of(cfg))[tokens]
    if prefix_embeds is not None:
        x = jnp.concatenate([prefix_embeds.astype(x.dtype), x], axis=1)
        offset = cache["pos"] if cache is not None else 0
        positions = jnp.arange(x.shape[1]) + offset
    x = shard.act(x, "act")

    if cache is None:
        def body(carry, lp):
            x, aux = carry
            x, a, _, _ = _dense_layer_fwd(lp, cfg, x, positions, shard)
            return (x, aux + a), None

        body = _maybe_remat(body, cfg)
        (x, aux), _ = scan_layers(body, (x, jnp.zeros((), jnp.float32)),
                                  params["layers"], cfg)
        new_cache = None
    else:
        pos = cache["pos"]
        ring, new_kpos = ring_info(pos, x.shape[1], cache["k"].shape[2],
                                   cache["kpos"], shard)
        positions = ring["q_pos"]

        def body(carry, inp):
            x, aux = carry
            lp, ck, cv = inp
            x, a, nk, nv = _dense_layer_fwd(lp, cfg, x, positions, shard,
                                            ck, cv, ring)
            return (x, aux + a), (nk, nv)

        (x, aux), (nk, nv) = scan_layers(
            body, (x, jnp.zeros((), jnp.float32)),
            (params["layers"], cache["k"], cache["v"]), cfg)
        # advance by the full written slab (prefix embeds + tokens)
        new_cache = {"k": nk, "v": nv, "pos": pos + x.shape[1],
                     "kpos": new_kpos}

    if last_only:
        x = x[:, -1:]      # serving prefill: head for last token only
    x = rmsnorm(params["ln_f"], x, cfg.norm_eps)
    head = (params["embed"].T if cfg.tie_embeddings
            else params["lm_head"])
    logits = shard.act(x @ head.astype(x.dtype), "logits")
    return logits, aux, new_cache


# ----------------------------------------------------------------------
# batched decode over a layer-major slot cache, written in place
# ----------------------------------------------------------------------

def _slot_layer_step(lp: dict, x, pos, kpos, ck, cv, *, cfg: ModelConfig):
    """One decoder layer for one slot's next token.

    x (1, 1, d); pos () the token's position; kpos (S,) the positions the
    ring held before this step (-1 = empty); ck/cv (Hkv, S, hd) the layer's
    cache before this step.  The token attends the ring's live positions,
    less the one it is about to overwrite, and itself as one extra score
    in the same float32 softmax.  Returns (x, k (Hkv, hd), v (Hkv, hd)).
    """
    hd, hkv = cfg.hd, cfg.n_kv_heads
    g = cfg.n_heads // hkv
    p = lp["attn"]
    h = rmsnorm(lp["ln1"], x, cfg.norm_eps)
    q = (h @ p["wq"]).reshape(1, 1, cfg.n_heads, hd)
    k = (h @ p["wk"]).reshape(1, 1, hkv, hd)
    v = (h @ p["wv"]).reshape(1, 1, hkv, hd)
    if cfg.qk_norm:
        q = rmsnorm(p["q_norm"], q, cfg.norm_eps)
        k = rmsnorm(p["k_norm"], k, cfg.norm_eps)
    q = apply_rope(q, pos[None], cfg.rope_theta)
    k = apply_rope(k, pos[None], cfg.rope_theta)
    k = k[0, 0].astype(ck.dtype)
    v = v[0, 0].astype(cv.dtype)
    qg = q[0, 0].reshape(hkv, g, hd)
    s = ck.shape[1]
    live = (kpos >= 0) & (kpos <= pos) & (jnp.arange(s) != pos % s)
    if cfg.swa_window > 0:
        live &= kpos > pos - cfg.swa_window
    scores = jnp.concatenate([
        jnp.einsum("hgd,hsd->hgs", qg, ck,
                   preferred_element_type=jnp.float32),
        jnp.einsum("hgd,hd->hg", qg, k,
                   preferred_element_type=jnp.float32)[..., None]], axis=-1)
    scores = scores / math.sqrt(hd)
    live = jnp.concatenate([live, jnp.ones((1,), bool)])
    probs = jax.nn.softmax(jnp.where(live, scores, jnp.float32(-1e30)),
                           axis=-1).astype(cv.dtype)
    out = (jnp.einsum("hgs,hsd->hgd", probs[..., :s], cv,
                      preferred_element_type=jnp.float32)
           + jnp.einsum("hg,hd->hgd", probs[..., s], v,
                        preferred_element_type=jnp.float32))
    out = out.reshape(1, 1, cfg.n_heads * hd).astype(x.dtype)
    x = x + out @ p["wo"]
    h = rmsnorm(lp["ln2"], x, cfg.norm_eps)
    if cfg.is_moe:
        out, _ = moe_fwd(lp["moe"], cfg, h)
    else:
        out = mlp_fwd(lp["mlp"], h)
    return x + out, k, v


def decoder_decode_slots(params: dict, cfg: ModelConfig, tokens: jax.Array,
                         cache: dict) -> tuple[jax.Array, dict]:
    """One decode step for every slot of a layer-major slot cache.

    tokens (B, 1, 1); cache {"k"/"v": (L, B, Hkv, S, hd), "kpos": (B, S),
    "pos": (B,)}.  The scan runs over layers, each layer ``vmap``s the
    single-slot step over slots and yields only the new keys and values;
    one ``dynamic_update_slice`` per slot then writes them at
    ``pos % S``, in place where the caller donates the cache.
    Returns (logits (B, 1, 1, V), new cache).
    """
    b = tokens.shape[0]
    s = cache["k"].shape[3]
    pos, kpos = cache["pos"], cache["kpos"]
    x = params["embed"].astype(dtype_of(cfg))[tokens]        # (B, 1, 1, d)
    step = jax.vmap(partial(_slot_layer_step, cfg=cfg),
                    in_axes=(None, 0, 0, 0, 0, 0))

    def body(x, inp):
        lp, ck, cv = inp
        x, nk, nv = step(lp, x, pos, kpos, ck, cv)
        return x, (nk, nv)

    x, (nk, nv) = scan_layers(body, x, (params["layers"], cache["k"],
                                        cache["v"]), cfg)
    x = rmsnorm(params["ln_f"], x, cfg.norm_eps)
    head = (params["embed"].T if cfg.tie_embeddings
            else params["lm_head"])
    logits = x @ head.astype(x.dtype)

    ring = pos % s
    ck, cv = cache["k"], cache["v"]
    for i in range(b):        # slot i's (L, Hkv, hd) as (L, 1, Hkv, 1, hd)
        at = (0, i, 0, ring[i], 0)
        ck = jax.lax.dynamic_update_slice(ck, nk[:, i][:, None, :, None], at)
        cv = jax.lax.dynamic_update_slice(cv, nv[:, i][:, None, :, None], at)
    return logits, {"k": ck, "v": cv,
                    "kpos": kpos.at[jnp.arange(b), ring].set(pos),
                    "pos": pos + 1}


# ----------------------------------------------------------------------
# encoder-decoder (seamless-m4t style)
# ----------------------------------------------------------------------

def encdec_init(key, cfg: ModelConfig) -> dict:
    dt = dtype_of(cfg)
    k_emb, k_enc, k_dec, k_head = jax.random.split(key, 4)

    def enc_layer(k):
        ka, kb = jax.random.split(k)
        return {
            "ln1": rmsnorm_init(cfg.d_model, dt),
            "attn": attention_init(ka, cfg),
            "ln2": rmsnorm_init(cfg.d_model, dt),
            "mlp": mlp_init(kb, cfg.d_model, cfg.d_ff, dt),
        }

    def dec_layer(k):
        ka, kb, kc = jax.random.split(k, 3)
        return {
            "ln1": rmsnorm_init(cfg.d_model, dt),
            "attn": attention_init(ka, cfg),
            "ln_x": rmsnorm_init(cfg.d_model, dt),
            "xattn": attention_init(kb, cfg, cross=True),
            "ln2": rmsnorm_init(cfg.d_model, dt),
            "mlp": mlp_init(kc, cfg.d_model, cfg.d_ff, dt),
        }

    return {
        "embed": (jax.random.normal(k_emb, (cfg.vocab, cfg.d_model),
                                    jnp.float32) * 0.02).astype(dt),
        "encoder": stack_init(k_enc, cfg.enc_layers, enc_layer),
        "decoder": stack_init(k_dec, cfg.n_layers, dec_layer),
        "ln_enc": rmsnorm_init(cfg.d_model, dt),
        "ln_f": rmsnorm_init(cfg.d_model, dt),
        "lm_head": dense_init(k_head, cfg.d_model, cfg.vocab, dt),
    }


def encode(params: dict, cfg: ModelConfig, src_embeds: jax.Array,
           shard=NOSHARD) -> jax.Array:
    """Bidirectional encoder over frontend frame embeddings."""
    x = shard.act(src_embeds.astype(dtype_of(cfg)), "act")
    positions = jnp.arange(x.shape[1])

    def body(x, lp):
        h = rmsnorm(lp["ln1"], x, cfg.norm_eps)
        # bidirectional: no mask, no cache
        a, _ = attention_fwd(lp["attn"], cfg, h, positions,
                             kv_source=h)
        x = x + shard.act(a, "act")
        h = rmsnorm(lp["ln2"], x, cfg.norm_eps)
        x = x + shard.act(mlp_fwd(lp["mlp"], h), "act")
        return x, None

    body = _maybe_remat(body, cfg)
    x, _ = scan_layers(body, x, params["encoder"], cfg)
    return rmsnorm(params["ln_enc"], x, cfg.norm_eps)


def encdec_fwd(params: dict, cfg: ModelConfig, tokens: jax.Array,
               enc_out: jax.Array, positions: jax.Array, shard=NOSHARD,
               cache: dict | None = None, last_only: bool = False
               ) -> tuple[jax.Array, jax.Array, dict | None]:
    x = params["embed"].astype(dtype_of(cfg))[tokens]
    x = shard.act(x, "act")

    def layer(lp, x, ck=None, cv=None, ring=None):
        h = rmsnorm(lp["ln1"], x, cfg.norm_eps)
        kv = None if ck is None else {"k": ck, "v": cv, **ring}
        a, nc = attention_fwd(lp["attn"], cfg, h, positions, kv_cache=kv)
        x = x + shard.act(a, "act")
        h = rmsnorm(lp["ln_x"], x, cfg.norm_eps)
        a, _ = attention_fwd(lp["xattn"], cfg, h, positions,
                             kv_source=enc_out)
        x = x + shard.act(a, "act")
        h = rmsnorm(lp["ln2"], x, cfg.norm_eps)
        x = x + shard.act(mlp_fwd(lp["mlp"], h), "act")
        return x, nc

    if cache is None:
        def body(x, lp):
            x, _ = layer(lp, x)
            return x, None
        body = _maybe_remat(body, cfg)
        x, _ = scan_layers(body, x, params["decoder"], cfg)
        new_cache = None
    else:
        pos = cache["pos"]
        ring, new_kpos = ring_info(pos, tokens.shape[1],
                                   cache["k"].shape[2], cache["kpos"],
                                   shard)
        positions = ring["q_pos"]

        def body(x, inp):
            lp, ck, cv = inp
            x, nc = layer(lp, x, ck, cv, ring)
            return x, (nc["k"], nc["v"])

        x, (nk, nv) = scan_layers(
            body, x, (params["decoder"], cache["k"], cache["v"]), cfg)
        new_cache = {"k": nk, "v": nv, "pos": pos + tokens.shape[1],
                     "kpos": new_kpos}

    if last_only:
        x = x[:, -1:]      # serving prefill: head for last token only
    x = rmsnorm(params["ln_f"], x, cfg.norm_eps)
    logits = shard.act(x @ params["lm_head"].astype(x.dtype), "logits")
    return logits, jnp.zeros((), jnp.float32), new_cache


# ----------------------------------------------------------------------
# zamba2-style hybrid: Mamba2 backbone + ONE shared attention block
# ----------------------------------------------------------------------

def hybrid_init(key, cfg: ModelConfig) -> dict:
    dt = dtype_of(cfg)
    k_emb, k_blocks, k_tail, k_shared, k_head = jax.random.split(key, 5)
    n_super = cfg.n_layers // cfg.attn_every
    n_tail = cfg.n_layers % cfg.attn_every

    def mamba_layer(k):
        return {"ln": rmsnorm_init(cfg.d_model, dt),
                "mamba": S.mamba2_init(k, cfg)}

    def super_block(k):
        return stack_init(k, cfg.attn_every, mamba_layer)

    ka, kb = jax.random.split(k_shared)
    params = {
        "embed": (jax.random.normal(k_emb, (cfg.vocab, cfg.d_model),
                                    jnp.float32) * 0.02).astype(dt),
        "blocks": stack_init(k_blocks, n_super, super_block),
        # zamba2's signature: a single parameter-shared attn+MLP block
        "shared": {
            "ln1": rmsnorm_init(cfg.d_model, dt),
            "attn": attention_init(ka, cfg),
            "ln2": rmsnorm_init(cfg.d_model, dt),
            "mlp": mlp_init(kb, cfg.d_model, cfg.d_ff, dt),
        },
        "ln_f": rmsnorm_init(cfg.d_model, dt),
        "lm_head": dense_init(k_head, cfg.d_model, cfg.vocab, dt),
    }
    if n_tail:
        params["tail"] = stack_init(k_tail, n_tail, mamba_layer)
    return params


def hybrid_fwd(params: dict, cfg: ModelConfig, tokens: jax.Array,
               positions: jax.Array, shard=NOSHARD,
               cache: dict | None = None, last_only: bool = False
               ) -> tuple[jax.Array, jax.Array, dict | None]:
    """cache (decode): {"ssm": (n_super, k, B,H,P,N), "ssm_tail": (tail,...),
    "k"/"v": (n_apps, B, max, Hkv, hd), "pos"}."""
    x = params["embed"].astype(dtype_of(cfg))[tokens]
    x = shard.act(x, "act")
    shared = params["shared"]

    def shared_block(x, ck=None, cv=None, ring=None):
        h = rmsnorm(shared["ln1"], x, cfg.norm_eps)
        kv = None if ck is None else {"k": ck, "v": cv, **ring}
        a, nc = attention_fwd(shared["attn"], cfg, h, positions,
                              kv_cache=kv)
        x = x + shard.act(a, "act")
        h = rmsnorm(shared["ln2"], x, cfg.norm_eps)
        x = x + shard.act(mlp_fwd(shared["mlp"], h), "act")
        return x, nc

    if cache is None:
        def mamba_body(x, lp):
            h = rmsnorm(lp["ln"], x, cfg.norm_eps)
            y, _ = S.mamba2_fwd(lp["mamba"], cfg, h)
            return x + shard.act(y, "act"), None

        mamba_body = _maybe_remat(mamba_body, cfg)

        def super_body(x, block):
            x, _ = scan_layers(mamba_body, x, block, cfg)
            x, _ = shared_block(x)
            return x, None

        x, _ = scan_layers(super_body, x, params["blocks"], cfg)
        if "tail" in params:
            x, _ = scan_layers(mamba_body, x, params["tail"], cfg)
        new_cache = None
    else:
        pos = cache["pos"]
        single = tokens.shape[1] == 1   # static: decode vs prefill-with-state
        ring, new_kpos = ring_info(pos, tokens.shape[1],
                                   cache["k"].shape[2], cache["kpos"],
                                   shard)
        positions = ring["q_pos"]

        def mamba_step_body(x, inp):
            lp, st = inp
            h = rmsnorm(lp["ln"], x, cfg.norm_eps)
            if single:
                y, new_st = S.mamba2_step(lp["mamba"], cfg, h, st)
            else:
                y, new_st = S.mamba2_fwd(lp["mamba"], cfg, h, state=st)
            return x + shard.act(y, "act"), new_st

        def super_body(x, inp):
            block, st, ck, cv = inp
            x, new_st = scan_layers(mamba_step_body, x, (block, st), cfg)
            x, nc = shared_block(x, ck, cv, ring)
            return x, (new_st, nc["k"], nc["v"])

        x, (new_ssm, nk, nv) = scan_layers(
            super_body, x,
            (params["blocks"], cache["ssm"], cache["k"], cache["v"]), cfg)
        new_tail = None
        if "tail" in params:
            x, new_tail = scan_layers(mamba_step_body, x,
                                      (params["tail"], cache["ssm_tail"]),
                                      cfg)
        new_cache = {"ssm": new_ssm, "k": nk, "v": nv,
                     "pos": pos + tokens.shape[1], "kpos": new_kpos}
        if new_tail is not None:
            new_cache["ssm_tail"] = new_tail

    if last_only:
        x = x[:, -1:]      # serving prefill: head for last token only
    x = rmsnorm(params["ln_f"], x, cfg.norm_eps)
    logits = shard.act(x @ params["lm_head"].astype(x.dtype), "logits")
    return logits, jnp.zeros((), jnp.float32), new_cache


# ----------------------------------------------------------------------
# xLSTM stack: alternating (mLSTM, sLSTM) pairs
# ----------------------------------------------------------------------

def xlstm_init(key, cfg: ModelConfig) -> dict:
    dt = dtype_of(cfg)
    k_emb, k_pairs, k_head = jax.random.split(key, 3)
    n_pairs = cfg.n_layers // 2

    def pair_init(k):
        ka, kb = jax.random.split(k)
        return {
            "ln_m": rmsnorm_init(cfg.d_model, dt),
            "mlstm": S.mlstm_init(ka, cfg),
            "ln_s": rmsnorm_init(cfg.d_model, dt),
            "slstm": S.slstm_init(kb, cfg),
        }

    return {
        "embed": (jax.random.normal(k_emb, (cfg.vocab, cfg.d_model),
                                    jnp.float32) * 0.02).astype(dt),
        "pairs": stack_init(k_pairs, n_pairs, pair_init),
        "ln_f": rmsnorm_init(cfg.d_model, dt),
        "lm_head": dense_init(k_head, cfg.d_model, cfg.vocab, dt),
    }


def xlstm_fwd(params: dict, cfg: ModelConfig, tokens: jax.Array,
              shard=NOSHARD, cache: dict | None = None,
              last_only: bool = False
              ) -> tuple[jax.Array, jax.Array, dict | None]:
    """cache (decode): per-pair recurrent states, stacked on axis 0."""
    x = params["embed"].astype(dtype_of(cfg))[tokens]
    x = shard.act(x, "act")

    def pair_body(x, inp):
        if cache is None:
            lp = inp
            m_state = s_state = None
        else:
            lp, m_state, s_state = inp
        h = rmsnorm(lp["ln_m"], x, cfg.norm_eps)
        y, new_m = S.mlstm_fwd(lp["mlstm"], cfg, h, m_state)
        x = x + shard.act(y, "act")
        h = rmsnorm(lp["ln_s"], x, cfg.norm_eps)
        y, new_s = S.slstm_fwd(lp["slstm"], cfg, h, s_state)
        x = x + shard.act(y, "act")
        return x, (new_m, new_s)

    if cache is None:
        body = _maybe_remat(lambda x, lp: (pair_body(x, lp)[0], None), cfg)
        x, _ = scan_layers(body, x, params["pairs"], cfg)
        new_cache = None
    else:
        x, (new_m, new_s) = scan_layers(
            pair_body, x, (params["pairs"], cache["mlstm"], cache["slstm"]),
            cfg)
        new_cache = {"mlstm": new_m, "slstm": new_s,
                     "pos": cache["pos"] + tokens.shape[1]}

    if last_only:
        x = x[:, -1:]      # serving prefill: head for last token only
    x = rmsnorm(params["ln_f"], x, cfg.norm_eps)
    logits = shard.act(x @ params["lm_head"].astype(x.dtype), "logits")
    return logits, jnp.zeros((), jnp.float32), new_cache
