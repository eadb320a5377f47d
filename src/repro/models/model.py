"""Unified model facade: build_model(cfg) -> Model with init / loss /
prefill / decode_step / decode_step_inplace / init_cache / input_specs.

The same entry points serve four consumers:
  - CPU smoke tests (reduced configs, no sharding),
  - the serving engine (prefill + decode with KV/state caches),
  - the trainer (loss -> grad),
  - the multi-pod dry-run (input_specs -> ShapeDtypeStruct lowering).
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

from repro.models import transformer as T
from repro.models.config import ModelConfig
from repro.models.layers import dtype_of
from repro.models.ssm import mamba2_fwd, mamba2_step


@dataclass
class Model:
    cfg: ModelConfig

    # ------------------------------------------------------------------
    # params
    # ------------------------------------------------------------------

    def init(self, key: jax.Array) -> dict:
        cfg = self.cfg
        if cfg.family == "encdec":
            return T.encdec_init(key, cfg)
        if cfg.family == "hybrid":
            return T.hybrid_init(key, cfg)
        if cfg.family == "ssm" and cfg.xlstm:
            return T.xlstm_init(key, cfg)
        return T.decoder_init(key, cfg)

    # ------------------------------------------------------------------
    # forward / loss
    # ------------------------------------------------------------------

    def forward(self, params: dict, batch: dict, shard=T.NOSHARD
                ) -> tuple[jax.Array, jax.Array]:
        """Full-sequence logits for training. Returns (logits, aux_loss)."""
        cfg = self.cfg
        tokens = batch["tokens"]
        if cfg.family == "encdec":
            enc_out = T.encode(params, cfg, batch["frontend"], shard)
            positions = jnp.arange(tokens.shape[1])
            logits, aux, _ = T.encdec_fwd(params, cfg, tokens, enc_out,
                                          positions, shard)
        elif cfg.family == "hybrid":
            positions = jnp.arange(tokens.shape[1])
            logits, aux, _ = T.hybrid_fwd(params, cfg, tokens, positions,
                                          shard)
        elif cfg.family == "ssm" and cfg.xlstm:
            logits, aux, _ = T.xlstm_fwd(params, cfg, tokens, shard)
        else:
            positions = jnp.arange(tokens.shape[1])
            prefix = batch.get("frontend")
            logits, aux, _ = T.decoder_fwd(params, cfg, tokens, positions,
                                           shard, prefix_embeds=prefix)
            if prefix is not None:
                logits = logits[:, prefix.shape[1]:]
        return logits, aux

    def loss(self, params: dict, batch: dict, shard=T.NOSHARD) -> jax.Array:
        logits, aux = self.forward(params, batch, shard)
        labels = batch["labels"]
        logits = logits.astype(jnp.float32)
        logp = jax.nn.log_softmax(logits, axis=-1)
        valid = (labels >= 0)
        safe = jnp.where(valid, labels, 0)
        tok_lp = jnp.take_along_axis(logp, safe[..., None],
                                     axis=-1)[..., 0]
        n = jnp.maximum(jnp.sum(valid), 1)
        ce = -jnp.sum(jnp.where(valid, tok_lp, 0.0)) / n
        return ce + aux

    # ------------------------------------------------------------------
    # serving: cache + prefill + decode
    # ------------------------------------------------------------------

    def init_cache(self, batch: int, max_seq: int,
                   src_len: int = 0) -> dict:
        cfg = self.cfg
        dt = dtype_of(cfg)
        hd = cfg.hd
        if cfg.family == "hybrid":
            n_super = cfg.n_layers // cfg.attn_every
            n_tail = cfg.n_layers % cfg.attn_every
            kv_len = max_seq if cfg.swa_window == 0 else min(
                max_seq, cfg.swa_window)
            cache = {
                "ssm": jnp.zeros((n_super, cfg.attn_every, batch,
                                  cfg.ssm_heads, cfg.ssm_head_dim,
                                  cfg.ssm_state), jnp.float32),
                "k": jnp.zeros((n_super, batch, max_seq, cfg.n_kv_heads,
                                hd), dt),
                "v": jnp.zeros((n_super, batch, max_seq, cfg.n_kv_heads,
                                hd), dt),
                "kpos": jnp.full((max_seq,), -1, jnp.int32),
                "pos": jnp.zeros((), jnp.int32),
            }
            if n_tail:
                cache["ssm_tail"] = jnp.zeros(
                    (n_tail, batch, cfg.ssm_heads, cfg.ssm_head_dim,
                     cfg.ssm_state), jnp.float32)
            return cache
        if cfg.family == "ssm" and cfg.xlstm:
            n_pairs = cfg.n_layers // 2
            h = cfg.n_heads
            dh = cfg.d_model // h
            d = cfg.d_model
            return {
                "mlstm": (jnp.zeros((n_pairs, batch, h, dh, dh),
                                    jnp.float32),
                          jnp.zeros((n_pairs, batch, h, dh), jnp.float32),
                          jnp.full((n_pairs, batch, h), -1e30,
                                   jnp.float32)),
                "slstm": (jnp.zeros((n_pairs, batch, d), jnp.float32),
                          jnp.zeros((n_pairs, batch, d), jnp.float32),
                          jnp.zeros((n_pairs, batch, d), jnp.float32),
                          jnp.full((n_pairs, batch, d), -1e30,
                                   jnp.float32)),
                "pos": jnp.zeros((), jnp.int32),
            }
        # dense / moe / vlm / encdec: per-layer KV cache
        kv_len = max_seq if cfg.swa_window == 0 else min(max_seq,
                                                         cfg.swa_window)
        cache = {
            "k": jnp.zeros((cfg.n_layers, batch, kv_len, cfg.n_kv_heads,
                            hd), dt),
            "v": jnp.zeros((cfg.n_layers, batch, kv_len, cfg.n_kv_heads,
                            hd), dt),
            "kpos": jnp.full((kv_len,), -1, jnp.int32),
            "pos": jnp.zeros((), jnp.int32),
        }
        if cfg.family == "encdec":
            cache["enc_out"] = jnp.zeros((batch, src_len, cfg.d_model), dt)
        return cache

    def prefill(self, params: dict, tokens: jax.Array, cache: dict,
                shard=T.NOSHARD, frontend: jax.Array | None = None
                ) -> tuple[jax.Array, dict]:
        """Process the prompt, fill the cache, return last-position logits."""
        cfg = self.cfg
        s = tokens.shape[1]
        positions = jnp.arange(s) + cache["pos"]
        if cfg.family == "encdec":
            enc_out = T.encode(params, cfg, frontend, shard)
            cache = dict(cache, enc_out=enc_out)
            logits, _, new_cache = T.encdec_fwd(
                params, cfg, tokens, enc_out, positions, shard,
                cache={k: cache[k] for k in ("k", "v", "kpos", "pos")},
                last_only=True)
            new_cache["enc_out"] = enc_out
        elif cfg.family == "hybrid":
            logits, _, new_cache = T.hybrid_fwd(params, cfg, tokens,
                                                positions, shard,
                                                cache=cache, last_only=True)
        elif cfg.family == "ssm" and cfg.xlstm:
            logits, _, new_cache = T.xlstm_fwd(params, cfg, tokens, shard,
                                               cache=cache, last_only=True)
        else:
            logits, _, new_cache = T.decoder_fwd(params, cfg, tokens,
                                                 positions, shard,
                                                 prefix_embeds=frontend,
                                                 cache=cache,
                                                 last_only=True)
        return logits[:, -1:], new_cache

    def decode_step(self, params: dict, tokens: jax.Array, cache: dict,
                    shard=T.NOSHARD) -> tuple[jax.Array, dict]:
        """One decode step: tokens (B, 1) -> logits (B, 1, V), new cache."""
        return self.prefill(params, tokens, cache, shard) \
            if self.cfg.family == "encdec" and "enc_out" not in cache \
            else self._step(params, tokens, cache, shard)

    def decode_step_inplace(self, params: dict, tokens: jax.Array,
                            cache: dict) -> tuple[jax.Array, dict]:
        """One decode step for a batch of slots over a layer-major slot
        cache (``transformer.decoder_decode_slots``): tokens (B, 1, 1) ->
        logits (B, 1, 1, V), new cache.  Each slot's new keys and values
        are written at its own position, in place where the caller
        donates the cache.  For the per-layer K/V cache of
        ``decoder_fwd`` (dense, moe, vlm) only."""
        return T.decoder_decode_slots(params, self.cfg, tokens, cache)

    def _step(self, params, tokens, cache, shard):
        cfg = self.cfg
        s = tokens.shape[1]
        positions = jnp.arange(s) + cache["pos"]
        if cfg.family == "encdec":
            logits, _, new_cache = T.encdec_fwd(
                params, cfg, tokens, cache["enc_out"], positions, shard,
                cache={k: cache[k] for k in ("k", "v", "kpos", "pos")})
            new_cache["enc_out"] = cache["enc_out"]
        elif cfg.family == "hybrid":
            logits, _, new_cache = T.hybrid_fwd(params, cfg, tokens,
                                                positions, shard,
                                                cache=cache)
        elif cfg.family == "ssm" and cfg.xlstm:
            logits, _, new_cache = T.xlstm_fwd(params, cfg, tokens, shard,
                                               cache=cache)
        else:
            logits, _, new_cache = T.decoder_fwd(params, cfg, tokens,
                                                 positions, shard,
                                                 cache=cache)
        return logits, new_cache

    # ------------------------------------------------------------------
    # dry-run input specs
    # ------------------------------------------------------------------

    def input_specs(self, shape: "ShapeSpec") -> dict:
        """ShapeDtypeStruct stand-ins for every model input of a shape cell."""
        cfg = self.cfg
        i32 = jnp.int32
        B, S = shape.global_batch, shape.seq_len
        f = jax.ShapeDtypeStruct
        dt = dtype_of(cfg)
        if shape.kind == "train":
            batch = {"tokens": f((B, S), i32), "labels": f((B, S), i32)}
            if cfg.family == "vlm":
                ftok = cfg.frontend_tokens
                batch = {"tokens": f((B, S - ftok), i32),
                         "labels": f((B, S - ftok), i32),
                         "frontend": f((B, ftok, cfg.d_model), dt)}
            elif cfg.family == "encdec":
                batch["frontend"] = f((B, S, cfg.d_model), dt)
            return {"batch": batch}
        if shape.kind == "prefill":
            cache = jax.eval_shape(
                lambda: self.init_cache(B, S, src_len=S))
            spec = {"tokens": f((B, S), i32), "cache": cache}
            if cfg.family == "encdec":
                spec["frontend"] = f((B, S, cfg.d_model), dt)
            if cfg.family == "vlm":
                ftok = cfg.frontend_tokens
                spec["tokens"] = f((B, S - ftok), i32)
                spec["frontend"] = f((B, ftok, cfg.d_model), dt)
            return spec
        # decode: one new token against a seq_len-deep cache
        cache = jax.eval_shape(
            lambda: self.init_cache(B, S, src_len=min(S, 4096)))
        return {"tokens": f((B, 1), i32), "cache": cache}


def build_model(cfg: ModelConfig) -> Model:
    return Model(cfg)


@dataclass(frozen=True)
class ShapeSpec:
    name: str
    kind: str          # train | prefill | decode
    seq_len: int
    global_batch: int
