"""Seeded weights of a dense decoder, made on the device from ``--seed``.

The benchmark, not the program, makes the weights: one jitted call builds
the whole tree in the layout the program's decoder takes, in bfloat16 on
the serving device.  The reference rebuilds any single layer from the same
seed (``layer_f32``), so it never takes an array the program has held.

Every weight is an odd integer in [-255, 255] times a per-leaf scale.  An
8-bit integer is exact in bfloat16 and the product is one IEEE multiply,
so the values do not depend on how XLA fuses the generator: the stacked
draw for the program and the per-layer draw for the reference agree bit
for bit.  The scales follow the program's own initialiser: 1/sqrt(fan_in)
for projections, 0.02 for the embedding, 1 for norm scales.
"""

from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp
import numpy as np

# standard deviation of the odd integers in [-255, 255] drawn uniformly
_INT_STD = math.sqrt((256 ** 2 - 1) / 3)


def root_key(seed: int) -> jax.Array:
    """A key from any whole number: the low and high 32 bits are folded in
    separately, so seeds past 2**32 stay distinct."""
    s = int(seed) % (1 << 64)
    k = jax.random.key(0)
    k = jax.random.fold_in(k, np.uint32(s & 0xFFFFFFFF))
    return jax.random.fold_in(k, np.uint32(s >> 32))


def dims(cfg: dict) -> dict:
    d = cfg["hidden_size"]
    hd = cfg.get("head_dim") or d // cfg["num_attention_heads"]
    return {"d": d, "hd": hd, "hq": cfg["num_attention_heads"],
            "hkv": cfg["num_key_value_heads"],
            "ff": cfg["intermediate_size"], "vocab": cfg["vocab_size"],
            "layers": cfg["num_hidden_layers"]}


def layer_leaves(cfg: dict) -> dict:
    """Per-layer leaves: path -> (shape, scale); scale None is a norm."""
    m = dims(cfg)
    d, hd, hq, hkv, ff = m["d"], m["hd"], m["hq"], m["hkv"], m["ff"]
    leaves = {
        ("ln1", "scale"): ((d,), None),
        ("attn", "wq"): ((d, hq * hd), d),
        ("attn", "wk"): ((d, hkv * hd), d),
        ("attn", "wv"): ((d, hkv * hd), d),
        ("attn", "wo"): ((hq * hd, d), hq * hd),
        ("ln2", "scale"): ((d,), None),
        ("mlp", "w_gate"): ((d, ff), d),
        ("mlp", "w_up"): ((d, ff), d),
        ("mlp", "w_down"): ((ff, d), ff),
    }
    if cfg.get("qk_norm"):
        leaves[("attn", "q_norm", "scale")] = ((hd,), None)
        leaves[("attn", "k_norm", "scale")] = ((hd,), None)
    return leaves


def _draw(key, shape, std: float | None, dtype):
    if std is None:
        return jnp.ones(shape, dtype)
    odd = jax.random.bits(key, shape, jnp.uint8).astype(jnp.int32) * 2 - 255
    scale = np.float32(std / _INT_STD)
    return (odd.astype(jnp.float32) * scale).astype(dtype)


def _leaf_key(key, path) -> jax.Array:
    # a stable id per leaf path, independent of dict order
    return jax.random.fold_in(key, np.uint32(zlib.crc32("/".join(path)
                                                        .encode())))


def _set(tree: dict, path, value) -> None:
    for p in path[:-1]:
        tree = tree.setdefault(p, {})
    tree[path[-1]] = value


def _layer(key, cfg: dict, layer, dtype) -> dict:
    out: dict = {}
    for path, (shape, fan_in) in layer_leaves(cfg).items():
        k = jax.random.fold_in(_leaf_key(key, ("layers",) + path), layer)
        std = None if fan_in is None else 1.0 / math.sqrt(fan_in)
        _set(out, path, _draw(k, shape, std, dtype))
    return out


def _outer(key, cfg: dict, name: str, dtype):
    m = dims(cfg)
    k = _leaf_key(key, (name,))
    if name == "embed":
        return _draw(k, (m["vocab"], m["d"]), 0.02, dtype)
    if name == "lm_head":
        return _draw(k, (m["d"], m["vocab"]), 1.0 / math.sqrt(m["d"]), dtype)
    return {"scale": _draw(k, (m["d"],), None, dtype)}      # ln_f


def outer_names(cfg: dict) -> tuple[str, ...]:
    return ("embed", "ln_f") + (() if cfg.get("tie_word_embeddings")
                                else ("lm_head",))


def make_params(cfg: dict, seed: int, device) -> dict:
    """The whole bfloat16 tree on ``device``, in one jitted call."""
    n = dims(cfg)["layers"]

    def build(key):
        params = {name: _outer(key, cfg, name, jnp.bfloat16)
                  for name in outer_names(cfg)}
        params["layers"] = jax.vmap(
            lambda i: _layer(key, cfg, i, jnp.bfloat16))(jnp.arange(n))
        return params

    out = jax.sharding.SingleDeviceSharding(device)
    with jax.default_device(device):
        key = root_key(seed)
    return jax.jit(build, out_shardings=out)(key)


def check_layout(params: dict, model) -> None:
    """The tree has the program's own layout: same paths, shapes, dtypes."""
    want = jax.eval_shape(model.init, jax.random.key(0))
    got = jax.tree.map(lambda a: (a.shape, a.dtype), params)
    want = jax.tree.map(lambda a: (a.shape, a.dtype), want)
    if jax.tree.structure(got) != jax.tree.structure(want) or got != want:
        raise ValueError("the program's parameter layout differs from the "
                         f"benchmark's: {want} vs {got}")


class LayerMaker:
    """Rebuilds weights one layer at a time, for the reference."""

    def __init__(self, cfg: dict, seed: int) -> None:
        self.cfg = cfg
        self.key = root_key(seed)
        self._layer = jax.jit(lambda key, i: _f32(
            _layer(key, cfg, i, jnp.bfloat16)))
        self._outer = jax.jit(lambda key, name: _f32(
            _outer(key, cfg, name, jnp.bfloat16)), static_argnums=1)

    def layer_f32(self, i: int) -> dict:
        return self._layer(self.key, jnp.int32(i))

    def outer_f32(self, name: str):
        return self._outer(self.key, name)


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)
