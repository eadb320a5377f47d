"""Seeded weights of any architecture, made on the device from ``--seed``.

The benchmark, not the program, makes the weights: one jitted call builds
the whole tree in the layout the program's model takes, in bfloat16 on
the serving device.  The architecture module (``archs/<arch>.py``) lists
the leaves, their shapes and scales; this module draws them.  The
reference rebuilds any single layer from the same seed (``layer_f32``),
so it never takes an array the program has held.

Every weight is an odd integer in [-255, 255] times a per-leaf scale.  An
8-bit integer is exact in bfloat16 and the product is one IEEE multiply,
so the values do not depend on how XLA fuses the generator: the stacked
draw for the program and the per-layer draw for the reference agree bit
for bit.  Each leaf's key is the seed's folded with a hash of the leaf's
path (and, for a stacked leaf, with the layer's index).
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


def _leaves(key, leaves: dict, dtype, index=None) -> dict:
    """Draws {path: (shape, std[, dtype])} into a tree, each leaf in its
    own dtype where it names one; a stacked leaf's key is its path's folded
    with the layer's index."""
    out: dict = {}
    for path, (shape, std, *own) in leaves.items():
        k = _leaf_key(key, path)
        if index is not None:
            k = jax.random.fold_in(k, index)
        _set(out, path, _draw(k, shape, std, own[0] if own else dtype))
    return out


def _stack(key, arch, cfg: dict, name: str, index, dtype) -> dict:
    _, leaves = arch.stacks(cfg)[name]
    return _leaves(key, {(name,) + p: v for p, v in leaves.items()}, dtype,
                   index)[name]


def _arch(cfg: dict, arch):
    if arch is not None:
        return arch
    from benchmarks.chip import spec
    return spec.arch(cfg["arch"])


def make_params(cfg: dict, seed: int, device, arch=None) -> dict:
    """The whole bfloat16 tree on ``device``, in one jitted call."""
    arch = _arch(cfg, arch)

    def build(key):
        params = _leaves(key, arch.outer_leaves(cfg), jnp.bfloat16)
        for name, (n, _) in arch.stacks(cfg).items():
            params[name] = jax.vmap(lambda i, name=name: _stack(
                key, arch, cfg, name, i, jnp.bfloat16))(jnp.arange(n))
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

    def __init__(self, cfg: dict, seed: int, arch=None) -> None:
        self.cfg = cfg
        self.arch = arch = _arch(cfg, arch)
        self.key = root_key(seed)
        self._layer = jax.jit(lambda key, i, name: _f32(_stack(
            key, arch, cfg, name, i, jnp.bfloat16)), static_argnums=2)
        outer = arch.outer_leaves(cfg)
        self._outer = jax.jit(lambda key, path: _f32(_under(_leaves(
            key, {p: v for p, v in outer.items() if p[:len(path)] == path},
            jnp.bfloat16), path)), static_argnums=1)

    def layer_f32(self, stack: str, i: int) -> dict:
        """Layer ``i`` of the stack ``stack``, in float32."""
        return self._layer(self.key, jnp.int32(i), stack)

    def outer_f32(self, path: tuple):
        """The float32 leaf at ``path``, or the subtree under it."""
        return self._outer(self.key, tuple(path))


def _under(tree, path):
    for p in path:
        tree = tree[p]
    return tree


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)
