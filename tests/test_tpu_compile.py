"""Ahead-of-time compiles for a described TPU v5e chip (no chip attached).

The TPU compiler refuses what interpret mode accepts: block shapes that do
not tile, primitives Mosaic cannot lower, programs that do not fit the
chip's memory.  These compiles catch that on every run of the suite, at the
widths the chip smoke test serves: the Pallas kernels at qwen3-0.6b and
zamba2-7b widths, and the serving engine's decode step for qwen3-0.6b.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and the suite runs in several.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import ARCHS
from repro.kernels.flash_attention import flash_attention_kernel
from repro.kernels.paged_attention import paged_attention_kernel
from repro.kernels.ssd_scan import ssd_scan_kernel
from repro.models import build_model
from repro.serving.engine import decode_fn, slot_cache

V5E_HBM_BYTES = 16 * 2**30


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    with pytest.MonkeyPatch.context() as mp:
        # else the TPU compiler writes its logs under the temp directory
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler here, or no topology
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip is written to the persistent
        # cache but cannot be read back without the chip
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", enabled)
            cc.reset_cache()


def _spec(one_chip, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def test_flash_attention_qwen3_widths(one_chip):
    cfg = ARCHS["qwen3-0.6b"]
    s, hq, hkv, hd = 1024, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    _compile(lambda q, k, v: flash_attention_kernel(q, k, v, causal=True),
             _spec(one_chip, (1, s, hq, hd)),
             _spec(one_chip, (1, s, hkv, hd)),
             _spec(one_chip, (1, s, hkv, hd)))


def test_paged_attention_qwen3_widths(one_chip):
    cfg = ARCHS["qwen3-0.6b"]
    b, page, per_seq = 8, 16, 2048 // 16
    hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    _compile(paged_attention_kernel,
             _spec(one_chip, (b, hq, hd)),
             _spec(one_chip, (b * per_seq, page, hkv, hd)),
             _spec(one_chip, (b * per_seq, page, hkv, hd)),
             _spec(one_chip, (b, per_seq), jnp.int32),
             _spec(one_chip, (b,), jnp.int32))


def test_ssd_scan_zamba2_widths(one_chip):
    cfg = ARCHS["zamba2-7b"]
    h, p, n, seq = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state, 1024
    assert (h, p, n) == (112, 64, 64)
    f32 = jnp.float32
    _compile(lambda x, a, B, C: ssd_scan_kernel(x, a, B, C)[0],
             _spec(one_chip, (1, seq, h, p), f32),
             _spec(one_chip, (1, seq, h), f32),
             _spec(one_chip, (1, seq, n), f32),
             _spec(one_chip, (1, seq, n), f32))


def test_engine_decode_qwen3_fits_one_chip(one_chip):
    """The engine's decode step at published width: 8 slots x 2048."""
    model = build_model(ARCHS["qwen3-0.6b"])
    place = lambda s: _spec(one_chip, s.shape, s.dtype)  # noqa: E731
    params = jax.tree.map(place, jax.eval_shape(model.init,
                                                jax.random.key(0)))
    cache = jax.tree.map(place, jax.eval_shape(
        lambda: slot_cache(model, 8, 2048)))
    toks = _spec(one_chip, (8, 1, 1), jnp.int32)
    compiled = decode_fn(model).lower(params, toks, cache).compile()
    mem = compiled.memory_analysis()
    # the donated cache is written in place: its output is the argument
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert total < V5E_HBM_BYTES, total
    # the weights arrive as arguments (1.2 GB of bf16), not as constants
    n_param = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(params))
    assert mem.argument_size_in_bytes >= n_param
