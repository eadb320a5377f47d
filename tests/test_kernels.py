"""Pallas kernel validation: shape/dtype sweeps against the pure-jnp
oracles in kernels/ref.py, executed with interpret=True on CPU."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.flash_attention import flash_attention_kernel
from repro.kernels.paged_attention import paged_attention_kernel
from repro.kernels.ssd_scan import ssd_scan_kernel

TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


class TestFlashAttention:
    @pytest.mark.parametrize("s", [128, 192, 256])
    @pytest.mark.parametrize("d", [64, 120, 128])
    @pytest.mark.parametrize("hq,hkv", [(4, 4), (8, 2)])
    def test_shapes_causal(self, s, d, hq, hkv):
        ks = jax.random.split(jax.random.key(0), 3)
        q = jax.random.normal(ks[0], (2, s, hq, d), jnp.float32)
        k = jax.random.normal(ks[1], (2, s, hkv, d), jnp.float32)
        v = jax.random.normal(ks[2], (2, s, hkv, d), jnp.float32)
        out = flash_attention_kernel(q, k, v, causal=True, interpret=True)
        want = ref.flash_attention_ref(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)

    @pytest.mark.parametrize("window", [32, 100, 200])
    def test_sliding_window(self, window):
        ks = jax.random.split(jax.random.key(1), 3)
        q = jax.random.normal(ks[0], (1, 256, 4, 64), jnp.float32)
        k = jax.random.normal(ks[1], (1, 256, 4, 64), jnp.float32)
        v = jax.random.normal(ks[2], (1, 256, 4, 64), jnp.float32)
        out = flash_attention_kernel(q, k, v, causal=True, window=window,
                                     interpret=True)
        want = ref.flash_attention_ref(q, k, v, causal=True, window=window)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)

    def test_bf16(self):
        ks = jax.random.split(jax.random.key(2), 3)
        q = jax.random.normal(ks[0], (2, 128, 4, 64), jnp.bfloat16)
        k = jax.random.normal(ks[1], (2, 128, 2, 64), jnp.bfloat16)
        v = jax.random.normal(ks[2], (2, 128, 2, 64), jnp.bfloat16)
        out = flash_attention_kernel(q, k, v, causal=True, interpret=True)
        want = ref.flash_attention_ref(q, k, v, causal=True)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(want, np.float32),
            atol=3e-2, rtol=3e-2)

    def test_ragged_seq_padding(self):
        ks = jax.random.split(jax.random.key(3), 3)
        q = jax.random.normal(ks[0], (1, 200, 4, 64), jnp.float32)
        k = jax.random.normal(ks[1], (1, 200, 4, 64), jnp.float32)
        v = jax.random.normal(ks[2], (1, 200, 4, 64), jnp.float32)
        out = flash_attention_kernel(q, k, v, causal=True, interpret=True)
        want = ref.flash_attention_ref(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)


class TestPagedAttention:
    @pytest.mark.parametrize("page,per_seq", [(16, 8), (32, 4)])
    @pytest.mark.parametrize("hq,hkv", [(4, 4), (8, 2)])
    def test_vs_ref(self, page, per_seq, hq, hkv):
        B, D, P = 3, 64, 64
        ks = jax.random.split(jax.random.key(4), 4)
        q = jax.random.normal(ks[0], (B, hq, D), jnp.float32)
        kp = jax.random.normal(ks[1], (P, page, hkv, D), jnp.float32)
        vp = jax.random.normal(ks[2], (P, page, hkv, D), jnp.float32)
        table = jax.random.permutation(
            ks[3], P)[:B * per_seq].reshape(B, per_seq).astype(jnp.int32)
        lengths = jnp.array([page * per_seq, 3, page + 1][:B], jnp.int32)
        out = paged_attention_kernel(q, kp, vp, table, lengths,
                                     interpret=True)
        want = ref.paged_attention_ref(q, kp, vp, table, lengths)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)

    def test_short_sequences_skip_pages(self):
        B, page, per_seq, hq, hkv, D = 2, 16, 8, 4, 2, 64
        ks = jax.random.split(jax.random.key(5), 4)
        q = jax.random.normal(ks[0], (B, hq, D), jnp.float32)
        kp = jax.random.normal(ks[1], (32, page, hkv, D), jnp.float32)
        vp = jax.random.normal(ks[2], (32, page, hkv, D), jnp.float32)
        table = jnp.arange(B * per_seq, dtype=jnp.int32).reshape(B, per_seq)
        lengths = jnp.array([1, 2], jnp.int32)
        out = paged_attention_kernel(q, kp, vp, table, lengths,
                                     interpret=True)
        want = ref.paged_attention_ref(q, kp, vp, table, lengths)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)


class TestSSDScan:
    @pytest.mark.parametrize("l", [128, 256, 384])
    @pytest.mark.parametrize("p,n", [(32, 16), (64, 64)])
    def test_vs_sequential_ref(self, l, p, n):
        b, h = 2, 3
        ks = jax.random.split(jax.random.key(6), 4)
        x = jax.random.normal(ks[0], (b, l, h, p), jnp.float32)
        a = -jnp.abs(jax.random.normal(ks[1], (b, l, h))) * 0.1
        B = jax.random.normal(ks[2], (b, l, n), jnp.float32)
        C = jax.random.normal(ks[3], (b, l, n), jnp.float32)
        y, _ = ssd_scan_kernel(x, a, B, C, interpret=True)
        want, _ = ref.ssd_scan_ref(x, a, B, C)
        np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                                   atol=2e-4, rtol=2e-4)

    def test_matches_model_ssd(self):
        """Kernel semantics == the model's chunked jnp implementation."""
        from repro.models.ssm import ssd_chunked
        b, l, h, p, n = 1, 256, 2, 32, 16
        ks = jax.random.split(jax.random.key(7), 4)
        x = jax.random.normal(ks[0], (b, l, h, p), jnp.float32)
        a = -jnp.abs(jax.random.normal(ks[1], (b, l, h))) * 0.1
        B = jax.random.normal(ks[2], (b, l, n), jnp.float32)
        C = jax.random.normal(ks[3], (b, l, n), jnp.float32)
        y_model, _ = ssd_chunked(x, a, B, C, chunk=128)
        y_kernel, _ = ssd_scan_kernel(x, a, B, C, interpret=True)
        np.testing.assert_allclose(np.asarray(y_kernel),
                                   np.asarray(y_model),
                                   atol=2e-4, rtol=2e-4)


def test_ops_dispatch_cpu_uses_ref():
    """On CPU (non-interpret) the wrappers fall through to the oracle."""
    ks = jax.random.split(jax.random.key(8), 3)
    q = jax.random.normal(ks[0], (1, 64, 4, 64), jnp.float32)
    k = jax.random.normal(ks[1], (1, 64, 4, 64), jnp.float32)
    v = jax.random.normal(ks[2], (1, 64, 4, 64), jnp.float32)
    out = ops.flash_attention(q, k, v, causal=True)
    want = ref.flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=1e-6)


def test_force_kernel_off_tpu_is_an_error():
    """force_kernel never falls back to the interpreter in silence."""
    q = jnp.zeros((1, 64, 4, 64), jnp.float32)
    with pytest.raises(RuntimeError, match="needs the TPU backend"):
        ops.flash_attention(q, q, q, force_kernel=True)
    out = ops.flash_attention(q, q, q, force_kernel=True, interpret=True)
    assert out.shape == q.shape
