"""Jit'd dispatch wrappers: Pallas kernel on TPU, pure-jnp oracle elsewhere.

The rest of the framework calls these entry points; the backend decision is
made once here.  ``interpret=True`` forces the Pallas path with the
interpreter (CPU validation — what the kernel tests use).
``force_kernel=True`` demands the compiled kernel and raises off the TPU:
a kernel result from the interpreter is never passed off as a chip result.
"""

from __future__ import annotations

import functools

import jax

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention_kernel
from repro.kernels.paged_attention import paged_attention_kernel
from repro.kernels.ssd_scan import ssd_scan_kernel


def _use_kernel(force_kernel: bool, interpret: bool) -> bool:
    if interpret or jax.default_backend() == "tpu":
        return True
    if force_kernel:
        raise RuntimeError(
            "force_kernel=True needs the TPU backend, found "
            f"{jax.default_backend()!r}; pass interpret=True to run the "
            "kernel in the Pallas interpreter")
    return False


@functools.partial(jax.jit, static_argnames=("causal", "window",
                                             "force_kernel", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    force_kernel: bool = False, interpret: bool = False):
    if _use_kernel(force_kernel, interpret):
        return flash_attention_kernel(q, k, v, causal=causal,
                                      window=window, interpret=interpret)
    return ref.flash_attention_ref(q, k, v, causal=causal, window=window)


@functools.partial(jax.jit, static_argnames=("force_kernel", "interpret"))
def paged_attention(q, k_pages, v_pages, block_table, lengths, *,
                    force_kernel: bool = False, interpret: bool = False):
    if _use_kernel(force_kernel, interpret):
        return paged_attention_kernel(q, k_pages, v_pages, block_table,
                                      lengths, interpret=interpret)
    return ref.paged_attention_ref(q, k_pages, v_pages, block_table,
                                   lengths)


@functools.partial(jax.jit, static_argnames=("chunk", "force_kernel",
                                             "interpret"))
def ssd_scan(x, a, B, C, *, chunk: int = 128, force_kernel: bool = False,
             interpret: bool = False):
    if _use_kernel(force_kernel, interpret):
        y, _ = ssd_scan_kernel(x, a, B, C, chunk=chunk, interpret=interpret)
        return y
    y, _ = ref.ssd_scan_ref(x, a, B, C)
    return y
