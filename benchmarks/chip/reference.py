"""The plain reference: the architecture's forward pass in float32.

The layers come from the architecture module the configuration names
(``archs/<arch>.py``: ``embed``, ``layer_forward``, ``head``, ``norm``),
written from the published architecture.  It imports nothing of the
program and takes no array the program made: weights are rebuilt from the
seed one layer at a time (``weights.LayerMaker``), so a model whose
float32 weights would not fit beside anything else still runs.  Every
matrix product runs at ``Precision.HIGHEST`` (``mm``).

``Reference.block_gaps`` is the comparison that decides ``correct``: for
each served token, how far its reference logit lies below the reference's
best logit at that position.  With ``control=True`` it is the same reading
for the token that the reference computed with fp8 (e4m3, per-tensor
scaled) weights puts first.
Sequences run in fixed blocks of ``block`` rows of ``length`` positions, so
the compiled shapes are the same in every run and come from the cache.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip.weights import LayerMaker

HI = jax.lax.Precision.HIGHEST
HEAD_ROWS = 512             # rows of the output head computed at a time


def mm(x, w):
    return jnp.matmul(x, w, precision=HI)


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
    return jax.lax.map(lambda c: fn(mm(c, head)), chunks).reshape(r)


class Reference:
    """Float32 forward of fixed blocks of token sequences."""

    def __init__(self, cfg: dict, seed: int, *, block: int, length: int,
                 device=None, arch=None) -> None:
        rows = HEAD_ROWS if (block * length) % HEAD_ROWS == 0 \
            else block * length
        self.cfg, self.block, self.length = cfg, block, length
        self.device = device
        self.maker = LayerMaker(cfg, seed, arch)
        self.arch = arch = self.maker.arch
        self._layer = jax.jit(lambda w, x, i, name: arch.layer_forward(
            w, x, cfg, i), static_argnums=3)
        self._layer8 = jax.jit(lambda w, x, i, name: arch.layer_forward(
            fp8(w), x, cfg, i), static_argnums=3)

        def gap(h, norm_w, head, targets):
            h = arch.norm(h, norm_w, cfg).reshape(-1, h.shape[-1])
            t = targets.reshape(-1)

            def per(logits, tt):
                best = jnp.max(logits, axis=-1)
                got = jnp.take_along_axis(
                    logits, jnp.maximum(tt, 0)[:, None], axis=-1)[:, 0]
                return jnp.where(tt >= 0, best - got, 0.0)
            r = h.shape[0]
            hc = h.reshape(r // rows, rows, h.shape[-1])
            tc = t.reshape(r // rows, rows)
            out = jax.lax.map(lambda a: per(mm(a[0], head), a[1]), (hc, tc))
            return out.reshape(targets.shape)

        def top(h, norm_w, head):
            h = arch.norm(h, norm_w, cfg).reshape(-1, h.shape[-1])
            return _head_rows(h, head, lambda lg: jnp.argmax(
                lg, axis=-1).astype(jnp.float32), rows).astype(jnp.int32) \
                .reshape(self.block, self.length)

        self._gap = jax.jit(gap)
        self._top = jax.jit(top)

    def _final(self, h, control: bool):
        norm_w, head = self.arch.head(self.maker.outer_f32, self.cfg)
        if control:
            head = fp8(head)
        return norm_w, head

    def _run(self, tokens, control: bool):
        x = self.arch.embed(self.maker.outer_f32, tokens, self.cfg)
        layer = self._layer8 if control else self._layer
        for name, (n, _) in self.arch.stacks(self.cfg).items():
            for i in range(n):
                x = layer(self.maker.layer_f32(name, i), x, jnp.int32(i),
                          name)
        return x

    def logits(self, h, norm_w, head):
        """Logits of hidden rows (for tests at a small size)."""
        return mm(self.arch.norm(h, norm_w, self.cfg), head)

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
            norm_w, head = self._final(h, control=False)
            tgt = jnp.asarray(targets, jnp.int32)
            if control:
                h8 = self._run(tok, control=True)
                norm8, head8 = self._final(h8, control=True)
                top = self._top(h8, norm8, head8)
                del h8, head8
                tgt = jnp.where(tgt >= 0, top, -1)
            return np.asarray(self._gap(h, norm_w, head, tgt))


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
