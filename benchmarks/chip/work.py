"""Operations and bytes of a dense decoder, from its shapes and live lengths.

The arithmetic of ``launch/roofline.py`` (2 x N operations per token for N
active parameters), taken from the configuration file and counted from the
lengths the traffic really had, not from compiler estimates:

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

from benchmarks.chip.weights import dims

BYTES = 2                       # bfloat16 weights and cache


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


def decode_step(cfg: dict, contexts: list[int]) -> tuple[float, float]:
    """(operations, bytes) of one decode step; contexts[i] is the number of
    positions running slot i attends, the new one included."""
    flops = sum(2 * matmul_params(cfg) + attention_flops(cfg, c)
                for c in contexts)
    kv = kv_bytes_per_position(cfg)
    read = sum(_attended(cfg, c - 1) for c in contexts) * kv
    written = len(contexts) * kv
    return float(flops), float(weight_bytes(cfg) + read + written)


def least_time(flops: float, nbytes: float, peak: dict) -> float:
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])


def request_flops(cfg: dict, prompt_len: int, generated: int) -> float:
    """Model operations to prefill ``prompt_len`` tokens and generate
    ``generated`` more (the first from the prefill)."""
    n = 2 * matmul_params(cfg)
    # the prompt attends causally: position i sees i + 1 positions
    attn = sum(attention_flops(cfg, i + 1) for i in range(prompt_len))
    attn += sum(attention_flops(cfg, prompt_len + j + 1)
                for j in range(max(generated - 1, 0)))
    return float(n * (prompt_len + max(generated - 1, 0)) + attn)
