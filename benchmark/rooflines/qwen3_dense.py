"""Least work a Qwen3-style dense decoder needs, from its published shapes.

Lower bounds on what the algorithm must move or compute: weights read once a
step, the live cache read once, no padding, no recomputation. A roofline share
built on them that reads over 100% means a count or a program-name mapping is
wrong, not that the chip is fast.
"""

from __future__ import annotations


def matmul_params(c: dict) -> int:
    """Parameters of the per-layer matrices (every token multiplies by these)."""
    d, hd = c["hidden_size"], c["head_dim"]
    h, kh, ff = c["num_attention_heads"], c["num_key_value_heads"], c["intermediate_size"]
    per_layer = d * h * hd + 2 * d * kh * hd + h * hd * d + 3 * d * ff
    return c["num_hidden_layers"] * per_layer


def head_params(c: dict) -> int:
    return c["hidden_size"] * c["vocab_size"]


def weight_bytes(c: dict, bytes_per_param: int = 2) -> int:
    """Bytes of weights one decode step has to read: every layer matrix and the
    output head (the embedding itself is a gather of a few rows)."""
    return (matmul_params(c) + head_params(c)) * bytes_per_param


def kv_bytes_per_token(c: dict, bytes_per_value: int = 2) -> int:
    return 2 * c["num_hidden_layers"] * c["num_key_value_heads"] * c["head_dim"] * bytes_per_value


def decode_step_bytes(c: dict, live_tokens: float) -> float:
    """HBM bytes of one decode step over all slots: bound by bandwidth."""
    return weight_bytes(c) + live_tokens * kv_bytes_per_token(c)


def prefill_flops(c: dict, prompt_lengths: list[int], cached: int = 0) -> float:
    """FLOPs to prefill prompts of these lengths, less ``cached`` tokens served
    from the prefix cache: 2 x matrix parameters a token, causal attention
    (QK^T and PV over half the square), and the head once a prompt. Bound by
    compute."""
    tokens = max(0, sum(prompt_lengths) - cached)
    hd, h, layers = c["head_dim"], c["num_attention_heads"], c["num_hidden_layers"]
    attention = sum(2 * 2 * h * hd * (n * (n + 1) / 2) for n in prompt_lengths) * layers
    total = sum(prompt_lengths)
    if total:
        attention *= tokens / total
    return 2.0 * matmul_params(c) * tokens + attention + 2.0 * head_params(c) * len(prompt_lengths)
