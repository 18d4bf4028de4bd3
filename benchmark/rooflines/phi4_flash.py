"""Least work Phi-4-mini-flash's layers need on one chip, from the published
shapes and the sizes its model class assumes (configs/phi4-mini-flash.json).

Lower bounds on what the algorithm must move or compute. ``decode_step_bytes``
is the bound the shared readers use: the weights once and layer 17's K/V of the
live tokens once. It LEAVES OUT the window rings, the recurrent state and the
seven further reads of that row by the cross layers, so a roofline share built
on it is low by what they cost; ``mechanism_bytes`` prices them from counts of
the run. A share built on these that reads over 100% means a count or a
program-name mapping is wrong.
"""

from __future__ import annotations


def _d_inner(c: dict) -> int:
    return c["ssm_expand"] * c["hidden_size"]


def feed_forward_params(c: dict) -> int:
    return 3 * c["hidden_size"] * c["intermediate_size"]


def state_space_params(c: dict) -> int:
    """in, x (dt + B + C) and dt projections, out: the matrices a token multiplies by."""
    d, di, n, r = c["hidden_size"], _d_inner(c), c["ssm_d_state"], c["ssm_dt_rank"]
    return d * 2 * di + di * (r + 2 * n) + r * di + di * d


def attention_params(c: dict, cross: bool = False) -> int:
    d = c["hidden_size"]
    hd = d // c["num_attention_heads"]
    qw, kvw = c["num_attention_heads"] * hd, c["num_key_value_heads"] * hd
    return d * (qw if cross else qw + 2 * kvw) + qw * d


def memory_unit_params(c: dict) -> int:
    return 2 * c["hidden_size"] * _d_inner(c)


def layer_counts(c: dict) -> dict:
    """How many layers of each mixer: n/4 window pairs, the middle pair, n/4 - 1 cross pairs."""
    pairs = c["num_hidden_layers"] // 4
    return {"state_space": pairs + 1, "window": pairs, "full": 1, "memory": pairs - 1,
            "cross": pairs - 1}


def self_half_params(c: dict) -> int:
    """Matrices of the layers every prompt token has to pass: the state-space
    and self-attention layers (they keep state or K/V) and their feed-forwards."""
    n = layer_counts(c)
    return (n["state_space"] * state_space_params(c)
            + (n["window"] + n["full"]) * attention_params(c)
            + (n["state_space"] + n["window"] + n["full"]) * feed_forward_params(c))


def cross_half_params(c: dict) -> int:
    """Matrices of the cross half: only the position that is sampled needs them."""
    n = layer_counts(c)
    return (n["memory"] * memory_unit_params(c) + n["cross"] * attention_params(c, cross=True)
            + (n["memory"] + n["cross"]) * feed_forward_params(c))


def head_params(c: dict) -> int:
    return c["hidden_size"] * c["vocab_size"]


def weight_bytes(c: dict, bytes_per_param: int = 2) -> int:
    """Bytes of weights one decode step has to read: every layer matrix and the
    output head, which is the tied embedding (its lookup is a gather of a few rows)."""
    return (self_half_params(c) + cross_half_params(c) + head_params(c)) * bytes_per_param


def kv_bytes_per_token(c: dict, bytes_per_value: int = 2) -> int:
    """Layer 17's keys and values: the only cache that grows with the context."""
    hd = c["hidden_size"] // c["num_attention_heads"]
    return 2 * c["num_key_value_heads"] * hd * bytes_per_value


def decode_step_bytes(c: dict, live_tokens: float) -> float:
    """HBM bytes of one decode step over all slots: weights + the full row of
    the live tokens, read once. Ring, state and the cross layers' seven further
    reads of the row are NOT in it (``mechanism_bytes``)."""
    return weight_bytes(c) + live_tokens * kv_bytes_per_token(c)


def mechanism_bytes(c: dict, live_rows: float, ring_columns: float, live_tokens: float = 0.0) -> float:
    """Bytes a decode step moves for what this configuration adds to a slot:
    the valid columns of the rings (``ring_columns``: summed over the live rows,
    at most the window each; read by each window layer), the recurrent state
    (read and written, float32) and conv tail (read and written) of the live
    rows in each state-space layer, and the cross layers' reads of the full row."""
    n = layer_counts(c)
    di, state, taps = _d_inner(c), c["ssm_d_state"], c["ssm_d_conv"]
    ring = n["window"] * ring_columns * kv_bytes_per_token(c)
    carried = n["state_space"] * live_rows * 2 * (di * state * 4 + (taps - 1) * di * 2)
    shared = n["cross"] * live_tokens * kv_bytes_per_token(c)
    return ring + carried + shared


def scan_bytes(c: dict, tokens: int) -> float:
    """Least bytes the state-space recurrence of ONE layer moves for ``tokens``
    positions of a row: delta (float32), u, B, C in and y out (bfloat16), the
    state in and out once. The state itself never has to leave the chip's fast
    memory between positions."""
    di, state = _d_inner(c), c["ssm_d_state"]
    per_token = di * 4 + di * 2 + 2 * state * 2 + di * 2
    return tokens * per_token + 2 * di * state * 4


def prefill_flops(c: dict, prompt_lengths: list[int], cached: int = 0) -> float:
    """FLOPs to prefill prompts of these lengths (``cached`` is 0: this
    configuration is served without a prefix cache): 2 x the self half's
    matrices a token, the recurrence (3 multiply-adds a state element a token),
    window attention over at most the window and the full layer's causal
    attention (QK^T over 64, PV over the pair's 128, half the square), and the
    cross half and the head once a prompt."""
    tokens = max(0, sum(prompt_lengths) - cached)
    n = layer_counts(c)
    heads = c["num_attention_heads"]
    hd = c["hidden_size"] // heads
    window = c["sliding_window"]
    per_pair = 2 * heads * (hd + 2 * hd)
    full = sum(per_pair * (m * (m + 1) / 2) for m in prompt_lengths) * n["full"]
    windowed = sum(
        per_pair * (min(m, window) * (min(m, window) + 1) / 2 + max(m - window, 0) * window)
        for m in prompt_lengths
    ) * n["window"]
    recurrence = n["state_space"] * tokens * 6.0 * _d_inner(c) * c["ssm_d_state"]
    return (2.0 * self_half_params(c) * tokens + recurrence + full + windowed
            + (2.0 * cross_half_params(c) + 2.0 * head_params(c)) * len(prompt_lengths))
