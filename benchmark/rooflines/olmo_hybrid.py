"""Least work Olmo-Hybrid's layers need on the first stage of a two-stage
pipeline, from the published shapes (configs/olmo-hybrid-7b-pp2.json).

Lower bounds on what the algorithm must move. ``decode_step_bytes`` is the bound
the shared readers use (``decode_hbm_roofline``): the held layers' weights and
the output head once (every one is dense: a step reads all of them), and the
full-attention layers' K/V of the live tokens once. The shared reader hands it
the live TOKENS and not the live rows, so the matrix state is not in it: the
share it gives is under the step's true least by what ``mechanism_bytes`` prices
(the state and conv tail of the live rows, read once and written once: 0.88 GB
of 9.3 GB with 16 rows live). ``state_step_bytes`` is what ONE call of the state
kernel (ops/pallas_delta.py) moves at this model's (30, 96, 192) tile, for
whoever times that kernel alone. A share built on these that reads over 100%
means a count or a program-name mapping is wrong.

No prefill count is here: ``prefill_flops_roofline`` lists its cells in an entry
of ``BENCHMARK.json`` that a PR of this kind may not edit, so nothing would read
one (PERF.md section 7).
"""

from __future__ import annotations


def _linear(c: dict) -> dict:
    n, d_k, d_v = c["linear_num_value_heads"], c["linear_key_head_dim"], c["linear_value_head_dim"]
    return dict(n=n, d_k=d_k, d_v=d_v, value=n * d_v,
                channels=2 * c["linear_num_key_heads"] * d_k + n * d_v, taps=c["linear_conv_kernel_dim"])


def layer_counts(c: dict) -> dict:
    """Full-attention and linear-attention layers among the layers held here."""
    held = c["layer_types"][: c["num_hidden_layers"]]
    full = sum(kind == "full_attention" for kind in held)
    return {"full": full, "linear": len(held) - full}


def head_dim(c: dict) -> int:
    return c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"]


def delta_params(c: dict) -> int:
    """A linear-attention layer's matrices: q, k, v and gate projections, b and a, the output."""
    z, d = _linear(c), c["hidden_size"]
    return d * (z["channels"] + z["value"]) + d * 2 * z["n"] + z["value"] * d


def attention_params(c: dict) -> int:
    """The full attention: q, k, v and o projections, no bias, no gate."""
    d, hd = c["hidden_size"], head_dim(c)
    return d * hd * (c["num_attention_heads"] + 2 * c["num_key_value_heads"]) + c["num_attention_heads"] * hd * d


def feed_forward_params(c: dict) -> int:
    return 3 * c["hidden_size"] * c["intermediate_size"]


def layer_params(c: dict) -> int:
    """Every matrix of the layers held here."""
    n = layer_counts(c)
    return (n["linear"] * delta_params(c) + n["full"] * attention_params(c)
            + c["num_hidden_layers"] * feed_forward_params(c))


def head_params(c: dict) -> int:
    return c["hidden_size"] * c["vocab_size"]


def weight_bytes(c: dict, bytes_per_param: int = 2) -> int:
    """Bytes of weights every decode step has to read: the held layers and the
    output head, at the stated precision's 2 bytes (the embedding is gathered a
    row a token)."""
    return (layer_params(c) + head_params(c)) * bytes_per_param


def kv_bytes_per_token(c: dict, bytes_per_value: int = 2) -> int:
    """Keys and values of the full-attention layers: the only cache that grows."""
    return layer_counts(c)["full"] * 2 * c["num_key_value_heads"] * head_dim(c) * bytes_per_value


def state_bytes_per_row(c: dict) -> int:
    """What a slot carries for its linear layers, and what one snapshot in the
    prefix cache holds: a float32 (d_k, d_v) matrix a head and the conv's last
    taps - 1 columns of [q; k; v] in bfloat16."""
    z = _linear(c)
    return layer_counts(c)["linear"] * (z["n"] * z["d_k"] * z["d_v"] * 4 + (z["taps"] - 1) * z["channels"] * 2)


def state_step_bytes(c: dict, live_rows: float) -> float:
    """Bytes one call of the state kernel moves: a layer's matrix state of the
    live rows, in and out (its vectors are kilobytes beside it)."""
    z = _linear(c)
    return 2.0 * live_rows * z["n"] * z["d_k"] * z["d_v"] * 4


def decode_step_bytes(c: dict, live_tokens: float) -> float:
    """HBM bytes of one decode step over all slots: weights + head + the live
    K/V, read once. The state is NOT in it (above)."""
    return weight_bytes(c) + live_tokens * kv_bytes_per_token(c)


def mechanism_bytes(c: dict, live_rows: float) -> float:
    """Bytes a decode step moves for what this configuration adds to a slot: the
    matrix state and conv tail of the live rows, read once and written once."""
    return 2.0 * live_rows * state_bytes_per_row(c)
