"""Least work Qwen3-Next's hybrid layers need on one chip's share, from the
published shapes (configs/qwen3-next-ep2.json).

Lower bounds on what the algorithm must move or compute. ``decode_step_bytes``
is the bound the shared readers use: the weights outside the experts, the output
head's slice and the full-attention layers' K/V of the live tokens, each once.
It LEAVES OUT the routed experts (a step is not bound to touch any expert held
here; with all 256 in, a step that touched 213 would read over 100%) and the
matrix state. ``expert_bytes`` prices the experts a step really touched, which
the program counts (``serve_moe_experts_touched_total``); ``mechanism_bytes``
the state and conv tail of the live rows, read and written. A share built on
these that reads over 100% means a count or a program-name mapping is wrong.

No prefill count is here: ``prefill_flops_roofline`` lists its cells in an entry
of ``BENCHMARK.json`` that a PR of this kind may not edit, so nothing would read
one (PERF.md section 7 says what the reader is owed).
"""

from __future__ import annotations

def _linear(c: dict) -> dict:
    n_k, n_v = c["linear_num_key_heads"], c["linear_num_value_heads"]
    d_k, d_v = c["linear_key_head_dim"], c["linear_value_head_dim"]
    return dict(n_k=n_k, n_v=n_v, d_k=d_k, d_v=d_v, key=n_k * d_k, value=n_v * d_v,
                channels=2 * n_k * d_k + n_v * d_v, taps=c["linear_conv_kernel_dim"])


def layer_counts(c: dict) -> dict:
    """Full-attention layers (one a period) and linear-attention layers."""
    full = c["num_hidden_layers"] // c["full_attention_interval"]
    return {"full": full, "linear": c["num_hidden_layers"] - full}


def delta_params(c: dict) -> int:
    """A linear-attention layer's matrices: in_proj_qkvz, in_proj_ba, out_proj."""
    z, d = _linear(c), c["hidden_size"]
    return d * (z["channels"] + z["value"]) + d * 2 * z["n_v"] + z["value"] * d


def attention_params(c: dict) -> int:
    """The gated full attention: q_proj (query and gate), k_proj, v_proj, o_proj."""
    d, h, kh, hd = c["hidden_size"], c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    return d * 2 * h * hd + 2 * d * kh * hd + h * hd * d


def expert_params(c: dict) -> int:
    """One routed expert: a SwiGLU of the expert width."""
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def feed_forward_params_outside_experts(c: dict) -> int:
    """What every token multiplies by in a layer's feed-forward: the router at
    its published width, the shared expert and its gate vector."""
    d = c["hidden_size"]
    return d * c["num_experts_published"] + 3 * d * c["shared_expert_intermediate_size"] + d


def params_outside_experts(c: dict) -> int:
    """Every layer's matrices but the routed experts'."""
    n = layer_counts(c)
    return (n["linear"] * delta_params(c) + n["full"] * attention_params(c)
            + c["num_hidden_layers"] * feed_forward_params_outside_experts(c))


def head_params(c: dict) -> int:
    return c["hidden_size"] * c["vocab_size"]


def weight_bytes(c: dict, bytes_per_param: int = 2) -> int:
    """Bytes of weights EVERY decode step has to read: the layers outside the
    routed experts and the output head's slice, at the stated precision's 2 bytes."""
    return (params_outside_experts(c) + head_params(c)) * bytes_per_param


def expert_bytes(c: dict, experts_touched: float, bytes_per_param: int = 2) -> float:
    """Bytes of the held experts that had a token (a count summed over layers)."""
    return experts_touched * expert_params(c) * bytes_per_param


def kv_bytes_per_token(c: dict, bytes_per_value: int = 2) -> int:
    """Keys and values of the full-attention layers: the only cache that grows."""
    return layer_counts(c)["full"] * 2 * c["num_key_value_heads"] * c["head_dim"] * bytes_per_value


def state_bytes_per_row(c: dict) -> int:
    """What a slot carries for its linear layers: a float32 (d_k, d_v) matrix a
    value head and the conv's last taps - 1 columns of [q; k; v] in bfloat16."""
    z = _linear(c)
    return layer_counts(c)["linear"] * (z["n_v"] * z["d_k"] * z["d_v"] * 4 + (z["taps"] - 1) * z["channels"] * 2)


def decode_step_bytes(c: dict, live_tokens: float) -> float:
    """HBM bytes of one decode step over all slots: weights outside the experts
    + head + the live K/V, read once. Experts and state are NOT in it (above)."""
    return weight_bytes(c) + live_tokens * kv_bytes_per_token(c)


def mechanism_bytes(c: dict, live_rows: float) -> float:
    """Bytes a decode step moves for what this configuration adds to a slot: the
    matrix state and conv tail of the live rows, read once and written once."""
    return 2.0 * live_rows * state_bytes_per_row(c)
