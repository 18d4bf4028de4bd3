"""Least work Laguna's layers of more than one kind need on one chip's share,
from the published shapes (configs/laguna-s-ep2.json), counted by hand.

Lower bounds on what the algorithm must move. ``decode_step_bytes`` is the bound
the shared readers use: the weights outside the routed experts, the output
head's slice and the FULL layers' K/V of the live tokens, each once. It LEAVES
OUT the routed experts (a step is not bound to touch any expert held here; with
all 128 in, a step that touched 70 would read over 100%) and the sliding
layers' rings. ``expert_bytes`` prices the experts a step really touched, which
the program counts (``serve_moe_experts_touched_total``); ``mechanism_bytes``
the ring columns the live rows attend over, which it counts too
(``serve_decode_attn_columns_total{kind="window"}``). A share built on these that
reads over 100% means a count or a program-name mapping is wrong.

No prefill count is here: ``prefill_flops_roofline`` lists its cells in an entry
of ``BENCHMARK.json`` that a PR of this kind may not edit, so nothing would read
one (PERF.md section 7 says what the reader is owed).
"""

from __future__ import annotations


def layer_counts(c: dict) -> dict:
    """Layers of each kind, off the config's own lists."""
    kinds, feeds = c["layer_types"], c["mlp_layer_types"]
    return {"full": kinds.count("full_attention"), "window": kinds.count("sliding_attention"),
            "dense": feeds.count("dense"), "sparse": feeds.count("sparse")}


def attention_params(c: dict, heads: int) -> int:
    """One attention at ``heads`` query heads: q_proj and o_proj at that count,
    k_proj and v_proj at the K/V heads, the per-head gate's projection."""
    d, hd, kh = c["hidden_size"], c["head_dim"], c["num_key_value_heads"]
    return 2 * d * heads * hd + 2 * d * kh * hd + d * heads


def attention_params_total(c: dict) -> int:
    return sum(attention_params(c, heads) for heads in c["num_attention_heads_per_layer"])


def dense_params(c: dict) -> int:
    """A dense layer's SwiGLU of ``intermediate_size``."""
    return 3 * c["hidden_size"] * c["intermediate_size"]


def expert_params(c: dict) -> int:
    """One routed expert: a SwiGLU of the expert width."""
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def sparse_params_outside_experts(c: dict) -> int:
    """What every token multiplies by in an expert layer: the router at its
    published width and the shared expert."""
    d = c["hidden_size"]
    return d * c["num_experts_published"] + 3 * d * c["shared_expert_intermediate_size"]


def params_outside_experts(c: dict) -> int:
    """Every layer's matrices but the routed experts'."""
    n = layer_counts(c)
    return (attention_params_total(c) + n["dense"] * dense_params(c)
            + n["sparse"] * sparse_params_outside_experts(c))


def head_params(c: dict) -> int:
    return c["hidden_size"] * c["vocab_size"]


def total_params(c: dict) -> int:
    """Everything the chip holds: both embeddings, every layer with the experts
    held here, and the norms (two a layer and the final one)."""
    n = layer_counts(c)
    return (2 * head_params(c) + params_outside_experts(c)
            + n["sparse"] * c["num_experts"] * expert_params(c)
            + (2 * c["num_hidden_layers"] + 1) * c["hidden_size"])


def weight_bytes(c: dict, bytes_per_param: int = 2) -> int:
    """Bytes of weights EVERY decode step has to read: the layers outside the
    routed experts and the output head's slice, at the stated precision's 2 bytes."""
    return (params_outside_experts(c) + head_params(c)) * bytes_per_param


def expert_bytes(c: dict, experts_touched: float, bytes_per_param: int = 2) -> float:
    """Bytes of the held experts that had a token (a count summed over layers)."""
    return experts_touched * expert_params(c) * bytes_per_param


def column_bytes(c: dict, bytes_per_value: int = 2) -> int:
    """Keys and values of one position in one layer's cache, a row's or a ring's."""
    return 2 * c["num_key_value_heads"] * c["head_dim"] * bytes_per_value


def kv_bytes_per_token(c: dict, bytes_per_value: int = 2) -> int:
    """Keys and values of the FULL layers: the only cache that grows with a row."""
    return layer_counts(c)["full"] * column_bytes(c, bytes_per_value)


def ring_bytes_per_row(c: dict, bytes_per_value: int = 2) -> int:
    """What a slot holds for its sliding layers: ``sliding_window`` columns each."""
    return layer_counts(c)["window"] * c["sliding_window"] * column_bytes(c, bytes_per_value)


def decode_step_bytes(c: dict, live_tokens: float) -> float:
    """HBM bytes of one decode step over all slots: weights outside the experts
    + head + the full layers' live K/V, read once. Experts and rings are NOT in
    it (above)."""
    return weight_bytes(c) + live_tokens * kv_bytes_per_token(c)


def mechanism_bytes(c: dict, ring_columns: float) -> float:
    """Bytes a decode step reads for what this configuration adds to a slot:
    the rings' columns the live rows attend over (a count summed over the
    sliding layers: at most ``sliding_window`` a row and layer)."""
    return ring_columns * column_bytes(c)
