"""Llama-family transformer: pure-functional JAX, scan-over-layers, KV cache.

Design (TPU-first, not a port):
- Layer parameters are **stacked** along a leading n_layers axis and the
  decoder runs as one ``lax.scan`` — one compiled layer body regardless of
  depth, fast compiles, and clean (L, ...) sharding.
- One forward serves three regimes via static shape/flags: training (no
  cache), prefill (writes the cache), decode (S=1 against the cache).
- The stacked KV cache is the layer scan's CARRY, never its xs/ys: a block
  writes its new K/V into the stacked buffer in place at (layer, row, 0, 0,
  position) and attention reads its layer out of that buffer (the decode
  kernel through its index maps). A scan cannot alias xs with ys, so a
  cache scanned per layer costs a slice, a second buffer and a copy back:
  three passes over the whole cache every decode step.
- All matmuls in bf16 on the MXU with fp32 softmax/norm accumulation; the
  causal prefill path dispatches to the pallas flash kernel on TPU
  (prime_tpu.ops.pallas_attention).
- SPMD: pure functions of pytrees — sharding comes from the caller via
  NamedSharding on params/batch (prime_tpu.parallel.sharding), no mesh logic
  in model code.
"""

from __future__ import annotations

import math
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from prime_tpu.models.config import ModelConfig
from prime_tpu.models.quantize import matmul as _mm
from prime_tpu.ops.attention import (
    _apply_softcap,
    cache_prefill_attention,
    decode_attention,
    multi_head_attention,
    write_cache,
)
from prime_tpu.ops.norms import rms_norm
from prime_tpu.ops.rope import apply_rope_rows, rope_frequencies

Params = dict[str, Any]


class KVCache(NamedTuple):
    """Per-layer stacked KV cache: k/v are (L, B, KH, head_dim, C), L being
    ``config.cache_rows``: one row an attention (two a layer for the
    shortcut block).

    The cache is stored **feature-major** (head_dim in sublanes, cache slots in
    lanes) so decode reads are lane-aligned for any head_dim: C is always a
    multiple of 128, head_dim often is not (llama3.2 uses 64). With the
    conventional (C, head_dim) layout the flash-decode kernel would pad 64
    lanes to 128 and read twice the cache bytes — fatal for a path that is
    pure HBM bandwidth.

    Optional int8 quantization (``init_cache(quantized=True)``): k/v hold int8
    with per-slot fp32 scales (L, B, KH, 1, C) — decode is pure HBM bandwidth,
    so halving the cache bytes is up to ~2x decode throughput at long context.
    The scales fold EXACTLY into the decode einsums (scores scale per key
    slot, value scale folds into the softmax weights), so the only error is
    the int8 rounding itself (~0.4% RMS per tensor).
    """

    k: jnp.ndarray
    v: jnp.ndarray
    lengths: jnp.ndarray  # (B,) valid entries per sequence
    k_scale: jnp.ndarray | None = None  # (L, B, KH, 1, C) fp32 when quantized
    v_scale: jnp.ndarray | None = None
    # the sambay block (models/sambay.py): k/v are its ONE full-length row;
    # beside it a window layer's ring and a state-space layer's carried state.
    # The standard block under ``config.layer_types`` (models/kinds.py): k/v are
    # the FULL layers' rows only, ring_k/ring_v the sliding layers' rings
    ring_k: jnp.ndarray | None = None     # (window layers, B, KH, D, window)
    ring_v: jnp.ndarray | None = None
    ssm_state: jnp.ndarray | None = None  # (state layers, B, d_state, d_inner) float32
    conv_tail: jnp.ndarray | None = None  # (state layers, B, d_conv - 1, d_inner)
    # the gated_delta block (models/delta.py): k/v are a row a PERIOD (its full
    # attention), conv_tail its linear layers' [q; k; v] columns, and a linear
    # layer's matrix state a value head
    delta_state: jnp.ndarray | None = None  # (linear layers, B, value heads, d_k, d_v) float32

    @property
    def capacity(self) -> int:
        return self.k.shape[4]

    @property
    def quantized(self) -> bool:
        return self.k_scale is not None


class CacheLeaf(NamedTuple):
    """What a walk over the cache has to know of one of its leaves."""

    slot_axis: int             # the axis a slot (a sequence) is a row of
    capacity_axis: int | None  # the axis that grows with the slot capacity; None: a
    #                            slot's part is as large whatever the capacity, and
    #                            moves whole (a ring wraps, a state has no positions)
    kind: str                  # what memory reports it as: "full" | "ring" | "state"


# THE declaration of the cache's leaves (``lengths`` aside, which is per slot
# and nothing else): a new kind of per-slot state is a field above, a line here
# and its allocation in ``init_cache``; the engine's one walk over a cache
# (serve/engine.py cache_leaves) places, pins, splices and counts from this.
CACHE_LEAVES: dict[str, CacheLeaf] = {
    "k": CacheLeaf(1, 4, "full"),
    "v": CacheLeaf(1, 4, "full"),
    "k_scale": CacheLeaf(1, 4, "full"),
    "v_scale": CacheLeaf(1, 4, "full"),
    "ring_k": CacheLeaf(1, None, "ring"),
    "ring_v": CacheLeaf(1, None, "ring"),
    "ssm_state": CacheLeaf(1, None, "state"),
    "conv_tail": CacheLeaf(1, None, "state"),
    "delta_state": CacheLeaf(1, None, "state"),
}


def init_cache(
    config: ModelConfig,
    batch: int,
    capacity: int,
    dtype=jnp.bfloat16,
    quantized: bool = False,
) -> KVCache:
    if config.mla:
        # MLA: ONE shared latent column [c_kv; roped k_pe] per token rides
        # the k array (KH=1, width rank+rope); v is a 1-wide dummy the scan
        # carries untouched (models/mla.py) — the latent is already ~10x
        # smaller than per-head K/V, so int8 cache quant is not wired here
        if quantized:
            raise ValueError("MLA caches are latent-compressed; kv_quant is unsupported")
        return KVCache(
            k=jnp.zeros((config.cache_rows, batch, 1, config.mla_cache_dim, capacity), dtype=dtype),
            v=jnp.zeros((config.cache_rows, batch, 1, 1, capacity), dtype=dtype),
            lengths=jnp.zeros((batch,), dtype=jnp.int32),
        )
    if config.block == "sambay":
        # pairs of K/V heads are stored as one head of twice the width
        # (models/sambay.py); the recurrent state is float32 whatever ``dtype``
        if quantized:
            raise ValueError("the sambay block's cache is not quantized: kv_quant is unsupported")
        heads, width = config.n_kv_heads // 2, 2 * config.head_dim
        state_layers = config.window_pairs + 1
        row = (1, batch, heads, width, capacity)
        ring = (config.window_pairs, batch, heads, width, config.sliding_window)
        return KVCache(
            k=jnp.zeros(row, dtype=dtype),
            v=jnp.zeros(row, dtype=dtype),
            lengths=jnp.zeros((batch,), dtype=jnp.int32),
            ring_k=jnp.zeros(ring, dtype=dtype),
            ring_v=jnp.zeros(ring, dtype=dtype),
            ssm_state=jnp.zeros(
                (state_layers, batch, config.ssm_d_state, config.d_inner), dtype=jnp.float32
            ),
            conv_tail=jnp.zeros(
                (state_layers, batch, config.ssm_d_conv - 1, config.d_inner), dtype=dtype
            ),
        )
    if config.layer_types is not None:
        # rows for the full layers, rings of ``sliding_window`` columns for the
        # sliding ones: a window layer never reads past its window, so a
        # full-length row for it would be memory nothing attends over
        if quantized:
            raise ValueError(
                "a layer_types model's rings are not quantized: kv_quant is unsupported "
                "(ROADMAP.md R7)"
            )
        heads = (batch, config.n_kv_heads, config.head_dim)
        row = (config.full_layers, *heads, capacity)
        ring = (config.window_layers, *heads, config.sliding_window)
        return KVCache(
            k=jnp.zeros(row, dtype=dtype),
            v=jnp.zeros(row, dtype=dtype),
            lengths=jnp.zeros((batch,), dtype=jnp.int32),
            ring_k=jnp.zeros(ring, dtype=dtype),
            ring_v=jnp.zeros(ring, dtype=dtype),
        )
    if config.block == "gated_delta":
        # K/V for the full-attention layers only, state and tail for the linear
        # ones only; the matrix state is float32 whatever ``dtype``
        if quantized:
            raise ValueError(
                "the gated_delta block's cache is not quantized: kv_quant is unsupported "
                "(ROADMAP.md R7)"
            )
        row = (config.cache_rows, batch, config.n_kv_heads, config.head_dim, capacity)
        return KVCache(
            k=jnp.zeros(row, dtype=dtype),
            v=jnp.zeros(row, dtype=dtype),
            lengths=jnp.zeros((batch,), dtype=jnp.int32),
            conv_tail=jnp.zeros(
                (config.linear_layers, batch, config.linear_conv - 1, config.linear_conv_channels),
                dtype=dtype,
            ),
            delta_state=jnp.zeros(
                (config.linear_layers, batch, config.linear_value_heads,
                 config.linear_key_dim, config.linear_value_dim),
                dtype=jnp.float32,
            ),
        )
    shape = (config.cache_rows, batch, config.n_kv_heads, config.head_dim, capacity)
    scale_shape = (config.cache_rows, batch, config.n_kv_heads, 1, capacity)
    if quantized:
        return KVCache(
            k=jnp.zeros(shape, dtype=jnp.int8),
            v=jnp.zeros(shape, dtype=jnp.int8),
            lengths=jnp.zeros((batch,), dtype=jnp.int32),
            k_scale=jnp.zeros(scale_shape, dtype=jnp.float32),
            v_scale=jnp.zeros(scale_shape, dtype=jnp.float32),
        )
    return KVCache(
        k=jnp.zeros(shape, dtype=dtype),
        v=jnp.zeros(shape, dtype=dtype),
        lengths=jnp.zeros((batch,), dtype=jnp.int32),
    )


def quantize_kv(x: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Per-slot symmetric int8: x is (..., head_dim, S). Returns (q, scale)
    with scale shaped (..., 1, S)."""
    absmax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-2, keepdims=True)
    scale = absmax / 127.0
    q = jnp.round(x.astype(jnp.float32) / jnp.maximum(scale, 1e-12)).astype(jnp.int8)
    return q, scale


def _norm(x: jnp.ndarray, weight: jnp.ndarray, config: ModelConfig) -> jnp.ndarray:
    return rms_norm(x, weight, config.rms_eps, plus_one=config.norm_plus_one)


def _lora_kernel_eligible(w: Any, x: jnp.ndarray, b: jnp.ndarray) -> bool:
    """Gate for the fused gathered-LoRA pallas kernel (ops/pallas_lora.py):
    plain (unquantized) 2-D base weight, single device (a bare pallas_call
    cannot partition under SPMD jit — same rule as the quantized-matmul
    kernels), and a TPU backend — or interpret mode, which is how the CPU
    test matrix pins the kernel bit-identical to the einsum chain. Real
    TPUs additionally need lane-aligned projection dims; interpret mode
    relaxes that so tiny test models still exercise the kernel."""
    from prime_tpu.models.quantize import _mesh_context_active
    from prime_tpu.ops.attention import _pallas_interpret

    if isinstance(w, tuple) or getattr(w, "ndim", 0) != 2:
        return False
    if _mesh_context_active():
        return False
    if _pallas_interpret():
        return True
    return (
        jax.default_backend() == "tpu"
        and x.shape[-1] % 128 == 0
        and b.shape[-1] % 128 == 0
    )


def _lora_mm(
    x: jnp.ndarray,               # (B, S, d_in) projection input
    lp: Params,                   # one layer's params (may carry lora stacks)
    name: str,                    # target projection ("wq", "w_down", ...)
    adapter_ids: jnp.ndarray | None,  # (B,) int32 per-row bank slots
) -> jnp.ndarray:
    """One adapted projection: ``x @ W`` plus, when the layer carries a
    multi-LoRA bank stack for this target, the per-row gathered BGMV-style
    delta ``(x @ A[idx]) @ B'[idx]`` (serve/adapters.py — B' has the LoRA
    scale folded in; bank slot 0 is the all-zeros base adapter, so base rows
    add an exact zero). Factor math runs in fp32 like ``merge_lora``'s delta
    — the factors are tiny, no reason to round them — and the delta is added
    in the activation dtype, mirroring the merged path's cast.

    When eligible, base + gather + delta run as ONE pallas program
    (ops/pallas_lora.fused_lora_matmul — the adapter gather happens in the
    kernel's BlockSpec index maps, so the stacked bank is never copied per
    row); the kernel replicates this chain's rounding exactly and the einsum
    path below stays the non-TPU/mesh reference."""
    a = lp.get(f"lora:{name}:a")  # (A, d_in, r) this layer's stacked A
    if a is None or adapter_ids is None:
        return _mm(x, lp[name])
    b = lp[f"lora:{name}:b"]      # (A, r, d_out)
    w = lp[name]
    if _lora_kernel_eligible(w, x, b):
        from prime_tpu.ops.attention import _pallas_interpret
        from prime_tpu.ops.pallas_lora import fused_lora_matmul

        return fused_lora_matmul(
            x, w, a, b, adapter_ids, interpret=_pallas_interpret()
        )
    y = _mm(x, w)
    a_rows = a[adapter_ids].astype(jnp.float32)   # (B, d_in, r) row gather
    b_rows = b[adapter_ids].astype(jnp.float32)   # (B, r, d_out)
    h = jnp.einsum("bsd,bdr->bsr", x.astype(jnp.float32), a_rows)
    delta = jnp.einsum("bsr,bro->bso", h, b_rows)
    return y + delta.astype(y.dtype)


def merge_adapter_stacks(stack: Params, adapters: dict | None, rows: slice) -> Params:
    """Merge a multi-LoRA bank's per-target ``(L, A, ...)`` factor stacks
    into a layer-param stack under reserved ``lora:<target>:a/b`` keys, so
    the stacks scan with the layer params (one compiled layer body, adapters
    included) — sliced by the same ``rows`` the layer stacks use. Targets
    absent from this stack (e.g. attention keys of a different stack) are
    skipped."""
    if adapters is None:
        return stack
    merged = dict(stack)
    for name, ab in adapters["layers"].items():
        if name not in stack:
            continue
        merged[f"lora:{name}:a"] = ab["a"][rows]
        merged[f"lora:{name}:b"] = ab["b"][rows]
    return merged


def init_params(rng: jax.Array, config: ModelConfig, dtype=jnp.bfloat16) -> Params:
    """Random init (truncated-normal-ish scaled); checkpoint loaders overwrite."""
    if config.block == "shortcut_moe":
        return _init_shortcut_params(rng, config, dtype)
    if config.block == "sambay":
        from prime_tpu.models.sambay import init_params as init_sambay_params

        return init_sambay_params(rng, config, dtype)
    if config.block == "gated_delta":
        return _init_delta_params(rng, config, dtype)
    if config.layer_types is not None:
        from prime_tpu.models.kinds import init_params as init_kinds_params

        return init_kinds_params(rng, config, dtype)
    keys = jax.random.split(rng, 17)
    d, hd = config.d_model, config.head_dim
    h, kh, ff, layers = config.n_heads, config.n_kv_heads, config.d_ff, config.n_layers
    # DeepSeek dense prefix: the MLP stacks cover only the tail layers — at
    # 256-expert scale, building full-length expert stacks just to slice
    # them would be a multi-GB transient allocation
    mlp_layers = layers - config.first_k_dense
    # Gemma-style (1+w) norms are zero-initialized (≡ unit scale)
    norm_init = jnp.zeros if config.norm_plus_one else jnp.ones

    def dense(key, shape, fan_in):
        return (jax.random.normal(key, shape, dtype=jnp.float32) * (fan_in**-0.5)).astype(dtype)

    if config.is_moe:
        # the experts HELD here (a share of an expert-parallel deployment, or
        # all of them); the router keeps its published width
        experts = config.experts_held
        mlp_weights = {
            # router stays fp32: routing decisions are precision-sensitive
            "router": dense(keys[9], (mlp_layers, d, config.n_experts), d).astype(jnp.float32),
            "w_gate": dense(keys[5], (mlp_layers, experts, d, ff), d),
            "w_up": dense(keys[6], (mlp_layers, experts, d, ff), d),
            "w_down": dense(keys[7], (mlp_layers, experts, ff, d), ff),
        }
        if config.moe_bias:  # GPT-OSS: router + every expert projection
            mlp_weights |= {
                "router_bias": jnp.zeros((mlp_layers, config.n_experts), dtype=jnp.float32),
                "b_gate": jnp.zeros((mlp_layers, experts, ff), dtype=dtype),
                "b_up": jnp.zeros((mlp_layers, experts, ff), dtype=dtype),
                "b_down": jnp.zeros((mlp_layers, experts, d), dtype=dtype),
            }
        if config.moe_score_bias:  # DeepSeek-V3 aux-free balance bias (fp32,
            # selection-only — updated out-of-band, not by the loss)
            mlp_weights["score_bias"] = jnp.zeros((mlp_layers, config.n_experts), dtype=jnp.float32)
        if config.n_shared_experts:  # DeepSeekMoE always-on shared expert(s)
            sf = config.n_shared_experts * ff
            mlp_weights |= {
                "w_shared_gate": dense(keys[10], (mlp_layers, d, sf), d),
                "w_shared_up": dense(keys[11], (mlp_layers, d, sf), d),
                "w_shared_down": dense(keys[12], (mlp_layers, sf, d), sf),
            }
    else:
        mlp_weights = {
            "w_gate": dense(keys[5], (mlp_layers, d, ff), d),
            "w_up": dense(keys[6], (mlp_layers, d, ff), d),
            "w_down": dense(keys[7], (mlp_layers, ff, d), ff),
        }

    attn_biases = {}
    if config.attn_bias:  # Qwen2-style q/k/v biases (no output bias)
        attn_biases = {
            "bq": jnp.zeros((layers, h * hd), dtype=dtype),
            "bk": jnp.zeros((layers, kh * hd), dtype=dtype),
            "bv": jnp.zeros((layers, kh * hd), dtype=dtype),
        }
    if config.attn_out_bias:  # Llama-arch attention_bias biases o_proj too
        attn_biases["bo"] = jnp.zeros((layers, d), dtype=dtype)
    if config.qk_norm:  # Qwen3-style per-head q/k RMSNorm (weights shared across heads)
        attn_biases |= {
            "q_norm": norm_init((layers, hd), dtype=dtype),
            "k_norm": norm_init((layers, hd), dtype=dtype),
        }
    if config.qk_norm_full:  # OLMo-2: rms statistic spans all heads jointly
        attn_biases |= {
            "q_norm_full": norm_init((layers, h * hd), dtype=dtype),
            "k_norm_full": norm_init((layers, kh * hd), dtype=dtype),
        }
    if config.attn_sinks:  # GPT-OSS: per-head sink logits (fp32 — they live
        # inside the softmax normalization)
        attn_biases["sinks"] = jnp.zeros((layers, h), dtype=jnp.float32)
    if config.post_norms:  # Gemma2/OLMo-2 norms on the block outputs
        attn_biases |= {
            "attn_post_norm": norm_init((layers, d), dtype=dtype),
            "mlp_post_norm": norm_init((layers, d), dtype=dtype),
        }
    pre_norms = (
        {
            "attn_norm": norm_init((layers, d), dtype=dtype),
            "mlp_norm": norm_init((layers, d), dtype=dtype),
        }
        if config.pre_norms
        else {}
    )
    if config.mla:
        from prime_tpu.models.mla import init_mla_attn_params

        attn_weights = init_mla_attn_params(keys, config, dtype, dense)
    else:
        attn_weights = {
            "wq": dense(keys[1], (layers, d, h * hd), d),
            "wk": dense(keys[2], (layers, d, kh * hd), d),
            "wv": dense(keys[3], (layers, d, kh * hd), d),
            "wo": dense(keys[4], (layers, h * hd, d), h * hd),
        }
    shared_keys = {**attn_weights, **pre_norms, **attn_biases}
    params: Params = {
        "embed": dense(keys[0], (config.vocab_size, d), d),
        "layers": {
            **shared_keys,
            **mlp_weights,
        },
        "final_norm": norm_init((d,), dtype=dtype),
    }
    if config.first_k_dense:
        # DeepSeek dense-prefix: the first k layers swap the MoE for a dense
        # MLP of width dense_ff. Attention/norm/bias stacks were built over
        # ALL layers — split them; the MLP stacks were already built
        # tail-sized (mlp_layers).
        kd = config.first_k_dense
        dff = config.dense_ff or ff
        params["layers"] = {
            key: (value[kd:] if key in shared_keys else value)
            for key, value in params["layers"].items()
        }
        params["dense_layers"] = {
            **{key: value[:kd] for key, value in shared_keys.items()},
            "w_gate": dense(keys[14], (kd, d, dff), d),
            "w_up": dense(keys[15], (kd, d, dff), d),
            "w_down": dense(keys[16], (kd, dff, d), dff),
        }
    if not config.tie_embeddings:
        params["lm_head"] = dense(keys[8], (d, config.vocab_size), d)
    return params


# the shortcut block's per-sub-layer params, stacked (L, 2, ...); the router
# and the experts are one a layer, (L, ...)
_SHORTCUT_EXPERT_KEYS = ("router", "score_bias", "experts_gate", "experts_up", "experts_down")


def _init_shortcut_params(rng: jax.Array, config: ModelConfig, dtype) -> Params:
    """Random init of the shortcut-connected double layer (LongCat-Flash):
    the same recipe and key numbering as ``init_params`` (17 keys; a matrix
    is normal * fan_in**-0.5 rounded to ``dtype``), the experts on keys
    10-12. Each matrix is made by one jitted call, so that its float32
    draw never sits in memory beside the weights already made: at the
    published widths one expert stack is 3.2 GB in float32."""
    from prime_tpu.models.mla import init_mla_attn_params

    keys = jax.random.split(rng, 17)
    d, ff, dff = config.d_model, config.d_ff, config.dense_ff or config.d_ff
    layers, held, width = config.n_layers, config.experts_held, config.router_width

    def dense(key, shape, fan_in, out=dtype):
        make = jax.jit(
            lambda k: (jax.random.normal(k, shape, dtype=jnp.float32) * (fan_in**-0.5)).astype(out)
        )
        return make(key)

    pair = (layers, 2)
    stack = {
        **init_mla_attn_params(keys, config, dtype, dense, lead=pair),
        "attn_norm": jnp.ones((*pair, d), dtype=dtype),
        "mlp_norm": jnp.ones((*pair, d), dtype=dtype),
        "w_gate": dense(keys[5], (*pair, d, dff), d),
        "w_up": dense(keys[6], (*pair, d, dff), d),
        "w_down": dense(keys[7], (*pair, dff, d), dff),
        # router and selection bias stay fp32, as every router here
        "router": dense(keys[9], (layers, d, width), d, out=jnp.float32),
        "score_bias": jnp.zeros((layers, width), dtype=jnp.float32),
        "experts_gate": dense(keys[10], (layers, held, d, ff), d),
        "experts_up": dense(keys[11], (layers, held, d, ff), d),
        "experts_down": dense(keys[12], (layers, held, ff, d), ff),
    }
    params: Params = {
        "embed": dense(keys[0], (config.vocab_size, d), d),
        "layers": stack,
        "final_norm": jnp.ones((d,), dtype=dtype),
    }
    if not config.tie_embeddings:
        params["lm_head"] = dense(keys[8], (d, config.vocab_size), d)
    return params


# the gated_delta block's random recipe: which of the 24 keys split from the
# seed's key draws which leaf (benchmark/reference/qwen3_next.py restates it)
DELTA_KEY_OF = {
    "embed": 0, "lm_head": 1, "router": 2, "w_gate": 3, "w_up": 4, "w_down": 5,
    "w_shared_gate": 6, "w_shared_up": 7, "w_shared_down": 8, "shared_expert_gate": 9,
    "delta.w_qkvz": 10, "delta.w_ba": 11, "delta.conv_w": 12, "delta.A_log": 13,
    "delta.dt_bias": 14, "delta.w_out": 15,
    "attn.wq": 16, "attn.wk": 17, "attn.wv": 18, "attn.wo": 19,
}
# leaves of the gated_delta stack that are not one a layer: the linear layers'
# and the full layers' own stacks, and the expert matrices, which the grouped
# matmul takes whole
_DELTA_WHOLE = ("delta", "attn", "w_gate", "w_up", "w_down")


def _init_delta_params(rng: jax.Array, config: ModelConfig, dtype) -> Params:
    """Random init of the gated_delta block (Qwen3-Next, Olmo-Hybrid). A matrix
    is ``normal * fan_in**-0.5`` rounded to ``dtype`` (the router float32), the
    shared expert's gate vector and the conv taps the same with their fan-in;
    a norm's weight is what scales by one (zero under ``norm_plus_one``), the
    gated norm's weight one. ``A_log`` is uniform in [log 1e-3, log 1e-1] and
    ``dt_bias`` uniform in [-1, 1] (both float32, a value head): a state then
    keeps a token for some 10 to 1000 positions, where the published
    initialisation (A uniform in (0, 16), dt_bias 1) forgets it within one
    under random inputs. One jitted draw a leaf, stacked over its layers:
    (n_layers, ...) for what every layer has, (linear layers, ...) under
    ``delta`` and (periods, ...) under ``attn``. Which norms a layer has, whether
    its feed-forward is the expert layer or dense, and what the full attention
    norms and gates follow the configuration's flags."""
    keys = jax.random.split(rng, 24)
    d, ff, hd = config.d_model, config.d_ff, config.head_dim
    layers, linear, periods = config.n_layers, config.linear_layers, config.cache_rows
    h, kh = config.n_heads, config.n_kv_heads
    n_v, taps, channels = config.linear_value_heads, config.linear_conv, config.linear_conv_channels
    value = n_v * config.linear_value_dim
    shared = config.shared_ff

    def draw(name, shape, fan_in, out=dtype):
        make = jax.jit(
            lambda k: (jax.random.normal(k, shape, dtype=jnp.float32) * (fan_in**-0.5)).astype(out)
        )
        return make(keys[DELTA_KEY_OF[name]])

    def uniform(name, low, high):
        u = jax.random.uniform(keys[DELTA_KEY_OF[name]], (linear, n_v), dtype=jnp.float32)
        return low + u * (high - low)

    def unit_norm(*shape):  # a norm weight that scales by one
        return (jnp.zeros if config.norm_plus_one else jnp.ones)(shape, dtype=dtype)

    stack = {}
    if config.pre_norms:
        stack.update(attn_norm=unit_norm(layers, d), mlp_norm=unit_norm(layers, d))
    if config.post_norms:
        stack.update(attn_post_norm=unit_norm(layers, d), mlp_post_norm=unit_norm(layers, d))
    if config.is_moe:
        stack.update(
            router=draw("router", (layers, d, config.n_experts), d, out=jnp.float32),
            w_gate=draw("w_gate", (layers, config.experts_held, d, ff), d),
            w_up=draw("w_up", (layers, config.experts_held, d, ff), d),
            w_down=draw("w_down", (layers, config.experts_held, ff, d), ff),
            w_shared_gate=draw("w_shared_gate", (layers, d, shared), d),
            w_shared_up=draw("w_shared_up", (layers, d, shared), d),
            w_shared_down=draw("w_shared_down", (layers, shared, d), shared),
            shared_expert_gate=draw("shared_expert_gate", (layers, d), d),
        )
    else:
        stack.update(
            w_gate=draw("w_gate", (layers, d, ff), d),
            w_up=draw("w_up", (layers, d, ff), d),
            w_down=draw("w_down", (layers, ff, d), ff),
        )
    stack["delta"] = {
        "w_qkvz": draw("delta.w_qkvz", (linear, d, channels + value), d),
        "w_ba": draw("delta.w_ba", (linear, d, 2 * n_v), d),
        "conv_w": draw("delta.conv_w", (linear, taps, channels), taps),
        "A_log": uniform("delta.A_log", math.log(1e-3), math.log(1e-1)),
        "dt_bias": uniform("delta.dt_bias", -1.0, 1.0),
        "gate_norm": jnp.ones((linear, config.linear_value_dim), dtype=dtype),
        "w_out": draw("delta.w_out", (linear, value, d), value),
    }
    attn = {
        "wq": draw("attn.wq", (periods, d, (2 if config.attn_output_gate else 1) * h * hd), d),
        "wk": draw("attn.wk", (periods, d, kh * hd), d),
        "wv": draw("attn.wv", (periods, d, kh * hd), d),
        "wo": draw("attn.wo", (periods, h * hd, d), h * hd),
    }
    if config.qk_norm:
        attn.update(q_norm=unit_norm(periods, hd), k_norm=unit_norm(periods, hd))
    if config.qk_norm_full:
        attn.update(q_norm_full=unit_norm(periods, h * hd), k_norm_full=unit_norm(periods, kh * hd))
    stack["attn"] = attn
    return {
        "embed": draw("embed", (config.vocab_size, d), d),
        "layers": stack,
        "final_norm": unit_norm(d),
        "lm_head": draw("lm_head", (d, config.vocab_size), d),
    }


def sliding_layer_flags(config: ModelConfig) -> jnp.ndarray:
    """(n_layers,) bool: which layers use the sliding window
    (``ModelConfig.sliding_layers``: the pattern is an explicit config field so
    non-Gemma2 window schemes can't silently inherit the even alternation).
    Shared by forward() and the pipeline (where the flags shard over pp
    alongside the layer stack)."""
    if not config.sliding_window:
        return jnp.zeros((config.n_layers,), dtype=bool)
    return jnp.asarray(config.sliding_layers, dtype=bool)


def _scales(kv: tuple[jnp.ndarray, ...]) -> dict[str, jnp.ndarray]:
    """The int8 cache's scale leaves as the attention ops' keywords."""
    return dict(k_scale=kv[2], v_scale=kv[3]) if len(kv) == 4 else {}


def _attention_block(
    x: jnp.ndarray,               # (B, S, D)
    lp: Params,                   # one layer's params
    positions: jnp.ndarray,       # (B, S)
    rope_tables: tuple[jnp.ndarray, jnp.ndarray],
    config: ModelConfig,
    kv: tuple[jnp.ndarray, ...],  # the STACKED cache leaves (L, B, KH, *, C): (k, v),
    #                               (k, v, k_scale, v_scale) when quantized, () without a cache
    layer: jnp.ndarray | None,    # () traced: this block's layer in the stacked leaves
    cache_lengths: jnp.ndarray | None,
    decode: bool,
    attn_impl: str,
    attend_lengths: jnp.ndarray | None = None,  # (B,) decode: slots each row attends over
    #                                             (None: its cache and this step's column)
    work: tuple | None = None,                  # decode: the kernel's work list for them
    column_work: tuple | None = None,           # decode: the column write's list of live rows
    prefill_offset: jnp.ndarray | None = None,  # () chunked prefill: write+attend at offset
    sliding: jnp.ndarray | None = None,  # () traced bool: this layer uses the window
    rope_tables_local: tuple[jnp.ndarray, jnp.ndarray] | None = None,
    mesh=None,  # mesh-aware impls: "ring" (context-parallel training),
    #             "sharded" (serve decode: flash kernel under shard_map)
    adapter_ids: jnp.ndarray | None = None,  # (B,) multi-LoRA bank slots
    heads: int | None = None,   # this layer's query heads (None: config.n_heads)
    window: int | None = None,  # this layer's window (None: config.sliding_window
    #                             under ``sliding``); a layer-kind walk states it
    ring_attend=None,  # a window layer over a ring (models/kinds.py): (q, staged
    #                    blocks, ring leaves) -> (attention, ring leaves), in place
    #                    of the cached regimes below
):
    """One layer's attention. Returns (x + attention, kv): the stacked leaves
    with this layer's new K/V written in place (ops.attention.write_cache)."""
    batch, seq, _ = x.shape
    h, kh, hd = heads or config.n_heads, config.n_kv_heads, config.head_dim
    sm_scale = (config.query_scale or hd) ** -0.5
    gemma_kw = dict(
        softcap=config.attn_softcap, sliding=sliding, sinks=lp.get("sinks"),
        window=config.sliding_window if window is None else window,
    )
    cos, sin = rope_tables
    # gather the seq-sized rows FIRST, then (Gemma3) select local vs global
    # by the traced per-layer flag — selecting full (max_pos, D/2) tables in
    # every scanned layer would waste HBM bandwidth in the decode hot loop
    cos_rows, sin_rows = cos[positions], sin[positions]  # (B, S, D/2)
    if rope_tables_local is not None and sliding is not None:
        cos_rows = jnp.where(sliding, rope_tables_local[0][positions], cos_rows)
        sin_rows = jnp.where(sliding, rope_tables_local[1][positions], sin_rows)

    # OLMo-2 is post-norm only: no input norm param, the raw residual feeds in
    normed = _norm(x, lp["attn_norm"], config) if "attn_norm" in lp else x
    q = _lora_mm(normed, lp, "wq", adapter_ids)
    k = _lora_mm(normed, lp, "wk", adapter_ids)
    v = _lora_mm(normed, lp, "wv", adapter_ids)
    if "bq" in lp:  # Qwen2-style q/k/v biases
        q, k, v = q + lp["bq"], k + lp["bk"], v + lp["bv"]
    if "q_norm_full" in lp:  # OLMo-2: full-width RMSNorm before the head split
        q = _norm(q, lp["q_norm_full"], config)
        k = _norm(k, lp["k_norm_full"], config)
    gate = None
    if config.attn_output_gate:  # a head's projection is [query; gate], hd each
        q, gate = jnp.split(q.reshape(batch, seq, h, 2 * hd), 2, axis=-1)
        gate = gate.reshape(batch, seq, h * hd)
    q = q.reshape(batch, seq, h, hd)
    k = k.reshape(batch, seq, kh, hd)
    v = v.reshape(batch, seq, kh, hd)
    if "q_norm" in lp:  # Qwen3/Gemma3-style per-head RMSNorm before rope
        q = _norm(q, lp["q_norm"], config)
        k = _norm(k, lp["k_norm"], config)
    if config.rotary:
        q = apply_rope_rows(q, cos_rows, sin_rows)
        k = apply_rope_rows(k, cos_rows, sin_rows)

    q = q.transpose(0, 2, 1, 3)  # (B, H, S, hd)
    k = k.transpose(0, 2, 1, 3)
    v = v.transpose(0, 2, 1, 3)

    def staged(k, v):
        """This call's K/V (B, KH, S, hd) as the cache stores them:
        feature-major blocks, plus their per-slot scales when the cache is
        int8. The scales are PER-SLOT and every call writes its own slots, so
        each column is quantized once, whichever regime wrote it."""
        k_t, v_t = k.transpose(0, 1, 3, 2), v.transpose(0, 1, 3, 2)  # (B, KH, hd, S)
        if len(kv) == 2:
            return k_t, v_t
        (k_q, k_s), (v_q, v_s) = quantize_kv(k_t), quantize_kv(v_t)
        return k_q, v_q, k_s, v_s

    if ring_attend is not None and kv:
        attn, kv = ring_attend(q, staged(k, v), kv)
    elif decode:
        assert kv and cache_lengths is not None
        # this step's k/v column goes into each sequence's next free slot
        kv = write_cache(
            kv, staged(k, v), layer, cache_lengths, impl=attn_impl, work=column_work
        )
        attn = decode_attention(
            q, kv[0], kv[1], cache_lengths + 1 if attend_lengths is None else attend_lengths,
            sm_scale, impl=attn_impl, work=work,
            mesh=mesh, layer=layer, **_scales(kv), **gemma_kw,
        )
    elif prefill_offset is not None:
        # chunked prefill: write this chunk's K/V into the cache at the
        # offset — () one shared chunk offset, (B,) per-row window starts
        # (speculative verify) — then attend over the cache (earlier chunks
        # + reused prefix are visible; within-chunk attention stays causal
        # via the mask)
        assert kv
        kv = write_cache(kv, staged(k, v), layer, prefill_offset)
        attn = cache_prefill_attention(
            q, kv[0], kv[1], prefill_offset.astype(jnp.int32), sm_scale,
            layer=layer, **_scales(kv), **gemma_kw,
        )
    elif attn_impl == "ring":
        # context-parallel training: the sequence axis is sharded over the
        # mesh's `sp` axis and KV blocks rotate via ring attention
        # (parallel/ring_attention.py); no-cache path only. The uniform
        # window/softcap/sink knobs ride the ring fold; PER-LAYER sliding
        # schedules can't (the hop cap must be static and uniform across
        # the scanned layers), which forward() rejects up front.
        from prime_tpu.parallel.ring_attention import ring_self_attention
        from prime_tpu.parallel.sharding import ring_qkv_axes

        batch_axis, head_axis = ring_qkv_axes(mesh, kh)
        attn = ring_self_attention(
            q, k, v, mesh, seq_axis="sp", sm_scale=sm_scale,
            window=config.sliding_window, softcap=config.attn_softcap,
            sinks=lp.get("sinks"),
            batch_axis=batch_axis, head_axis=head_axis,
        )
    else:
        attn = multi_head_attention(q, k, v, sm_scale, impl=attn_impl, **gemma_kw)
        if kv:
            # prefill: stage the prompt's k/v feature-major at slots [0, S)
            kv = write_cache(kv, staged(k, v), layer, jnp.zeros((), jnp.int32))

    attn = attn.transpose(0, 2, 1, 3).reshape(batch, seq, h * hd)
    if gate is not None:
        attn = attn * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(attn.dtype)
    if "w_head_gate" in lp:  # Laguna: one scalar a head and token, from the normed input
        head_gate = jax.nn.sigmoid(_mm(normed, lp["w_head_gate"]).astype(jnp.float32))
        attn = (attn.reshape(batch, seq, h, hd) * head_gate[..., None].astype(attn.dtype)).reshape(
            batch, seq, h * hd
        )
    out = _lora_mm(attn, lp, "wo", adapter_ids)
    if "bo" in lp:  # Llama-arch attention_bias checkpoints bias o_proj too
        out = out + lp["bo"]
    if "attn_post_norm" in lp:  # Gemma2-style post-norm before the residual add
        out = _norm(out, lp["attn_post_norm"], config)
    return x + out, kv


def _moe_dropless(stack: Params) -> bool:
    """Whether a cached forward over this layer stack runs its experts through
    the dropless ``moe_infer``: expert weights in one plain array (int8/int4
    (q, scale) pairs keep the capacity einsum's dequantising contraction) and
    no SPMD mesh (ep-sharded experts keep the einsum, whose token contraction
    GSPMD turns into the exchange)."""
    from prime_tpu.models.quantize import _mesh_context_active

    return (
        "router" in stack
        and not isinstance(stack["w_gate"], tuple)
        and not _mesh_context_active()
    )


def runs_dropless_experts(params: Params, config: ModelConfig) -> bool:
    """Whether a cached ``forward`` of this model runs ``ops.moe.moe_infer``
    (under the caller's mesh context): the block kinds built on it always,
    the standard block where ``_moe_dropless`` says so."""
    if not config.is_moe:
        return False
    if config.block != "standard" or config.layer_types is not None:
        return True
    return _moe_dropless(params["layers"])


def _mlp_block(
    x: jnp.ndarray, lp: Params, config: ModelConfig,
    adapter_ids: jnp.ndarray | None = None,
    dropless: bool = False,  # inference: ops.moe.moe_infer, nothing dropped
    token_mask: jnp.ndarray | None = None,  # (B, S) rows the experts skip
    layer: jnp.ndarray | None = None,  # () dropless: lp's expert matrices are whole
    #                                    (L, held, ...) stacks, and this their layer
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray | None]:
    """Dense or sparse-MoE feed-forward. Returns (residual output, aux loss,
    the dropless expert layer's counts or None). The dropless layer computes
    the picks that fall among the experts held here, ``[first_expert,
    first_expert + held)`` of the router's width (``config.n_experts_held``)."""
    normed = _norm(x, lp["mlp_norm"], config) if "mlp_norm" in lp else x
    # key-presence decides, not config.is_moe alone: a DeepSeek dense-prefix
    # layer (first_k_dense) carries a plain MLP inside an MoE model
    if config.is_moe and "router" in lp:
        from prime_tpu.ops.moe import moe_infer, moe_mlp

        routing = dict(
            k=config.experts_per_token,
            norm_topk=config.norm_topk,
            router_b=lp.get("router_bias"),
            b_gate=lp.get("b_gate"),
            b_up=lp.get("b_up"),
            b_down=lp.get("b_down"),
            glu_clamp=config.moe_glu_clamp,
            score_func=config.moe_score_func,
            select_bias=lp.get("score_bias"),
            routed_scale=config.routed_scaling_factor,
            route_groups=config.moe_n_groups,
            route_topk_groups=config.moe_topk_groups,
        )
        experts = (normed, lp["router"], lp["w_gate"], lp["w_up"], lp["w_down"])
        if dropless:
            with jax.named_scope("moe"):
                y, stats = moe_infer(
                    *experts, token_mask=token_mask, layer=layer,
                    first_expert=config.first_expert, **routing,
                )
            aux = jnp.zeros((), jnp.float32)
        elif config.n_experts_held is not None:
            raise ValueError(
                "a share of the experts (n_experts_held) is computed by the dropless "
                "expert layer of a cached forward only: the capacity einsum holds all of them"
            )
        else:
            y, aux = moe_mlp(*experts, capacity_factor=config.capacity_factor, **routing)
            stats = None
        if "w_shared_gate" in lp:
            # DeepSeekMoE shared expert(s): a dense always-on silu MLP added
            # to the routed output (every token, no capacity, no routing)
            shared_gate = jax.nn.silu(_mm(normed, lp["w_shared_gate"]))
            shared = _mm(shared_gate * _mm(normed, lp["w_shared_up"]), lp["w_shared_down"])
            if "shared_expert_gate" in lp:  # Qwen3-Next: times sigmoid(w . x)
                score = jnp.sum(
                    normed.astype(jnp.float32) * lp["shared_expert_gate"].astype(jnp.float32),
                    axis=-1, keepdims=True,
                )
                shared = shared * jax.nn.sigmoid(score).astype(shared.dtype)
            y = y + shared
        if "mlp_post_norm" in lp:
            y = _norm(y, lp["mlp_post_norm"], config)
        return x + y, aux, stats
    act = jax.nn.silu if config.act == "silu" else _gelu_tanh
    gate = act(_lora_mm(normed, lp, "w_gate", adapter_ids))
    up = _lora_mm(normed, lp, "w_up", adapter_ids)
    y = _lora_mm(gate * up, lp, "w_down", adapter_ids)
    if "mlp_post_norm" in lp:  # Gemma2-style post-norm before the residual add
        y = _norm(y, lp["mlp_post_norm"], config)
    return x + y, jnp.zeros((), jnp.float32), None


def _shortcut_block(
    x: jnp.ndarray,               # (B, S, D)
    stack: Params,                # the WHOLE layer stack: (L, 2, ...) attention/FFN/norm
    #                               leaves, (L, ...) router and experts
    layer: jnp.ndarray,           # () this layer; its attentions are cache rows 2*layer, 2*layer + 1
    kv: tuple[jnp.ndarray, ...],
    attend,                       # (x, sub-layer params, cache row, kv) -> (x + attention, kv)
    config: ModelConfig,
    token_mask: jnp.ndarray | None,
):
    """One shortcut-connected double layer (LongCat-Flash ScMoE). With
    ``u_i = norm(a_i; mlp_norm_i)``:

        a0 = h  + MLA_0(norm(h))     m   = MoE(u0)          # read here ...
        b0 = a0 + FFN_0(u0)
        a1 = b0 + MLA_1(norm(b0))    b1  = a1 + FFN_1(u1)
        out = b1 + m                                         # ... added here

    The MoE is ``ops.moe.moe_infer`` over the experts held here: dropless,
    the zero-compute experts included. Returns (out, kv, expert counts).

    The layer's matrices are indexed out of the stack HERE, ``w[layer, i]``,
    one slice a matmul, and the experts go to the grouped matmul as the
    whole stack with ``layer``: a layer handed over as the scan's xs is a
    (2, ...) slice of which the sub-layer is a second slice, and a kernel
    operand is a buffer of its own, so the chip's compiler copied every
    layer's weights before using them (compiler, PR 26: 2.5 GB a layer)."""
    from prime_tpu.ops.moe import moe_infer

    def sub(i):
        return {k: v[layer, i] for k, v in stack.items() if k not in _SHORTCUT_EXPERT_KEYS}

    def ffn(u, p):
        with jax.named_scope("ffn"):
            return _mm(jax.nn.silu(_mm(u, p["w_gate"])) * _mm(u, p["w_up"]), p["w_down"])

    lp0, lp1 = sub(0), sub(1)
    with jax.named_scope("mla"):
        a0, kv = attend(x, lp0, 2 * layer, kv)
    u0 = _norm(a0, lp0["mlp_norm"], config)
    with jax.named_scope("moe"):
        m, stats = moe_infer(
            u0, stack["router"][layer], stack["experts_gate"], stack["experts_up"],
            stack["experts_down"], layer=layer,
            k=config.experts_per_token, first_expert=config.first_expert,
            n_zero=config.n_zero_experts, token_mask=token_mask,
            norm_topk=config.norm_topk, score_func=config.moe_score_func,
            select_bias=stack["score_bias"][layer], routed_scale=config.routed_scaling_factor,
        )
    b0 = a0 + ffn(u0, lp0)
    with jax.named_scope("mla"):
        a1, kv = attend(b0, lp1, 2 * layer + 1, kv)
    b1 = a1 + ffn(_norm(a1, lp1["mlp_norm"], config), lp1)
    return b1 + m, kv, stats


def _delta_period(
    x: jnp.ndarray,               # (B, S, D)
    stack: Params,                # the WHOLE layer stack (``_init_delta_params``)
    period: jnp.ndarray,          # () this period; its full attention is cache row ``period``
    leaves: tuple[jnp.ndarray, ...],  # (k, v, conv_tail, delta_state), or () without a cache
    attend,                       # (x, layer params, cache row, kv) -> (x + attention, kv)
    config: ModelConfig,
    token_mask: jnp.ndarray | None,
    state_work: tuple | None = None,  # decode under the kernel's gate: its work list
):
    """One period of the gated_delta block (Qwen3-Next, Olmo-Hybrid):
    ``interval - 1`` linear-attention layers (models/delta.py) and one full
    attention, every layer ``h = x + post(mixer(pre(x))); out = h +
    post(feed(pre(h)))`` with the norms the stack holds (``attn_norm`` /
    ``mlp_norm`` before a sub-layer, ``attn_post_norm`` / ``mlp_post_norm`` on
    its output; a stack has either or both) and, for the feed-forward, the
    dropless expert layer over the experts held here with the gated shared
    expert, or a dense one. Returns (out, leaves, expert counts; 0 where no
    expert layer ran).

    As in ``_shortcut_block`` a layer's matrices are indexed out of the stack
    here, one slice a matmul, and the experts go to the grouped matmul as the
    whole stack with the layer. A linear layer's state and tail are rows of the
    carried leaves, read and rewritten in place: the tail (and, as plain XLA,
    the state) sliced out and put back, the state under ``state_work`` handed to
    the mixer as the whole leaf with its row, for the kernel that steps the
    live rows' blocks where they lie (ops/pallas_delta.py)."""
    from prime_tpu.models.delta import delta_mixer

    interval = config.full_attention_interval
    kv, carried = leaves[:2], leaves[2:]
    whole = _DELTA_WHOLE if config.is_moe else ("delta", "attn")
    stats = 0
    for i in range(interval):
        layer = period * interval + i
        lp = {name: w[layer] for name, w in stack.items() if name not in whole}
        if i < interval - 1:
            row = period * (interval - 1) + i
            mixer = {name: w[row] for name, w in stack["delta"].items()}
            tail = state = in_place = None
            if carried:
                tails, states = carried
                tail = tails[row]
                # under the kernel's gate the mixer takes the stacked leaf and its row
                state, in_place = (
                    (states[row], None) if state_work is None else (states, (row, state_work))
                )
            out, state, tail = delta_mixer(
                _norm(x, lp["attn_norm"], config) if "attn_norm" in lp else x,
                mixer, state, tail, token_mask,
                key_heads=config.linear_key_heads, in_place=in_place,
                neg_eigval=config.linear_neg_eigval, grouped=config.linear_grouped_proj,
            )
            if carried:
                put = jax.lax.dynamic_update_index_in_dim
                carried = (
                    put(tails, tail, row, 0), state if in_place else put(states, state, row, 0)
                )
            if "attn_post_norm" in lp:
                out = _norm(out, lp["attn_post_norm"], config)
            x = x + out
        else:
            full = {name: w[period] for name, w in stack["attn"].items()}
            around = {name: lp[name] for name in ("attn_norm", "attn_post_norm") if name in lp}
            with jax.named_scope("gated_attention" if config.attn_output_gate else "full_attention"):
                x, kv = attend(x, {**full, **around}, period, kv)
        if config.is_moe:
            experts = {name: stack[name] for name in ("w_gate", "w_up", "w_down")}
            x, _, layer_stats = _mlp_block(
                x, {**lp, **experts}, config, dropless=True, token_mask=token_mask, layer=layer
            )
            stats = stats + layer_stats
        else:
            x, _, _ = _mlp_block(x, lp, config)
    return x, (*kv, *carried), stats


def _gelu_tanh(x: jnp.ndarray) -> jnp.ndarray:
    """HF's gelu_pytorch_tanh (the Gemma MLP activation)."""
    return jax.nn.gelu(x, approximate=True)


def _refuse_for_delta(mesh, adapters, prefill_offset) -> None:
    """What the gated_delta block does not serve (ROADMAP.md R7 queues each;
    int8 and int4 weights are refused where they would be made,
    models/quantize.py)."""
    what = None
    if mesh is not None and getattr(mesh, "size", 1) > 1:
        what = "a mesh (its matrix state and expert share have no sharding rule)"
    elif adapters is not None:
        what = "a LoRA bank"
    elif prefill_offset is not None and jnp.ndim(prefill_offset):
        what = "a per-row chunk offset (speculative verify would have to rewind the state)"
    if what:
        raise ValueError(f"the gated_delta block does not serve {what}: ROADMAP.md R7")


def forward(
    params: Params,
    tokens: jnp.ndarray,                 # (B, S) int32
    config: ModelConfig,
    positions: jnp.ndarray | None = None,  # (B, S); default arange (+ prefill_offset)
    cache: KVCache | None = None,
    decode: bool = False,
    attn_impl: str = "auto",
    return_aux: bool = False,
    prefill_offset: jnp.ndarray | None = None,  # () traced; chunked prefill at offset
    remat: str = "none",  # "none" | "full" | "dots" — training-path rematerialization
    longrope_select: int | None = None,  # static run-length bound for LongRoPE
    mesh=None,  # mesh-aware attn impls — "ring": mesh whose `sp` axis shards
    #           the sequence; "sharded": serving mesh for the shard_mapped
    #           flash-decode dispatch (parallel/decode_sharded.py)
    last_positions: jnp.ndarray | None = None,  # (B,) → logits only at these rows
    adapters: dict | None = None,  # multi-LoRA bank stacks (serve/adapters.py)
    adapter_ids: jnp.ndarray | None = None,  # (B,) int32 per-row bank slots
    token_mask: jnp.ndarray | None = None,  # (B, S) bool: False rows belong to nobody
    return_moe_stats: bool = False,
    return_attn_steps: bool = False,
    return_column_writes: bool = False,
    return_state_steps: bool = False,
):
    """Run the transformer. Returns (logits (B, S, V) fp32, updated cache),
    plus the summed MoE load-balance aux loss when ``return_aux``, plus the
    dropless expert layers' summed counts (``ops.moe.MOE_STATS``; zeros where
    none ran) when ``return_moe_stats``, plus, when ``return_attn_steps``, a
    (2,) int32 vector: the grid steps this decode step's attention kernel calls
    made and the most they could have made (``ops.attention.decode_work``;
    zeros where no work list was made), plus, when ``return_column_writes``, the
    rows this decode step's column-write kernel calls wrote and the rows they
    were offered (``ops.attention.column_write_work``; zeros where the write is
    XLA's), plus, when ``return_state_steps``, the steps' pair for the
    gated_delta block's state kernel (``ops.attention.delta_decode_work``).
    With ``last_positions`` the head matmul runs on ONE gathered position per
    row and logits are (B, 1, V): a prefill that only needs each sequence's
    next-token logits skips S× the unembedding FLOPs and never materializes
    the (B, S, V) fp32 buffer (~8 GB at B=4, S=4k, llama vocab — observed
    crashing the remote TPU compile helper before this path existed).

    - training:        cache=None, decode=False
    - prefill:         cache=init_cache(...), decode=False
    - chunked prefill: cache w/ lengths=offset, decode=False,
                       prefill_offset=offset — writes this chunk's KV at
                       [offset, offset+S) and attends over the cache, so a
                       long prompt (or a suffix after a reused prefix) feeds
                       in S-token chunks with O(S x 512) attention memory
                       (a column block of scores at a time)
    - decode step:     cache=<filled>, decode=True, S must be 1

    ``token_mask`` marks the rows that belong to somebody (None: all of them).
    The others cost as little as the program can make them: the dropless
    experts skip them, and a decode step attends them at length 0, which is
    zero and reads no cache block (``ops.attention.decode_attention``). Such a
    row's position and the lengths the cache carries are what they are without
    a mask. Its column is not written where the write is the kernel's
    (``write_columns`` walks the live rows), and lands in its next free slot
    where it is XLA's: either way the bytes a finished row left below its
    length stay as they are, and what lies at or past it is nobody's to read.

    In every cached regime the returned cache IS the argument's buffers,
    updated in place: the leaves ride one layer scan as its carry (both
    ``first_k_dense`` stacks scan the same buffers, at layer offsets 0 and
    kd). A caller that donates the cache, or carries it through its own scan
    of decode steps, gets one buffer end to end and no cache-sized
    temporary. The writes are ``ops.attention.write_cache``'s: per-row
    ``dynamic_update_slice``, or for decode beside the flash-decode kernel
    one aliased ``write_columns`` kernel. A scatter over the batch axis must
    not replace them (XLA copies the whole cache inside the layer loop for
    it).
    """
    batch, seq = tokens.shape
    if config.layer_types is not None:
        return _forward_kinds(
            params, tokens, config, positions, cache, decode, attn_impl, return_aux,
            prefill_offset, last_positions, adapters, token_mask,
            {"moe": return_moe_stats, "steps": return_attn_steps, "writes": return_column_writes},
        )
    if config.block == "sambay":
        return _forward_sambay(
            params, tokens, config, cache, decode, attn_impl, return_aux,
            prefill_offset, mesh, last_positions, adapters, token_mask, return_moe_stats,
            return_attn_steps, return_column_writes,
        )
    if config.mla:
        from prime_tpu.models.mla import validate_mla_config

        # loud rejection of per-head attention features the absorbed latent
        # form can't express (window/softcap/sinks/qk_norm/bias/...)
        validate_mla_config(config)
    if attn_impl == "ring":
        # context parallelism is a TRAINING-path mode: the KV cache's slot
        # axis is not ring-sharded (long-context decode is long_context.py's
        # sp path), and per-layer sliding schedules would need a per-layer
        # static hop cap the uniform scan can't express
        if config.mla:
            raise ValueError(
                "attn_impl='ring' does not serve MLA configs yet (the ring "
                "fold rotates per-head K/V, not the shared latent)"
            )
        if cache is not None:
            raise ValueError("attn_impl='ring' serves the no-cache (training) path only")
        if mesh is None or "sp" not in mesh.shape:
            raise ValueError("attn_impl='ring' needs mesh with an 'sp' axis")
        if config.sliding_window and config.sliding_pattern != "uniform":
            raise ValueError(
                "attn_impl='ring' supports uniform window schedules only "
                f"(got pattern {config.sliding_pattern!r}); per-layer "
                "schedules need a per-layer static hop cap"
            )
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(seq, dtype=jnp.int32)[None, :], (batch, seq))
        if prefill_offset is not None:
            off = prefill_offset.astype(jnp.int32)
            positions = positions + (off[:, None] if off.ndim else off)
    max_pos = cache.capacity if cache is not None else max(seq, config.max_seq_len)
    rope_tables = rope_frequencies(
        # MLA ropes only the shared qk_rope sub-head; the nope part and the
        # latent are position-free
        config.qk_rope_head_dim if config.mla else config.head_dim,
        max_pos, config.rope_theta,
        scale=config.rope_scale, llama3=config.rope_llama3, yarn=config.rope_yarn,
        yarn_truncate=config.rope_yarn_truncate, longrope=config.rope_longrope,
        # LongRoPE short/long selection follows the run's actual position
        # bound (static at trace time): callers that know their true bound
        # (sampler: prompt+max_new) pass it; otherwise cache runs can reach
        # capacity and no-cache runs only touch seq positions. One run keeps
        # ONE factor set — HF's mid-generation dynamic switch re-ropes new
        # queries against keys cached under the other set, which this stack
        # deliberately avoids. Serving guidance: size a continuous engine's
        # capacity <= the pretrained range when short-context behavior must
        # match HF's short factors.
        longrope_select=(
            longrope_select
            if longrope_select is not None
            else (cache.capacity if cache is not None else seq)
        ),
        partial=config.partial_rotary,
    )
    # Gemma3: local (sliding) layers use an unscaled short-range frequency
    rope_tables_local = (
        rope_frequencies(config.head_dim, max_pos, config.rope_local_theta)
        if config.rope_local_theta is not None
        else None
    )

    x = params["embed"][tokens]
    if config.scale_embed:  # Gemma normalizes hidden states by sqrt(d_model)
        x = x * jnp.asarray(config.d_model**0.5, dtype=x.dtype)

    cache_lengths = cache.lengths if cache is not None else None
    nobody = {}
    attn_steps = column_writes = jnp.zeros((2,), jnp.int32)
    if decode:
        from prime_tpu.ops.attention import column_write_work, decode_work

        # the rows that belong to somebody (None: all), listed once, here, for
        # every layer's column write
        live = None if token_mask is None else token_mask[:, 0]
        nobody["column_work"], column_writes = column_write_work(attn_impl, cache.k, live)
        column_writes = column_writes * cache.k.shape[0]  # a call a cache row
        if live is not None:
            # the slots a row attends over: the cache and this step's column,
            # and none for a row that belongs to nobody; with them the kernel's
            # list of the grid steps those lengths need. Once, here, for every
            # layer (a config whose layers alternate a window leaves the list to
            # the kernel, which makes a layer's from its own window)
            attend_lengths = jnp.where(live, cache_lengths + 1, 0)
            nobody["attend_lengths"] = attend_lengths
            if not config.sliding_window:
                nobody["work"], attn_steps = decode_work(
                    attn_impl, config.n_heads, cache.k, cache.quantized, attend_lengths, mesh
                )
                attn_steps = attn_steps * cache.k.shape[0]  # a call a cache row
    # Per-layer sliding flag rides the scan so one compiled body serves both
    # kinds. The pattern is an explicit config field (ModelConfig.sliding_pattern)
    # so non-Gemma2 window schemes can't silently inherit the even alternation.
    sliding_flags = sliding_layer_flags(config)
    state_work = state_steps = None

    def attend(x, lp, layer, kv, sliding=None):
        if config.mla:
            from prime_tpu.models.mla import mla_attention_block

            return mla_attention_block(
                x, lp, positions, rope_tables, config,
                kv, layer, cache_lengths, decode, attn_impl,
                prefill_offset=prefill_offset, **nobody,
            )
        return _attention_block(
            x, lp, positions, rope_tables, config,
            kv, layer, cache_lengths, decode, attn_impl,
            prefill_offset=prefill_offset, **nobody,
            sliding=sliding, rope_tables_local=rope_tables_local,
            mesh=mesh, adapter_ids=adapter_ids,
        )

    # What a scanned layer computes is the configuration's block kind, chosen
    # here, once, outside the scan. The dropless expert layer's counts ride
    # the carry only where one runs (None otherwise: a dense model's program
    # carries nothing for them).
    if config.block == "shortcut_moe":
        if not config.mla:
            raise ValueError("the shortcut_moe block's attentions are MLA (kv_lora_rank)")
        dropless = True

        def layer_fn(carry, scanned):
            x, aux_sum, kv, stats = carry
            layer = scanned[0]  # the block indexes the stack itself (see _shortcut_block)
            x, kv, layer_stats = _shortcut_block(
                x, params["layers"], layer, kv, attend, config, token_mask
            )
            return (x, aux_sum, kv, stats + layer_stats), None

    elif config.block == "gated_delta":
        _refuse_for_delta(mesh, adapters, prefill_offset)
        dropless = config.is_moe  # a dense feed-forward counts nothing
        if decode and cache is not None:
            from prime_tpu.ops.attention import delta_decode_work

            # the live rows, listed once for every linear layer's state kernel
            # (None where the step is plain XLA)
            live = jnp.ones((batch,), bool) if token_mask is None else token_mask[:, 0]
            state_work, state_steps = delta_decode_work(
                attn_impl, cache.k, cache.delta_state, live
            )

        def layer_fn(carry, scanned):
            x, aux_sum, kv, stats = carry
            x, kv, period_stats = _delta_period(
                x, params["layers"], scanned[0], kv, attend, config, token_mask, state_work
            )
            return (x, aux_sum, kv, stats if stats is None else stats + period_stats), None

    elif config.block == "standard":
        dropless = cache is not None and runs_dropless_experts(params, config)

        def layer_fn(carry, scanned):
            x, aux_sum, kv, stats = carry
            layer, lp, sliding = scanned
            x, kv = attend(x, lp, layer, kv, sliding)
            x, aux, layer_stats = _mlp_block(
                x, lp, config, adapter_ids=adapter_ids, dropless=dropless,
                token_mask=token_mask,
            )
            if layer_stats is not None:
                stats = stats + layer_stats
            return (x, aux_sum + aux, kv, stats), None

    else:
        raise ValueError(
            f"Unknown block {config.block!r} "
            "(want 'standard' | 'shortcut_moe' | 'sambay' | 'gated_delta')"
        )

    if remat not in ("none", "full", "dots"):
        raise ValueError(f"Unknown remat {remat!r} (want 'none' | 'full' | 'dots')")
    if remat != "none" and cache is None:
        # WITHOUT this, reverse-mode AD through the scan saves every
        # layer's residuals (activation memory = n_layers × per-layer);
        # checkpointing recomputes them in the backward pass. "dots"
        # keeps matmul outputs (cheap HBM, expensive to recompute on the
        # MXU) and drops the elementwise rest — the usual TPU trade.
        policy = (
            None
            if remat == "full"
            else jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        )
        layer_fn = jax.checkpoint(layer_fn, policy=policy, prevent_cse=False)

    # DeepSeek first_k_dense: the dense-prefix stack scans first, then the
    # MoE stack — same layer_fn (the MLP branch keys off each stack's own
    # params), over the same carried cache at layer offsets 0 and kd.
    kd = config.first_k_dense
    stacks = (
        [(params["dense_layers"], slice(0, kd)), (params["layers"], slice(kd, None))]
        if kd
        # (a block that indexes the stack itself scans no layer params)
        else [({} if config.block in ("shortcut_moe", "gated_delta") else params["layers"], slice(0, None))]
    )
    # The stacked cache leaves are the scan's CARRY, never its xs/ys: a scan
    # cannot alias xs with ys, so a cache scanned per layer is sliced out,
    # written into a second buffer and copied back, three passes over the
    # whole cache every step. As a carry that each block updates in place it
    # is one buffer from the caller's (donated) argument to the result.
    carried = ()
    if cache is not None:
        carried = ("k", "v") + (("k_scale", "v_scale") if cache.quantized else ())
        if config.block == "gated_delta":
            carried += ("conv_tail", "delta_state")
    kv = tuple(getattr(cache, name) for name in carried)
    from prime_tpu.ops.moe import MOE_STATS

    # (zeros where the capacity einsum ran and the caller asked for counts)
    counted = dropless or return_moe_stats
    moe_stats = jnp.zeros((len(MOE_STATS),), jnp.int32) if counted else None
    carry = (x, jnp.zeros((), jnp.float32), kv, moe_stats)
    # the scan's unit: a layer, or the gated_delta block's period
    units = config.cache_rows if config.block == "gated_delta" else config.n_layers
    layer_ids = jnp.arange(units, dtype=jnp.int32)
    sliding_flags = sliding_flags[:units]
    for stack, rows in stacks:
        # multi-LoRA bank: the per-target (L, A, ...) factor stacks ride the
        # layer scan under reserved lora:* keys, sliced by each stack's rows —
        # _lora_mm gathers each batch row's factors by adapter_ids inside the
        # scanned body (serve/adapters.py; no bank → byte-identical programs)
        stack = merge_adapter_stacks(stack, adapters, rows)
        carry, _ = jax.lax.scan(
            layer_fn, carry, (layer_ids[rows], stack, sliding_flags[rows])
        )
    x, aux_total, kv, moe_stats = carry
    new_cache = None
    if cache is not None:
        new_cache = cache._replace(
            lengths=cache.lengths + (1 if decode else seq), **dict(zip(carried, kv))
        )

    x = _norm(x, params["final_norm"], config)
    if last_positions is not None:
        # gather BEFORE the head matmul (see docstring)
        x = jnp.take_along_axis(
            x, last_positions.astype(jnp.int32)[:, None, None], axis=1
        )
    head = params["embed"].T if config.tie_embeddings else params["lm_head"]
    logits = _apply_softcap((x @ head).astype(jnp.float32), config.final_softcap)
    out = (logits, new_cache) + ((aux_total,) if return_aux else ())
    out += (moe_stats,) if return_moe_stats else ()
    out += (attn_steps,) if return_attn_steps else ()
    out += (column_writes,) if return_column_writes else ()
    if return_state_steps:
        out += (jnp.zeros((2,), jnp.int32) if state_steps is None else state_steps,)
    return out


def _forward_kinds(
    params, tokens, config, positions, cache, decode, attn_impl, return_aux,
    prefill_offset, last_positions, adapters, token_mask, wanted,
):
    """``forward`` for the standard block under ``config.layer_types``
    (models/kinds.py walks the layers): the same regimes and results. ``wanted``
    says which of the decode step's counts the caller asked for, in the order
    ``forward`` returns them."""
    from prime_tpu.models.kinds import kinds_layers

    seq = tokens.shape[1]
    x, leaves, counts = kinds_layers(
        params, params["embed"][tokens], config, positions, cache, decode, attn_impl,
        prefill_offset, token_mask, adapters,
    )
    new_cache = None
    if cache is not None:
        new_cache = cache._replace(
            lengths=cache.lengths + (1 if decode else seq),
            **dict(zip(("k", "v", "ring_k", "ring_v"), leaves)),
        )
    x = _norm(x, params["final_norm"], config)
    if last_positions is not None:
        x = jnp.take_along_axis(x, last_positions.astype(jnp.int32)[:, None, None], axis=1)
    logits = (x @ params["lm_head"]).astype(jnp.float32)
    out = (logits, new_cache) + ((jnp.zeros((), jnp.float32),) if return_aux else ())
    return out + tuple(counts[name] for name, asked in wanted.items() if asked)


def _forward_sambay(
    params, tokens, config, cache, decode, attn_impl, return_aux, prefill_offset,
    mesh, last_positions, adapters, token_mask, return_moe_stats, return_attn_steps,
    return_column_writes,
):
    """``forward`` for the sambay block kind (models/sambay.py): the same
    regimes and results. There are no positions to encode, so ``positions``
    is not looked at. ``token_mask`` marks, at decode, the rows that belong to
    somebody (the others attend over nothing; their recurrent state runs on
    and reaches no one, since an admission copies a slot's state whole), and
    in a prompt's chunk the real tokens (padding past a row's last token
    leaves ring, state and conv tail as they were)."""
    from prime_tpu.models.sambay import sambay_layers
    from prime_tpu.ops.norms import layer_norm

    if mesh is not None and getattr(mesh, "size", 1) > 1:
        raise ValueError(
            "the sambay block is not laid out for a mesh: its ring and recurrent "
            "state have no sharding rule yet"
        )
    if adapters is not None:
        raise ValueError("the sambay block serves no LoRA bank")
    seq = tokens.shape[1]
    x, leaves, attn_steps, column_writes = sambay_layers(
        params, params["embed"][tokens], config, cache, decode, attn_impl,
        prefill_offset, token_mask, last_positions,
    )
    new_cache = None
    if cache is not None:
        new_cache = KVCache(
            leaves[0], leaves[1], cache.lengths + (1 if decode else seq),
            ring_k=leaves[2], ring_v=leaves[3], ssm_state=leaves[4], conv_tail=leaves[5],
        )
    x = layer_norm(x, params["final_norm"], params["final_norm_bias"], config.rms_eps)
    logits = (x @ params["embed"].T).astype(jnp.float32)
    out = (logits, new_cache) + ((jnp.zeros((), jnp.float32),) if return_aux else ())
    if return_moe_stats:
        from prime_tpu.ops.moe import MOE_STATS

        out += (jnp.zeros((len(MOE_STATS),), jnp.int32),)
    out += (attn_steps,) if return_attn_steps else ()
    return out + ((column_writes,) if return_column_writes else ())
