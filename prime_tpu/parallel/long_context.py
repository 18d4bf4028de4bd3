"""Long-context decode: KV cache sharded along its slot axis over ``sp``.

Ring attention (ring_attention.py) covers long-context PREFILL: the sequence
is sharded over sp and KV blocks rotate around the ring. This module covers
the matching DECODE step: once a cache is longer than one chip's HBM, its
slot axis lives sharded over sp, and each decode step runs flash-softmax
locally per shard followed by a two-phase combine — the online-softmax merge
lifted to the mesh level:

    global_max  = pmax(local_max)
    scale_i     = exp(local_max_i - global_max)
    out         = psum(scale_i * local_acc) / psum(scale_i * local_sum)

One pmax + two psums per step over ICI, independent of context length; the
HBM traffic (the decode bottleneck) stays perfectly sharded.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


NEG_INF = -1e30


def sp_decode_attention(
    q: jnp.ndarray,              # (B, H, 1, D) replicated over sp
    k_cache: jnp.ndarray,        # (B, KH, D, C) with C sharded over sp
    v_cache: jnp.ndarray,        # (B, KH, D, C)
    cache_lengths: jnp.ndarray,  # (B,) GLOBAL valid lengths
    mesh,
    sm_scale: float | None = None,
    k_scale: jnp.ndarray | None = None,  # (B, KH, 1, C) int8-cache dequant
    v_scale: jnp.ndarray | None = None,  # scales, sharded over sp with C
) -> jnp.ndarray:
    """One decode step against a sequence-sharded cache. Returns (B, H, 1, D).

    int8 caches shard cleanly: the per-slot dequant scales live with their
    slots on each shard and fold into the local score/value einsums exactly
    as in the single-device quantized path — the combine is unchanged."""
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    shards = mesh.shape["sp"]
    capacity = k_cache.shape[3]
    if capacity % shards:
        raise ValueError(f"cache capacity {capacity} must divide over sp={shards}")
    local_c = capacity // shards
    quantized = k_scale is not None
    if quantized != (v_scale is not None):
        raise ValueError("k_scale and v_scale go together")
    slot_spec = P(None, None, None, "sp")
    scale_in = (
        (k_scale, v_scale)
        if quantized
        # dummy replicated ones keep ONE shard_map signature; `quantized`
        # gates their use statically
        else (jnp.ones((1, 1, 1, 1), jnp.float32),) * 2
    )
    scale_spec = slot_spec if quantized else P()

    @functools.partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(P(), slot_spec, slot_spec, scale_spec, scale_spec, P()),
        out_specs=P(),
    )
    def step(q_full, k_local, v_local, ks_local, vs_local, lengths):
        batch, heads, _, head_dim = q_full.shape
        kv_heads = k_local.shape[1]
        group = heads // kv_heads
        shard_index = jax.lax.axis_index("sp")

        qg = (q_full.reshape(batch, kv_heads, group, head_dim).astype(jnp.float32)) * sm_scale
        scores = jnp.einsum(
            "bkgd,bkdc->bkgc", qg, k_local.astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )
        if quantized:
            scores = scores * ks_local  # (B, KH, 1, C_local) broadcasts over G
        # this shard owns global slots [shard_index*local_c, ...+local_c)
        slots = shard_index * local_c + jnp.arange(local_c)
        valid = slots[None, None, None, :] < lengths[:, None, None, None]
        scores = jnp.where(valid, scores, NEG_INF)

        local_max = jnp.max(scores, axis=-1, keepdims=True)          # (B,KH,G,1)
        p = jnp.exp(scores - local_max) * valid
        if quantized:
            p = p * vs_local
        local_sum = jnp.sum(jnp.exp(scores - local_max) * valid, axis=-1, keepdims=True)
        local_acc = jnp.einsum(
            "bkgc,bkdc->bkgd", p, v_local.astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )

        global_max = jax.lax.pmax(local_max, "sp")
        scale = jnp.exp(local_max - global_max)
        total_sum = jax.lax.psum(local_sum * scale, "sp")
        total_acc = jax.lax.psum(local_acc * scale, "sp")
        out = total_acc / jnp.maximum(total_sum, 1e-30)
        return out.reshape(batch, heads, 1, head_dim).astype(q_full.dtype)

    return step(q, k_cache, v_cache, *scale_in, cache_lengths)
