"""Ring attention: causal self-attention with the sequence sharded over ICI.

Long-context first-class citizen: sequences larger than one chip's HBM are
sharded along an ``sp`` mesh axis. Each device holds a local (B, H, S/P, D)
block of q/k/v; KV blocks rotate around the ring via ``lax.ppermute`` while
every device folds each visiting block into an online-softmax accumulator
(the same math as the pallas flash kernel, lifted to the inter-chip level).
P-1 rotations fully overlap compute with ICI transfers under XLA's async
collective scheduling.

Causality across the ring: device i owns global positions
[i*S_local, (i+1)*S_local). A visiting KV block from source device j is
- fully visible if j < i,
- causally masked within the block if j == i,
- fully masked if j > i (the where-mask zeroes it; its transfer cost is the
  price of the symmetric schedule).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


NEG_INF = -1e30


def _ring_attention_local(
    q: jnp.ndarray,  # (B, H, S_local, D) — this device's block
    k: jnp.ndarray,  # (B, KH, S_local, D)
    v: jnp.ndarray,
    sinks: jnp.ndarray,  # (H,) per-head sink logits (zeros when unused)
    axis_name: str,
    sm_scale: float,
    window: int = 0,
    hops: int | None = None,  # ring rotations (host-static; None = P-1)
    softcap: float = 0.0,
    use_sinks: bool = False,
):
    axis_size = jax.lax.psum(1, axis_name)
    my_index = jax.lax.axis_index(axis_name)
    batch, heads, s_local, head_dim = q.shape
    kv_heads = k.shape[1]
    group = heads // kv_heads

    # GQA: keep k/v at (B, KH, S_local, D) through the ring — each ppermute
    # then moves 1/group of the repeated-layout bytes over ICI — and fold with
    # q grouped as (B, KH, G, S_local, D) so the einsum broadcasts over G.
    q32 = (q.astype(jnp.float32) * sm_scale).reshape(batch, kv_heads, group, s_local, head_dim)
    q_pos = my_index * s_local + jnp.arange(s_local)  # global positions of my queries

    m = jnp.full((batch, kv_heads, group, s_local, 1), NEG_INF, dtype=jnp.float32)
    l = jnp.zeros((batch, kv_heads, group, s_local, 1), dtype=jnp.float32)
    acc = jnp.zeros((batch, kv_heads, group, s_local, head_dim), dtype=jnp.float32)

    def fold(carry, kv_block, source_index):
        m_prev, l_prev, acc_prev = carry
        k_blk, v_blk = kv_block
        scores = jnp.einsum(
            "bhgqd,bhkd->bhgqk", q32, k_blk.astype(jnp.float32), preferred_element_type=jnp.float32
        )
        # the canonical softcap (cap-before-mask invariant lives there)
        from prime_tpu.ops.attention import _apply_softcap

        scores = _apply_softcap(scores, softcap)
        kv_pos = source_index * s_local + jnp.arange(s_local)
        visible = kv_pos[None, :] <= q_pos[:, None]  # (S_local, S_local) global causal mask
        if window:
            # sliding layer: the key must also be within `window` of the
            # query (delta < window, ops.attention._window_ok semantics)
            visible &= q_pos[:, None] - kv_pos[None, :] < window
        scores = jnp.where(visible[None, None, None], scores, NEG_INF)

        m_new = jnp.maximum(m_prev, jnp.max(scores, axis=-1, keepdims=True))
        p = jnp.exp(scores - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = acc_prev * alpha + jnp.einsum(
            "bhgqk,bhkd->bhgqd", p, v_blk.astype(jnp.float32), preferred_element_type=jnp.float32
        )
        return m_new, l_new, acc_new

    # step 0: my own block; then rotate kv around the ring `hops` times
    # (host-static — the loop lowers to a fixed-length scan, not a dynamic
    # while; ring_hops computes the sliding-layer cap)
    carry = fold((m, l, acc), (k, v), my_index)
    perm = [(s, (s + 1) % axis_size) for s in range(axis_size)]

    def ring_step(step, state):
        carry, (k_cur, v_cur) = state
        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        # after `step` rotations, I hold the block originally on device my_index - step
        source = (my_index - step + axis_size) % axis_size
        carry = fold(carry, (k_nxt, v_nxt), source)
        return carry, (k_nxt, v_nxt)

    last = (1 + hops) if hops is not None else axis_size
    (m, l, acc), _ = jax.lax.fori_loop(
        1, last, lambda s, st: ring_step(s, st), (carry, (k, v))
    )
    if use_sinks:
        # GPT-OSS attention sinks: one denominator adjustment after all
        # folds (the sink joins every query's normalization, no value) —
        # same algebra as ops.pallas_attention._finalize_attention
        sink = sinks.astype(jnp.float32).reshape(1, kv_heads, group, 1, 1)
        m_final = jnp.maximum(m, sink)
        rescale = jnp.exp(m - m_final)
        denom = l * rescale + jnp.exp(sink - m_final)
        out = (acc * rescale / jnp.maximum(denom, 1e-30))
    else:
        out = acc / jnp.maximum(l, 1e-30)
    return out.reshape(batch, heads, s_local, head_dim).astype(q.dtype)


def ring_self_attention(
    q: jnp.ndarray,  # (B, H, S, D) with S sharded on `seq_axis`
    k: jnp.ndarray,  # (B, KH, S, D)
    v: jnp.ndarray,
    mesh,
    seq_axis: str = "sp",
    sm_scale: float | None = None,
    window: int = 0,
    softcap: float = 0.0,
    sinks: jnp.ndarray | None = None,  # (H,) per-head sink logits
    batch_axis=None,  # mesh axis (or tuple) sharding the batch dim
    head_axis=None,   # mesh axis sharding the head dims (megatron tp)
) -> jnp.ndarray:
    """Causal ring attention over a mesh sequence axis (full-array API).

    ``window`` > 0 makes it a sliding layer: the causal mask adds the
    window band AND the ring stops after ``ring_hops(...)`` rotations —
    the KV blocks beyond the band are never transferred, so a
    Gemma/Mistral-style windowed layer costs O(window) ICI traffic per
    device instead of a full rotation. ``softcap``/``sinks`` carry the
    Gemma2/GPT-OSS score math. ``batch_axis``/``head_axis`` let the batch
    ride data axes and the heads ride tp (per-head math shards cleanly),
    so context parallelism composes with dp/fsdp/tp instead of silently
    replicating over them."""
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    shards = mesh.shape[seq_axis]
    hops = ring_hops(window, q.shape[2] // shards, shards)
    use_sinks = sinks is not None
    sinks_in = (
        sinks.astype(jnp.float32) if use_sinks else jnp.zeros((q.shape[1],), jnp.float32)
    )
    spec = P(batch_axis, head_axis, seq_axis, None)
    fn = jax.shard_map(
        functools.partial(
            _ring_attention_local, axis_name=seq_axis, sm_scale=sm_scale,
            window=window, hops=hops, softcap=softcap, use_sinks=use_sinks,
        ),
        mesh=mesh,
        in_specs=(spec, spec, spec, P(head_axis)),
        out_specs=spec,
    )
    return fn(q, k, v, sinks_in)


def ring_hops(window: int, s_local: int, axis_size: int) -> int:
    """Ring rotations a layer needs. Global layers make the full P-1; a
    sliding layer's earliest query (global i*S_local) sees back to
    q - window + 1, exactly ceil((window-1)/S_local) hops upstream — every
    earlier block is fully masked and never transferred (a window within
    one shard span costs exactly one hop)."""
    if not window:
        return axis_size - 1
    return min(axis_size - 1, -(-(window - 1) // s_local))
