"""Multi-chip flash decode: the pallas kernel under shard_map.

A ``pallas_call`` is opaque to the SPMD partitioner, so under a multi-device
mesh the jitted serve path falls back to XLA decode (ops/attention.py). This
module provides the building block that removes that limitation: the decode
step wrapped in ``shard_map`` with the serving layout's specs — batch over
the data axes, kv-heads over tp — so each device runs the flash-decode
kernel on exactly its local cache shard and no communication is needed (the
head-dim psum happens later in the attention output projection, as usual for
megatron attention).

Constraint: the batch shard and kv-head shard must be non-empty on every
device (B divisible by dp*fsdp, KH divisible by tp) — the same divisibility
the serving path already enforces.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P



def flash_decode_sharded(
    q: jnp.ndarray,              # (B, H, 1, D)
    k_cache: jnp.ndarray,        # ([L,] B, KH, D, C) feature-major, stacked or
    v_cache: jnp.ndarray,        # one layer, as flash_decode takes them
    cache_lengths: jnp.ndarray,  # (B,)
    mesh,
    sm_scale: float | None = None,
    softcap: float = 0.0,
    window: int = 0,
    sliding: jnp.ndarray | None = None,
    sinks: jnp.ndarray | None = None,  # (H,) per-head sink logits (GPT-OSS)
    layer: jnp.ndarray | int = 0,      # () layer of a stacked cache, replicated
    work: tuple | None = None,         # ops.attention.decode_work's list, replicated
    interpret: bool = False,
) -> jnp.ndarray:
    """Per-shard pallas flash decode over a (dp, fsdp, tp[, ...]) mesh.

    The Gemma/GPT-OSS variants shard cleanly: softcap and the window are
    per-score/per-slot (no cross-shard state), and sinks split over tp with
    the heads they normalize."""
    from prime_tpu.ops.pallas_attention import flash_decode
    from prime_tpu.parallel import sharding

    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5

    # one source of truth for the serving layout: prune the canonical specs
    # down to the axes this mesh actually has
    q_spec = sharding.prune_spec(P(("dp", "fsdp"), "tp", None, None), mesh)
    kv_spec = q_spec if k_cache.ndim == 4 else P(None, *q_spec)
    lengths_spec = sharding.prune_spec(sharding.lengths_spec(), mesh)
    sinks_spec = sharding.prune_spec(P("tp"), mesh)
    if sinks is None:
        # dummy replicated zeros keep ONE shard_map signature; use_sinks
        # stays False inside flash_decode via the has_sinks closure below
        sinks_in = jnp.zeros((q.shape[1],), jnp.float32)
    else:
        sinks_in = sinks.astype(jnp.float32)
    has_sinks = sinks is not None
    # the kernel's work list where every device holds every row (the caller
    # made it once for all layers); else each device lists its own rows
    work = tuple(work or ())

    @functools.partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(q_spec, kv_spec, kv_spec, lengths_spec, sinks_spec, P(), *[P()] * len(work)),
        out_specs=q_spec,
        # pallas_call's out ShapeDtypeStruct carries no varying-axes metadata
        check_vma=False,
    )
    def local_decode(q_local, k_local, v_local, lengths_local, sinks_local, layer, *work):
        return flash_decode(
            q_local, k_local, v_local, lengths_local, sm_scale=sm_scale,
            softcap=softcap, window=window, sliding=sliding,
            sinks=sinks_local if has_sinks else None, layer=layer,
            work=work or None, interpret=interpret,
        )

    return local_decode(
        q, k_cache, v_cache, cache_lengths, sinks_in, jnp.asarray(layer, jnp.int32), *work
    )
