"""The standard block over layers of more than one KIND (Laguna): full
attention on K/V rows beside window attention on rings, at different head
counts and rotary settings, a dense feed-forward beside expert layers.

``config.layer_types`` and ``config.mlp_layer_types`` are the published lists,
one entry a layer. The kinds differ in shape, so each has a parameter stack of
its own and a layer is (its kind's stack, its index there):

    params["layers"]["attn_norm" | "mlp_norm"]   (L, d)        every layer
    params["layers"]["full"]     wq wk wv wo w_head_gate       (full layers, ...)
    params["layers"]["window"]   the same at window_heads      (sliding layers, ...)
    params["layers"]["dense"]    w_gate w_up w_down            (dense layers, ...)
    params["layers"]["sparse"]   router, the held experts,     (sparse layers, ...)
                                 the shared expert

A layer is ``models/llama.py``'s ``_attention_block`` and ``_mlp_block``, told
its head count, window, rotary table and (for a window layer under a cache) how
to attend its ring. The walk scans each run of the pattern
(``config.kind_segments``) with the run's unit unrolled in the body, the layer's
matrices indexed out of the kind stacks there, one slice a matmul, and the
experts handed to the grouped matmul whole with their layer (as
``_delta_period`` does, and for its reason).

**The cache.** ``k`` / ``v`` hold the full layers' rows only
(``config.cache_rows``); a sliding layer keeps ``sliding_window`` columns a slot
in ``ring_k`` / ``ring_v``, written at ``position mod window``. Keys are stored
rotated, so which column a key sits in does not matter to a softmax: at decode
the ring is a plain cache of length ``min(length + 1, window)``; a prompt's
chunk attends the ring as it stood beside its own columns, by position
(``ops.attention.attention_at_positions``), then writes its newest columns over
it (``write_ring``), where padding past a row's last token evicts nothing. One
work list a kind and decode step, made here, once, for every layer of the kind.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from prime_tpu.models.config import ModelConfig
from prime_tpu.ops.attention import (
    _decode_uses_kernel,
    attention_at_positions,
    column_write_work,
    decode_attention,
    decode_work,
    ring_positions,
    write_cache,
    write_ring,
)
from prime_tpu.ops.rope import rope_frequencies

Params = dict[str, Any]

# the random recipe: which of the 24 keys split from the seed's key draws which
# leaf (benchmark/reference/laguna_s.py restates the table)
KEY_OF = {
    "embed": 0, "lm_head": 1,
    "full.wq": 2, "full.wk": 3, "full.wv": 4, "full.wo": 5, "full.w_head_gate": 6,
    "window.wq": 7, "window.wk": 8, "window.wv": 9, "window.wo": 10, "window.w_head_gate": 11,
    "dense.w_gate": 12, "dense.w_up": 13, "dense.w_down": 14,
    "sparse.router": 15, "sparse.w_gate": 16, "sparse.w_up": 17, "sparse.w_down": 18,
    "sparse.w_shared_gate": 19, "sparse.w_shared_up": 20, "sparse.w_shared_down": 21,
}
# a layer's entry in the config's lists -> its kind's stack
STACK_OF = {"full_attention": "full", "sliding_attention": "window", "dense": "dense", "sparse": "sparse"}
_EXPERTS = ("w_gate", "w_up", "w_down")


def init_params(rng: jax.Array, config: ModelConfig, dtype=jnp.bfloat16) -> Params:
    """Random init under ``config.layer_types``. A matrix is ``normal *
    fan_in**-0.5`` rounded to ``dtype`` (the router float32), a norm's weight
    one; one jitted draw a leaf, stacked over the layers of its kind."""
    keys = jax.random.split(rng, 24)
    d, hd, kh = config.d_model, config.head_dim, config.n_kv_heads
    ff, dff, shared = config.d_ff, config.dense_ff or config.d_ff, config.shared_ff
    count = {
        "full": config.full_layers, "window": config.window_layers,
        "dense": config.mlp_layer_types.count("dense"),
        "sparse": config.mlp_layer_types.count("sparse"),
    }

    def draw(name, shape, fan_in, out=dtype):
        make = jax.jit(
            lambda k: (jax.random.normal(k, shape, dtype=jnp.float32) * (fan_in**-0.5)).astype(out)
        )
        return make(keys[KEY_OF[name]])

    def attention(kind, h):
        n = count[kind]
        stack = {
            "wq": draw(f"{kind}.wq", (n, d, h * hd), d),
            "wk": draw(f"{kind}.wk", (n, d, kh * hd), d),
            "wv": draw(f"{kind}.wv", (n, d, kh * hd), d),
            "wo": draw(f"{kind}.wo", (n, h * hd, d), h * hd),
        }
        if config.attn_head_gate:
            stack["w_head_gate"] = draw(f"{kind}.w_head_gate", (n, d, h), d)
        return stack

    n, held = count["sparse"], config.experts_held
    sparse = {
        "router": draw("sparse.router", (n, d, config.n_experts), d, out=jnp.float32),
        "w_gate": draw("sparse.w_gate", (n, held, d, ff), d),
        "w_up": draw("sparse.w_up", (n, held, d, ff), d),
        "w_down": draw("sparse.w_down", (n, held, ff, d), ff),
    }
    if shared:
        sparse |= {
            "w_shared_gate": draw("sparse.w_shared_gate", (n, d, shared), d),
            "w_shared_up": draw("sparse.w_shared_up", (n, d, shared), d),
            "w_shared_down": draw("sparse.w_shared_down", (n, shared, d), shared),
        }
    return {
        "embed": draw("embed", (config.vocab_size, d), d),
        "layers": {
            "attn_norm": jnp.ones((config.n_layers, d), dtype),
            "mlp_norm": jnp.ones((config.n_layers, d), dtype),
            "full": attention("full", config.n_heads),
            "window": attention("window", config.window_heads or config.n_heads),
            "dense": {
                "w_gate": draw("dense.w_gate", (count["dense"], d, dff), d),
                "w_up": draw("dense.w_up", (count["dense"], d, dff), d),
                "w_down": draw("dense.w_down", (count["dense"], dff, d), dff),
            },
            "sparse": sparse,
        },
        "final_norm": jnp.ones((d,), dtype),
        "lm_head": draw("lm_head", (d, config.vocab_size), d),
    }


def _refuse(config, adapters, prefill_offset, attn_impl) -> None:
    """What a layer_types model does not serve (ROADMAP.md R7 queues each; int8
    and int4 weights and an int8 cache are refused where they would be made,
    models/quantize.py and ``init_cache``, a mesh where it would be served, the
    engine's constructor)."""
    what = None
    if attn_impl in ("ring", "sharded"):
        what = f"attention over a mesh (attn_impl {attn_impl!r}: its rings have no sharding rule)"
    elif adapters is not None:
        what = "a LoRA bank"
    elif prefill_offset is not None and jnp.ndim(prefill_offset):
        what = "a per-row chunk offset (speculative verify would have to rewind the rings)"
    if what:
        raise ValueError(f"a layer_types model ({config.name}) does not serve {what}: ROADMAP.md R7")


def kinds_layers(
    params: Params,
    x: jnp.ndarray,                   # (B, S, d) the embedded tokens
    config: ModelConfig,
    positions: jnp.ndarray | None,    # (B, S); None: arange (+ the chunk's offset)
    cache,                            # models.llama.KVCache or None
    decode: bool,
    attn_impl: str,
    prefill_offset: jnp.ndarray | None,
    token_mask: jnp.ndarray | None,   # (B, S): decode, rows that belong to nobody
    #                                   are False; a prompt's chunk, the padding
    #                                   past a row's last token is
    adapters=None,
):
    """Returns (x, cache leaves, counts). The leaves are ``(k, v, ring_k,
    ring_v)`` updated in place, or () without a cache. ``counts`` holds
    ``forward``'s optional results by name: "moe" (the expert layers' summed
    ``MOE_STATS``), "steps" and "writes" (a decode step's flash-decode grid
    steps and column-write rows, made and at most, both kinds summed)."""
    from prime_tpu.models.llama import _attention_block, _mlp_block
    from prime_tpu.ops.moe import MOE_STATS

    _refuse(config, adapters, prefill_offset, attn_impl)
    batch, seq, _ = x.shape
    window, hd = config.sliding_window, config.head_dim
    window_heads = config.window_heads or config.n_heads
    n_full, n_window = config.full_layers, config.window_layers
    cached = cache is not None
    if decode and (not cached or seq != 1):
        raise ValueError("a decode step takes a cache and one token a row")
    offset = None
    if cached and not decode:
        # a prompt with a cache is a chunk at its offset (0 where none was given)
        offset = jnp.zeros((), jnp.int32) if prefill_offset is None else prefill_offset.astype(jnp.int32)
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(seq, dtype=jnp.int32)[None, :], (batch, seq))
        if offset is not None:
            positions = positions + offset

    # the two rotary settings, tables as long as the slots (or the sequence)
    max_pos = cache.capacity if cached else seq
    rope = {
        "full": rope_frequencies(
            hd, max_pos, config.rope_theta, yarn=config.rope_yarn,
            yarn_truncate=config.rope_yarn_truncate, partial=config.partial_rotary,
        ),
        "window": rope_frequencies(hd, max_pos, config.rope_local_theta or config.rope_theta),
    }

    zeros2 = jnp.zeros((2,), jnp.int32)
    counts = {"moe": jnp.zeros((len(MOE_STATS),), jnp.int32), "steps": zeros2, "writes": zeros2}
    lengths = cache.lengths if cached else None
    step = {}  # a decode step's lists, made once for every layer of a kind
    if decode:
        live = None if token_mask is None else token_mask[:, 0]
        attend = lengths + 1 if live is None else jnp.where(live, lengths + 1, 0)
        ring_attend_lengths = jnp.minimum(attend, window)
        # the ring takes the path the rows take (one gate: a wide batch's per-row
        # dynamic_update_slice writes would be a launch a row a layer)
        ring_impl = "pallas" if _decode_uses_kernel(attn_impl, cache.k) else "xla"
        work, steps = decode_work(attn_impl, config.n_heads, cache.k, False, attend)
        ring_work, ring_steps = decode_work(
            ring_impl, window_heads, cache.ring_k, False, ring_attend_lengths
        )
        column_work, writes = column_write_work(attn_impl, cache.k, live)
        step = dict(attend_lengths=attend, work=work, column_work=column_work)
        counts["steps"] = steps * n_full + ring_steps * n_window
        counts["writes"] = writes * config.n_layers
    chunk_counts = None  # a chunk's real tokens a row: padding evicts no ring column
    if offset is not None:
        chunk_counts = (
            jnp.full((batch,), seq, jnp.int32) if token_mask is None
            else jnp.sum(token_mask.astype(jnp.int32), axis=1)
        )

    def over_ring(row):
        """How window layer ``row`` attends under a cache (``_attention_block``'s
        ``ring_attend``): queries (B, H, S, hd) rotated, the layer's staged K/V."""
        sm_scale = (config.query_scale or hd) ** -0.5

        def attend_ring(q, blocks, ring):
            if decode:
                ring = write_cache(
                    ring, blocks, row, lengths % window, impl=ring_impl, work=column_work
                )
                attn = decode_attention(
                    q, ring[0], ring[1], ring_attend_lengths, sm_scale, impl=ring_impl,
                    layer=row, work=ring_work,
                )
                return attn, ring
            # the ring as it stood, then the chunk's own columns, by position
            old = tuple(jax.lax.dynamic_index_in_dim(leaf, row, 0, keepdims=False) for leaf in ring)
            own = offset + jnp.arange(seq, dtype=jnp.int32)
            k_pos = jnp.concatenate([ring_positions(offset, window), own])[None, :]
            attn = attention_at_positions(
                q, *(jnp.concatenate([o, b.astype(o.dtype)], axis=-1) for o, b in zip(old, blocks)),
                own[None, :], k_pos, sm_scale, window=window,
            )
            return attn, write_ring(ring, blocks, row, offset, chunk_counts)

        return attend_ring

    stacks = params["layers"]

    def one_layer(x, leaves, moe_stats, layer, kinds, rows):
        """Layer ``layer`` (static or traced), of ``kinds`` (attention, feed-
        forward), at ``rows`` of its two kind stacks."""
        attn_kind, mlp_kind = (STACK_OF[k] for k in kinds)
        at, mlp_at = rows
        lp = {name: w[at] for name, w in stacks[attn_kind].items()}
        lp["attn_norm"] = stacks["attn_norm"][layer]
        rows_kv, ring = leaves[:2], leaves[2:]
        if attn_kind == "full":
            with jax.named_scope("full_attention"):
                x, rows_kv = _attention_block(
                    x, lp, positions, rope["full"], config, rows_kv, at, lengths, decode,
                    attn_impl, prefill_offset=offset, heads=config.n_heads, window=0, **step,
                )
        else:
            with jax.named_scope("window_attention"):
                x, ring = _attention_block(
                    x, lp, positions, rope["window"], config, ring, at, lengths, decode,
                    attn_impl, heads=window_heads, window=window, ring_attend=over_ring(at),
                )
        feed = {"mlp_norm": stacks["mlp_norm"][layer]}
        if mlp_kind == "dense":
            feed |= {name: w[mlp_at] for name, w in stacks["dense"].items()}
            x, _, _ = _mlp_block(x, feed, config)
        else:
            sparse = stacks["sparse"]
            feed |= {name: w if name in _EXPERTS else w[mlp_at] for name, w in sparse.items()}
            x, _, layer_stats = _mlp_block(
                x, feed, config, dropless=True, layer=mlp_at,
                # a chunk's padding and a decode step's dead rows route nowhere
                token_mask=token_mask,
            )
            moe_stats = moe_stats + layer_stats
        return x, (*rows_kv, *ring), moe_stats

    pairs = list(zip(config.layer_types, config.mlp_layer_types))
    # a layer's row in each of its two kind stacks: the layers of that kind before it
    stack_rows = [
        tuple(sum(before[k] == kind for before in pairs[:i]) for k, kind in enumerate(pair))
        for i, pair in enumerate(pairs)
    ]
    leaves = (cache.k, cache.v, cache.ring_k, cache.ring_v) if cached else ()
    moe_stats = counts["moe"]
    for first, unit, units in config.kind_segments:
        body = range(first, first + unit)
        # rows of a kind's stack one unit of the run takes: what a later unit's layers lie past
        stride = [tuple(sum(pairs[i][k] == pairs[j][k] for i in body) for k in (0, 1)) for j in body]

        def run_unit(carry, u, body=body, stride=stride):
            x, leaves, moe_stats = carry
            for j, step_rows in zip(body, stride):
                rows = tuple(row + u * n for row, n in zip(stack_rows[j], step_rows))
                x, leaves, moe_stats = one_layer(
                    x, leaves, moe_stats, j + u * len(body), pairs[j], rows
                )
            return (x, leaves, moe_stats), None

        carry = (x, leaves, moe_stats)
        if units == 1:
            carry, _ = run_unit(carry, 0)
        else:
            carry, _ = jax.lax.scan(run_unit, carry, jnp.arange(units, dtype=jnp.int32))
        x, leaves, moe_stats = carry
    counts["moe"] = moe_stats
    return x, leaves, counts
