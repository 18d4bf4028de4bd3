"""Multi-head attention with GQA: XLA reference path + pallas dispatch.

Two execution regimes, one entry point:
- **prefill** (S > 1): causal self-attention over the whole prompt — the
  pallas flash kernel when running on TPU with aligned shapes, otherwise a
  fused XLA einsum path (also the ground truth the kernel is tested against);
- **decode** (S == 1): a single query attending to the KV cache — a pure
  einsum over the cache (bandwidth-bound; XLA handles it optimally).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def write_cache(leaves, blocks, layer, start, impl: str = "xla", work: tuple | None = None):
    """Write one layer's new ``blocks`` (B, KH, *, S) into the stacked cache
    ``leaves`` (L, B, KH, *, C) IN PLACE, at slots [start, start + S) of
    ``layer``. ``start`` () is one offset for every row (a prompt at 0, a
    prefill chunk at its offset): one ``dynamic_update_slice``. ``start``
    (B,) is per-row (decode's next free slot, the speculative verify
    window): one ``dynamic_update_slice`` a row, or, for decode's single
    column where ``impl`` sends the attention to the flash-decode kernel
    (the same gate, so TPU and a long cache), one ``write_columns`` kernel
    for all leaves and the rows of ``work``: the step's list of the rows that
    belong to somebody (``column_write_work``; None: every row). There a row
    that belongs to nobody keeps its bytes, column included; on the other
    paths its column lands at ``start`` like any row's, at or past its own
    length, where nothing attends. All are forms XLA updates in the buffer the
    layer scan carries. A scatter over the batch axis
    (``.at[layer, arange(B), :, :, start].set``, or ``vmap`` of the update)
    must NOT be used here: XLA gives the scatter its own layout and copies
    the whole stacked cache inside the layer loop (compiler, PR 25)."""
    zero = jnp.zeros((), jnp.int32)
    layer, start = jnp.asarray(layer, jnp.int32), start.astype(jnp.int32)
    if start.ndim and blocks[0].shape[-1] == 1 and _decode_uses_kernel(impl, leaves[0]):
        from prime_tpu.ops.pallas_attention import write_columns

        return write_columns(
            tuple(leaves), tuple(blocks), layer, start, work=work,
            interpret=_pallas_interpret(),
        )

    def put(leaf, block):
        block = block[None]
        if start.ndim == 0:
            return jax.lax.dynamic_update_slice(leaf, block, (layer, zero, zero, zero, start))
        for row in range(block.shape[1]):
            leaf = jax.lax.dynamic_update_slice(
                leaf, block[:, row : row + 1],
                (layer, jnp.int32(row), zero, zero, start[row]),
            )
        return leaf

    return tuple(put(leaf, block) for leaf, block in zip(leaves, blocks))


def _layer_of(cache: jnp.ndarray | None, layer) -> jnp.ndarray | None:
    """One layer of a stacked (L, B, KH, *, C) leaf for the einsum paths; a
    4-D leaf is one layer already. The barrier keeps the einsum's preferred
    operand layout on the slice: without it XLA's layout assignment carries
    that layout up into the buffer the layer scan carries and re-lays the
    WHOLE stacked V leaf, a copy in and a copy out of every chunk prefill
    (compiler, PR 25), where the slice is one layer's bytes."""
    if cache is None or cache.ndim == 4:
        return cache
    return jax.lax.optimization_barrier(
        jax.lax.dynamic_index_in_dim(cache, layer, 0, keepdims=False)
    )


def _pallas_eligible(q: jnp.ndarray, head_dim: int) -> bool:
    if jax.default_backend() != "tpu":
        return False
    seq_len = q.shape[2]
    from prime_tpu.ops.pallas_attention import BLOCK_Q, _resolve_block

    # the kernel's own divisibility fallback drops an ill-fitting resolved
    # block back to the 128 default, so eligibility accepts either alignment
    block_q = _resolve_block("flash_prefill", "block_q", BLOCK_Q)
    return (
        seq_len % block_q == 0 or seq_len % BLOCK_Q == 0
    ) and head_dim % 128 == 0


def _apply_softcap(scores: jnp.ndarray, softcap: float) -> jnp.ndarray:
    """Gemma2-style logit softcapping: softcap * tanh(scores / softcap).
    Applied to the scaled scores BEFORE masking (masked -inf entries must not
    pass through tanh or they'd become finite)."""
    if softcap:
        return jnp.tanh(scores / softcap) * softcap
    return scores


def _window_ok(delta: jnp.ndarray, window: int, sliding: jnp.ndarray | None) -> jnp.ndarray:
    """True where the query-key distance fits the sliding window. ``sliding``
    is a traced per-layer bool (Gemma2 alternates windowed/global layers);
    None means the window applies unconditionally."""
    ok = delta < window
    if sliding is not None:
        ok = ok | ~sliding
    return ok


def _sink_softmax(scores: jnp.ndarray, sinks: jnp.ndarray) -> jnp.ndarray:
    """Softmax over [scores, sink] dropping the sink column (GPT-OSS
    attention sinks): each head owns a learned logit that joins the
    normalization but contributes no value, damping attention mass on early
    tokens. ``sinks`` must broadcast against scores' leading dims with a
    trailing singleton key axis."""
    m = jnp.maximum(jnp.max(scores, axis=-1, keepdims=True), sinks)
    p = jnp.exp(scores - m)
    denom = jnp.sum(p, axis=-1, keepdims=True) + jnp.exp(sinks - m)
    return p / denom


def xla_attention_causal(
    q: jnp.ndarray,  # (B, H, S, D)
    k: jnp.ndarray,  # (B, KH, S, D)
    v: jnp.ndarray,
    sm_scale: float,
    softcap: float = 0.0,
    window: int = 0,
    sliding: jnp.ndarray | None = None,
    sinks: jnp.ndarray | None = None,  # (H,) per-head sink logits (GPT-OSS)
) -> jnp.ndarray:
    """Reference causal attention (fp32 softmax), GQA via head repetition."""
    num_heads, kv_heads = q.shape[1], k.shape[1]
    if kv_heads != num_heads:
        reps = num_heads // kv_heads
        k = jnp.repeat(k, reps, axis=1)
        v = jnp.repeat(v, reps, axis=1)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k, preferred_element_type=jnp.float32) * sm_scale
    scores = _apply_softcap(scores, softcap)
    seq = q.shape[2]
    allowed = jnp.tril(jnp.ones((seq, seq), dtype=bool))
    if window:
        pos = jnp.arange(seq)
        allowed = allowed & _window_ok(pos[:, None] - pos[None, :], window, sliding)
    scores = jnp.where(allowed[None, None], scores, NEG_INF)
    if sinks is not None:
        probs = _sink_softmax(
            scores, sinks.astype(jnp.float32).reshape(1, num_heads, 1, 1)
        )
    else:
        probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", probs.astype(q.dtype), v)


def _pallas_interpret() -> bool:
    """PRIME_TPU_PALLAS_INTERPRET=1 runs the kernels in interpret mode, so
    the pallas dispatch paths (incl. window/softcap/sink/int8 variants) can
    be validated off-TPU — `chip_smoke.py --rehearsal` sets it on CPU. With a TPU
    backend it is refused: the interpreter on the chip would quietly stand in
    for every compiled kernel."""
    from prime_tpu.core.config import env_flag

    if not env_flag("PRIME_TPU_PALLAS_INTERPRET", False):
        return False
    if jax.default_backend() == "tpu":
        raise RuntimeError(
            "PRIME_TPU_PALLAS_INTERPRET is set but the backend is a TPU: "
            "interpret mode is for validating kernels off-chip; unset it"
        )
    return True


def _flash_decode_min_capacity() -> int:
    from prime_tpu.core.config import env_int

    return env_int("PRIME_TPU_FLASH_DECODE_MIN_C", 2048)


def _decode_int4(
    q, k_cache, v_cache, cache_lengths, sm_scale, impl,
    k_scale, v_scale, softcap, window, sliding, sinks, layer, work,
):
    """int4-KV decode dispatch: a nibble-packed uint8 cache (a QUARTER of
    the bf16 bytes) rides the flash-decode kernel behind the same scales
    plumbing as int8. The gate reuses the multi-device rule the int4 weight
    kernel established (models/quantize.py ``_mesh_context_active``): a bare
    pallas_call cannot partition under SPMD jit, so mesh callers — and
    non-TPU backends outside interpret mode — take the XLA reference, which
    widens the nibbles in-graph, folds the scales, and runs the standard
    fp path (the ground truth the kernel is tested against, under the
    documented int4 rounding tolerance, not bit-identity)."""
    from prime_tpu.models.quantize import unpack_kv_int4

    if _int4_uses_kernel(impl, k_cache):
        from prime_tpu.ops.pallas_attention import flash_decode

        return flash_decode(
            q, k_cache, v_cache, cache_lengths, sm_scale=sm_scale,
            softcap=softcap, window=window, sliding=sliding, sinks=sinks,
            k_scale=k_scale, v_scale=v_scale, layer=layer, work=work,
            interpret=_pallas_interpret(),
        )
    k_f = unpack_kv_int4(_layer_of(k_cache, layer)) * _layer_of(k_scale, layer)
    v_f = unpack_kv_int4(_layer_of(v_cache, layer)) * _layer_of(v_scale, layer)
    return decode_attention(
        q, k_f, v_f, cache_lengths, sm_scale, impl="xla",
        softcap=softcap, window=window, sliding=sliding, sinks=sinks,
    ).astype(q.dtype)


def _int4_uses_kernel(impl: str, k_cache: jnp.ndarray) -> bool:
    """Whether ``impl`` sends a nibble-packed cache's decode to the kernel
    (``_decode_int4``'s gate)."""
    from prime_tpu.models.quantize import _mesh_context_active

    kernel_ok = not _mesh_context_active() and (
        _pallas_interpret()
        or (
            jax.default_backend() == "tpu"
            and k_cache.shape[-1] >= _flash_decode_min_capacity()
        )
    )
    return impl == "pallas" or (impl == "auto" and kernel_ok)


def _decode_pallas_eligible(k_cache: jnp.ndarray) -> bool:
    if jax.default_backend() != "tpu":
        return False
    capacity = k_cache.shape[-1]
    from prime_tpu.ops.pallas_attention import BLOCK_C

    # Short caches: XLA wins. The decode step is weight-bandwidth-bound; at
    # small capacity the KV read is a rounding error (67 MB vs 2.5 GB of
    # weights for llama3.2-1b at C=256) and the kernel's launch/tiling
    # overhead is a net loss — measured on v5e-1: XLA 1597 tok/s vs pallas
    # 1438 at b8 p128+128. Flash-decode's per-sequence early exit only pays
    # once the cache itself is a meaningful fraction of step bytes.
    if capacity < _flash_decode_min_capacity():
        return False
    # the kernel blocks the cache-slot axis (<=512 slots per DMA), so VMEM
    # no longer caps the capacity; alignment keeps the auto path on the
    # dividing-block fast case
    return capacity % BLOCK_C == 0


def _decode_uses_kernel(impl: str, k_cache: jnp.ndarray) -> bool:
    """Whether ``impl`` sends this cache's decode to the bare (unsharded)
    flash-decode kernel. One owner: the decode attention and the kernel that
    writes the step's column beside it take the same path."""
    return impl == "pallas" or (impl == "auto" and _decode_pallas_eligible(k_cache))


def _mesh_shards(mesh) -> tuple[int, int]:
    """(ways the serving mesh splits the batch, ways it splits the kv heads)."""
    shape = getattr(mesh, "shape", {})
    return int(shape.get("dp", 1)) * int(shape.get("fsdp", 1)), int(shape.get("tp", 1))


def _sharded_decode_eligible(k_cache, mesh, quantized: bool) -> bool:
    """Whether the shard_mapped flash-decode kernel can serve this step on
    ``mesh``: the kernel itself must be worth it (_decode_pallas_eligible —
    TPU backend, long aligned cache), the int8 scale epilogue is not plumbed
    through the shard_map wrapper yet, and every device's shard must be
    non-empty (batch divisible by the data axes, kv heads by tp)."""
    if quantized or not _decode_pallas_eligible(k_cache):
        return False
    if int(getattr(mesh, "shape", {}).get("sp", 1)) > 1:
        return False  # slot-sharded caches take the sp decode path, not the kernel
    data, tp = _mesh_shards(mesh)
    batch, kv_heads = k_cache.shape[-4], k_cache.shape[-3]
    return batch % max(1, data) == 0 and kv_heads % max(1, tp) == 0


def decode_kernel_tiling(
    impl: str, num_heads: int, k_cache, quantized: bool, mesh=None
) -> tuple[int, int, int]:
    """(grid steps, block_c, kv heads a step) of the flash-decode call that
    ``decode_attention`` makes for this cache under ``impl``, from the same
    gates and the kernel's own shape function; zeros where decode takes an XLA
    path. The grid steps are the most a call can make, every row full: a call
    makes as many as its work list holds (``decode_work``). ``k_cache`` needs
    a shape and a dtype only. Under a mesh the kernel sees a device's local
    rows and kv heads, and so does this."""
    from prime_tpu.ops.pallas_attention import decode_call_tiling

    batch, kv_heads, kv_dim, capacity = k_cache.shape[-4:]
    if quantized and k_cache.dtype == jnp.uint8:
        uses_kernel = _int4_uses_kernel(impl, k_cache)
    elif impl == "sharded":
        uses_kernel = mesh is not None and _sharded_decode_eligible(
            k_cache, mesh, quantized=quantized
        )
        if uses_kernel:
            data, tp = _mesh_shards(mesh)
            batch, kv_heads, num_heads = batch // data, kv_heads // tp, num_heads // tp
    else:
        uses_kernel = _decode_uses_kernel(impl, k_cache)
    if not uses_kernel:
        return 0, 0, 0
    local = jax.ShapeDtypeStruct((batch, kv_heads, kv_dim, capacity), k_cache.dtype)
    heads, block_c = decode_call_tiling(num_heads, local, quantized)
    return batch * (kv_heads // heads) * (capacity // block_c), block_c, heads


def decode_work(
    impl: str, num_heads: int, k_cache, quantized: bool, lengths: jnp.ndarray, mesh=None
) -> tuple[tuple | None, jnp.ndarray]:
    """(work, steps) for one decode step's attend ``lengths`` (B,) over this
    cache: the flash-decode kernel's work list (``pallas_attention.
    decode_work_list`` at the call's tiling), for a model to make once a step,
    outside its layer loop, and hand to every layer's ``decode_attention``; and
    a (2,) int32 vector, the grid steps a call makes with it and the most it
    could make. (None, zeros) where decode takes an XLA path, and on a mesh
    that shards the rows, where each device's kernel lists its own."""
    from prime_tpu.ops.pallas_attention import decode_work_list

    most, block_c, _ = decode_kernel_tiling(impl, num_heads, k_cache, quantized, mesh)
    if not most or (impl == "sharded" and _mesh_shards(mesh)[0] > 1):
        return None, jnp.zeros((2,), jnp.int32)
    batch, blocks = k_cache.shape[-4], k_cache.shape[-1] // block_c
    work = decode_work_list(lengths, block_c, blocks)
    return work, jnp.stack([work[2] * (most // (batch * blocks)), jnp.int32(most)])


def column_write_work(
    impl: str, k_cache, live: jnp.ndarray | None
) -> tuple[tuple | None, jnp.ndarray]:
    """(work, writes) for one decode step's column writes into this cache:
    ``write_columns``' list of the ``live`` rows (B,) (``pallas_attention.
    column_work_list``), for a model to make once a step, outside its layer
    loop, and hand to every layer's ``write_cache``; and a (2,) int32 vector,
    the rows a call writes with it and the rows it was offered. ``live`` None:
    every row is live and no list is needed. (None, zeros) where decode takes
    an XLA path, which writes every row: the gate is the decode attention's own
    (``_decode_uses_kernel``)."""
    if not _decode_uses_kernel(impl, k_cache):
        return None, jnp.zeros((2,), jnp.int32)
    batch = jnp.int32(k_cache.shape[-4])
    if live is None:
        return None, jnp.stack([batch, batch])
    from prime_tpu.ops.pallas_attention import column_work_list

    work = column_work_list(live)
    return work, jnp.stack([jnp.sum(work[0]), batch])


def delta_decode_work(
    impl: str, k_cache, leaf, live: jnp.ndarray
) -> tuple[tuple | None, jnp.ndarray]:
    """(work, steps) for one decode step of the gated delta rule over the
    stacked state ``leaf`` (R, B, H, d_k, d_v) and the step's ``live`` rows
    (B,): the state kernel's work list (``pallas_delta.delta_work_list``), for
    a model to make once a step, outside its layer loop, and hand to every
    linear layer's call; and a (2,) int32 vector, the grid steps the R calls
    make with it and the most they could make. (None, zeros) where the step is
    plain XLA: the gate is the decode attention's own (``_decode_uses_kernel``
    over the block's K cache, so the column write, the attention and the state
    take one path) and, on a chip, a state block whose keys fill whole
    sublane tiles (the kernel sums over them; the values' lanes may end inside
    a tile, which Mosaic pads in VMEM)."""
    from prime_tpu.ops.pallas_delta import delta_step_heads, delta_work_list

    layers, batch, heads, d_k, d_v = leaf.shape
    tiled = _pallas_interpret() or d_k % 8 == 0
    if not (tiled and _decode_uses_kernel(impl, k_cache)):
        return None, jnp.zeros((2,), jnp.int32)
    work = delta_work_list(live)
    calls = layers * (heads // delta_step_heads(heads, d_k, d_v))
    return work, jnp.stack([work[1], jnp.int32(batch)]) * calls


def decode_attention(
    q: jnp.ndarray,          # (B, H, 1, D)
    k_cache: jnp.ndarray,    # (L, B, KH, D, C) stacked feature-major (see
    v_cache: jnp.ndarray,    # models.llama.KVCache), or one layer's (B, KH, D, C)
    cache_lengths: jnp.ndarray,  # (B,) number of valid cache entries
    sm_scale: float,
    impl: str = "auto",      # auto | pallas | xla | sharded
    k_scale: jnp.ndarray | None = None,  # ([L,] B, KH, 1, C) int8-cache dequant scales
    v_scale: jnp.ndarray | None = None,
    softcap: float = 0.0,                # Gemma2 score softcapping
    window: int = 0,                     # sliding-window size (0 = global)
    sliding: jnp.ndarray | None = None,  # traced per-layer bool for `window`
    sinks: jnp.ndarray | None = None,    # (H,) per-head sink logits (GPT-OSS)
    mesh=None,                           # impl="sharded": the serving mesh
    layer: jnp.ndarray | int = 0,        # () layer of a stacked cache to attend over
    work: tuple | None = None,           # decode_work(...)[0] for these lengths,
    #                                      made once for every layer
) -> jnp.ndarray:
    """One decode step against the cache, masking invalid (future) slots. A
    row of length 0 (a slot that belongs to nobody) comes back zero on every
    path, and on the kernel paths costs no grid step and reads no cache block.
    ``work`` is that kernel's list of the grid steps these lengths need
    (``decode_work``): the kernel takes the caller's or makes its own (as it
    must where a mesh shards the rows), and the einsum path has no use for it.

    The model hands over the stacked cache its layer scan carries and the
    traced ``layer``: the kernel paths put the layer in their index maps and
    read it in place; the einsum paths slice it out first (``_layer_of``).

    On TPU with a long cache (capacity >= PRIME_TPU_FLASH_DECODE_MIN_C,
    default 2048) this dispatches to the pallas flash-decode kernel
    (early-exit at each sequence's true length, one fused HBM pass). Short
    caches use the XLA path even on TPU: decode is weight-bandwidth-bound
    there and the kernel overhead is a measured net loss (see
    _decode_pallas_eligible). The XLA path is a grouped einsum — GQA without
    jnp.repeat, so the cache is never materialized per-query-head.

    A bare pallas_call is not SPMD-partitionable, so callers under a
    multi-device mesh pass either ``impl="xla"`` (GSPMD partitions the
    einsum path — the eval runner's choice, evals/runner.py JaxGenerator) or
    ``impl="sharded"`` with ``mesh`` (the sharded-replica serve engine):
    when the cache shape qualifies for the kernel, the decode runs it under
    ``shard_map`` with the serving layout's specs — each device streams
    exactly its local cache shard (parallel/decode_sharded.py) — and falls
    back to the partitioned XLA path otherwise (short caches, int8 caches,
    non-TPU backends, batch/head counts the mesh cannot divide).
    """
    quantized = k_scale is not None
    if quantized and k_cache.dtype == jnp.uint8:
        # int4 cache (nibble-packed): its own dispatch — kernel when the
        # multi-device gate allows, XLA widen-and-fold reference otherwise
        return _decode_int4(
            q, k_cache, v_cache, cache_lengths, sm_scale, impl,
            k_scale, v_scale, softcap, window, sliding, sinks, layer, work,
        )
    if impl == "sharded":
        if mesh is not None and _sharded_decode_eligible(
            k_cache, mesh, quantized=quantized
        ):
            from prime_tpu.parallel.decode_sharded import flash_decode_sharded

            return flash_decode_sharded(
                q, k_cache, v_cache, cache_lengths, mesh, sm_scale=sm_scale,
                softcap=softcap, window=window, sliding=sliding, sinks=sinks,
                layer=layer, work=work, interpret=_pallas_interpret(),
            )
        impl = "xla"  # SPMD-safe einsum path, partitioned by GSPMD
    if _decode_uses_kernel(impl, k_cache):
        from prime_tpu.ops.pallas_attention import flash_decode

        # softcap/sliding-window/sinks ride the kernel (Gemma2/3, Mistral,
        # Phi-3, GPT-OSS): the window even front-skips cache blocks, so a
        # sliding layer streams ~window slots instead of the whole cache.
        # int8 caches ride it too — half the HBM bytes stream per step
        # (widened to fp32 in VMEM), per-slot scales fold into the epilogues.
        return flash_decode(
            q, k_cache, v_cache, cache_lengths, sm_scale=sm_scale,
            softcap=softcap, window=window, sliding=sliding, sinks=sinks,
            k_scale=k_scale, v_scale=v_scale, layer=layer, work=work,
            interpret=_pallas_interpret(),
        )

    k_cache, v_cache = _layer_of(k_cache, layer), _layer_of(v_cache, layer)
    k_scale, v_scale = _layer_of(k_scale, layer), _layer_of(v_scale, layer)
    batch, num_heads, _, head_dim = q.shape
    kv_heads = k_cache.shape[1]
    group = num_heads // kv_heads
    qg = q.reshape(batch, kv_heads, group, head_dim)
    if quantized:
        # int8 cache: the per-slot scales fold exactly into the einsums —
        # scores pick up k's slot scale, v's slot scale folds into the probs,
        # so the int8 values are read once and never materialized dequantized
        scores = jnp.einsum(
            "bkgd,bkdc->bkgc", qg.astype(jnp.float32), k_cache.astype(jnp.float32),
            preferred_element_type=jnp.float32,
        ) * (k_scale * sm_scale)
    else:
        scores = (
            jnp.einsum("bkgd,bkdc->bkgc", qg, k_cache, preferred_element_type=jnp.float32)
            * sm_scale
        )
    scores = _apply_softcap(scores, softcap)
    capacity = k_cache.shape[3]
    slot_ids = jnp.arange(capacity)[None, None, None, :]
    lengths_b = cache_lengths[:, None, None, None]
    valid = slot_ids < lengths_b
    if window:
        # the query sits at position lengths-1; distance to slot s is
        # (lengths-1) - s
        valid = valid & _window_ok(lengths_b - 1 - slot_ids, window, sliding)
    scores = jnp.where(valid, scores, NEG_INF)
    if sinks is not None:
        probs = _sink_softmax(
            scores, sinks.astype(jnp.float32).reshape(1, kv_heads, group, 1)
        )
    else:
        probs = jax.nn.softmax(scores, axis=-1)
    if quantized:
        weighted = (probs * v_scale).astype(jnp.float32)
        out = jnp.einsum(
            "bkgc,bkdc->bkgd", weighted, v_cache.astype(jnp.float32),
            preferred_element_type=jnp.float32,
        ).astype(q.dtype)
    else:
        out = jnp.einsum("bkgc,bkdc->bkgd", probs.astype(q.dtype), v_cache)
    # a row of length 0 attends to nothing and is zero, as the kernel returns
    # it (its softmax over an all-masked row is uniform, not empty)
    out = jnp.where(lengths_b > 0, out, 0)
    return out.reshape(batch, num_heads, 1, head_dim)


# columns one step of ``cache_prefill_attention``'s walk takes: the engine's
# smallest chunk cap, which divides every row longer than itself
PREFILL_BLOCK = 512


def prefill_attention_block(capacity: int) -> int:
    """Columns in one block of ``cache_prefill_attention``'s walk over a row of
    ``capacity`` columns, from that shape alone: 512 where it divides the row,
    the whole row where it does not (a row under one block, a capacity the
    engine does not build). It has to divide: ``lax.dynamic_slice`` clamps a
    start past the end in silence, and a ragged last block would be folded in
    twice. The engine's rows are powers of two up to the chunk cap (512 or a
    multiple) and the cap's multiples after
    (``serve/engine.py::row_capacities``), so 512 divides every row that is
    longer than a block. On the v5e 512 keeps a chunk's score tile on the chip
    at a wave of one (48 heads x 512 x 512 of float32 is 50 MB) and is as fast
    as 1024 there and faster than 256; at 2048 columns and more the tile goes
    through HBM and a call takes two to three times as long (PERF.md, PR 43)."""
    return capacity if capacity % PREFILL_BLOCK else PREFILL_BLOCK


def prefill_block_span(first_offset, last_offset, seq, block, window=0):
    """[first, last) blocks of ``block`` columns that hold a column some query
    of a chunk of ``seq`` sees, its rows starting at ``first_offset`` ..
    ``last_offset``: none past the last row's last query, none wholly behind
    the first row's ``window`` where one applies. Operators alone (the clamp at
    0 too), so the walk's traced bounds and the host's count of them
    (``prefill_columns_visited``) are these same lines."""
    last = (last_offset + seq + block - 1) // block
    if not window:
        return 0, last
    behind = first_offset - window + 1
    return (behind * (behind > 0)) // block, last


def prefill_columns_visited(offset: int, seq: int, capacity: int, window: int = 0) -> int:
    """Columns of a row of ``capacity`` that one call of
    ``cache_prefill_attention`` visits with a chunk of ``seq`` queries at
    ``offset``, on the host: the walk's trip count x its block. ``window``: the
    call's window where it applies (a layer that slides)."""
    block = prefill_attention_block(capacity)
    first, last = prefill_block_span(offset, offset, seq, block, window)
    return (last - first) * block


def cache_prefill_attention(
    q: jnp.ndarray,          # (B, H, S, D) queries for a prompt CHUNK
    k_cache: jnp.ndarray,    # ([L,] B, KH, D, C) feature-major, chunk already written
    v_cache: jnp.ndarray,    # ([L,] B, KH, D, C)
    offset: jnp.ndarray,     # () or (B,) first cache slot of this chunk (traced)
    sm_scale: float,
    softcap: float = 0.0,
    window: int = 0,
    sliding: jnp.ndarray | None = None,
    k_scale: jnp.ndarray | None = None,  # ([L,] B, KH, 1, C) int8-cache dequant scales
    v_scale: jnp.ndarray | None = None,
    sinks: jnp.ndarray | None = None,    # (H,) per-head sink logits (GPT-OSS)
    layer: jnp.ndarray | int = 0,        # () layer of a stacked cache to attend over
) -> jnp.ndarray:
    """Attention for chunked prefill: the chunk's K/V are first *written* into
    the cache at ``offset``, then each chunk query attends over the cache
    with the mask ``slot < offset + q_index + 1`` — causal within the
    chunk, full visibility of everything before it (earlier chunks, a reused
    prefix). One code path serves chunk 0 (offset 0 ≡ plain causal) and every
    later chunk, so chunked prefill composes with prefix caching for free.

    The row is walked in column blocks (``prefill_attention_block``) under a
    running softmax: float32 running maximum, sum and output accumulator, as
    the kernels keep them (``pallas_attention._finalize_attention``), one
    rolled loop whose bounds are traced. It visits the blocks that hold a
    column some query sees (``prefill_block_span``): up to the chunk's own
    last column, and from the window's first where one applies. A chunk holds
    S x block scores at a time, never S x C, and the columns past the chunk
    are neither scored nor weighed. The layer comes out of the stacked leaf
    once, before the loop (``_layer_of``), and a step slices its block out of
    that: a block sliced from the stacked leaf inside the loop carries the
    einsum's operand layout up into the buffer the layer scan carries, and
    XLA re-lays the whole stacked K leaf, a copy in and a copy out of every
    chunk prefill (compiler, PR 43; the barrier on the slice does not stop it
    across the loop's boundary).

    Grouped-einsum GQA like the decode path — the cache is never materialized
    per-query-head.
    """
    shared = v_cache is k_cache  # one latent serves as keys and values (models/mla.py)
    k_cache = _layer_of(k_cache, layer)
    v_cache = k_cache if shared else _layer_of(v_cache, layer)
    k_scale, v_scale = _layer_of(k_scale, layer), _layer_of(v_scale, layer)
    batch, num_heads, seq, head_dim = q.shape
    kv_heads, capacity = k_cache.shape[-3], k_cache.shape[-1]
    group = num_heads // kv_heads
    block = prefill_attention_block(capacity)
    qg = q.reshape(batch, kv_heads, group, seq, head_dim)
    if k_scale is not None:
        qg = qg.astype(jnp.float32)
    # offset () = one shared chunk start; (B,) = per-sequence starts (the
    # speculative verify window sits at each row's own cache length)
    offset_b = jnp.reshape(offset.astype(jnp.int32), (-1,))
    q_pos = offset_b[:, None, None] + jnp.arange(seq)[None, :, None]  # (B|1, S, 1)
    first, last = prefill_block_span(jnp.min(offset_b), jnp.max(offset_b), seq, block, window)
    first = jnp.asarray(first, jnp.int32)  # both bounds traced int32: the loop is one ``while``
    if window and sliding is not None:
        first = jnp.where(sliding, first, 0)  # the layer's traced flag: a full layer starts at 0

    def columns(leaf, start):
        """(B, KH, *, block) of one layer's ``leaf`` at [start, start + block)."""
        return jax.lax.dynamic_slice_in_dim(leaf, start, block, axis=3)

    def fold(i, carry):
        m, l, acc = carry
        start = i * block
        k_blk = columns(k_cache, start)
        v_blk = k_blk if shared else columns(v_cache, start)
        if k_scale is not None:
            # int8 cache: per-slot scales are constant over the contracted d axis,
            # so dequant folds into the score epilogue exactly (decode path's
            # scheme; k_scale (B, KH, 1, block) broadcasts over the g and s dims)
            scores = jnp.einsum(
                "bkgsd,bkdc->bkgsc", qg, k_blk.astype(jnp.float32),
                preferred_element_type=jnp.float32,
            ) * (columns(k_scale, start)[:, :, None, :, :] * sm_scale)
        else:
            scores = (
                jnp.einsum("bkgsd,bkdc->bkgsc", qg, k_blk, preferred_element_type=jnp.float32)
                * sm_scale
            )
        scores = _apply_softcap(scores, softcap)
        slot_ids = start + jnp.arange(block)[None, None, :]       # (1, 1, block)
        visible = slot_ids < q_pos + 1                            # (B|1, S, block)
        if window:
            visible = visible & _window_ok(q_pos - slot_ids, window, sliding)
        scores = jnp.where(visible[:, None, None], scores, NEG_INF)
        # a query that has seen nothing yet folds its masked columns in at
        # weight 1; the first block it does see scales them away (alpha 0)
        m_new = jnp.maximum(m, jnp.max(scores, axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(scores - m_new)
        l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        if v_scale is not None:
            weighted = (p * columns(v_scale, start)[:, :, None, :, :]).astype(jnp.float32)
            pv = jnp.einsum(
                "bkgsc,bkdc->bkgsd", weighted, v_blk.astype(jnp.float32),
                preferred_element_type=jnp.float32,
            )
        else:
            pv = jnp.einsum(
                "bkgsc,bkdc->bkgsd", p.astype(q.dtype), v_blk,
                preferred_element_type=jnp.float32,
            )
        return m_new, l, acc * alpha + pv

    rows = (batch, kv_heads, group, seq)
    m, l, acc = jax.lax.fori_loop(
        first, last, fold,
        (
            jnp.full((*rows, 1), NEG_INF, jnp.float32),
            jnp.zeros((*rows, 1), jnp.float32),
            jnp.zeros((*rows, v_cache.shape[-2]), jnp.float32),
        ),
    )
    if sinks is not None:
        # the sink joins the denominator once (``_sink_softmax``'s arithmetic)
        sink = sinks.astype(jnp.float32).reshape(1, kv_heads, group, 1, 1)
        m_final = jnp.maximum(m, sink)
        scale = jnp.exp(m - m_final)
        acc, l = acc * scale, l * scale + jnp.exp(sink - m_final)
    return (acc / l).astype(q.dtype).reshape(batch, num_heads, seq, -1)


def multi_head_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    sm_scale: float | None = None,
    impl: str = "auto",  # auto | pallas | xla
    softcap: float = 0.0,
    window: int = 0,
    sliding: jnp.ndarray | None = None,
    sinks: jnp.ndarray | None = None,  # (H,) per-head sink logits (GPT-OSS)
) -> jnp.ndarray:
    """Causal self-attention (prefill path). Softcap / sliding-window /
    attention-sink configs ride the flash kernel too (a sliding layer's
    prefill skips KV blocks outside each query block's band)."""
    head_dim = q.shape[-1]
    if sm_scale is None:
        sm_scale = head_dim**-0.5
    if impl == "pallas" or (impl == "auto" and _pallas_eligible(q, head_dim)):
        from prime_tpu.ops.pallas_attention import flash_attention_causal

        return flash_attention_causal(
            q, k, v, sm_scale=sm_scale, softcap=softcap, window=window,
            sliding=sliding, sinks=sinks, interpret=_pallas_interpret(),
        )
    return xla_attention_causal(q, k, v, sm_scale, softcap, window, sliding, sinks=sinks)


def attention_at_positions(
    q: jnp.ndarray,       # (B, H, S, D)
    k_t: jnp.ndarray,     # (B, KH, D, T) feature-major, as the cache stores keys
    v_t: jnp.ndarray,     # (B, KH, D, T)
    q_pos: jnp.ndarray,   # (B | 1, S) the position of each query
    k_pos: jnp.ndarray,   # (B | 1, T) the position each key column holds; < 0: none
    sm_scale: float,
    window: int = 0,      # > 0: a query sees the ``window`` keys up to its own
) -> jnp.ndarray:
    """Attention in which what a query sees is a matter of positions alone:
    key columns in any order, each with the position it holds, a query seeing
    those at or before its own (and inside its window). For a model without
    positional encoding that is all a softmax needs, so one function serves a
    whole sequence, a chunk of queries over a ring of older columns beside its
    own, and one late query over a row. Grouped einsum, fp32 softmax."""
    batch, num_heads, seq, head_dim = q.shape
    kv_heads = k_t.shape[1]
    group = num_heads // kv_heads
    qg = q.reshape(batch, kv_heads, group, seq, head_dim)
    scores = (
        jnp.einsum("bkgsd,bkdc->bkgsc", qg, k_t, preferred_element_type=jnp.float32)
        * sm_scale
    )
    delta = q_pos[:, :, None] - k_pos[:, None, :]  # (B | 1, S, T)
    visible = (k_pos[:, None, :] >= 0) & (delta >= 0)
    if window:
        visible = visible & (delta < window)
    scores = jnp.where(visible[:, None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgsc,bkdc->bkgsd", probs.astype(q.dtype), v_t)
    return out.reshape(batch, num_heads, seq, head_dim)


def ring_positions(end: jnp.ndarray, columns: int) -> jnp.ndarray:
    """(..., columns) the position each column of a ring holds once positions
    [0, end) have been written at ``position mod columns``: the last one
    below ``end`` that lands on the column, negative where there is none yet.
    ``end`` () or (B,)."""
    last = jnp.asarray(end, jnp.int32)[..., None] - 1
    return last - (last - jnp.arange(columns, dtype=jnp.int32)) % columns


def write_ring(leaves, blocks, layer, offset, counts):
    """Write a chunk's ``blocks`` (B, KH, D, S), holding positions [offset,
    offset + S), into one layer of the stacked ring ``leaves`` (L, B, KH, D, W)
    at ``position mod W``. ``counts`` (B,): only a row's first ``counts``
    positions are its own (the rest is padding and must not evict live
    columns). Whatever the chunk's size and offset: a piece of at most W
    columns at a time, rolled to the columns its positions land on (a
    concatenate and a dynamic slice), and a column takes it where the newest
    position that lands there is one of the piece's own, else it stays. No
    gather: one over the ring's lanes cost 9-14 ms a leaf and layer on the
    v5e, nine tenths of a 512-token chunk (my chip run, PR 30, call 1)."""
    layer = jnp.asarray(layer, jnp.int32)
    zero = jnp.zeros((), jnp.int32)
    columns, size = leaves[0].shape[-1], blocks[0].shape[-1]
    offset, counts = jnp.asarray(offset, jnp.int32), counts.astype(jnp.int32)
    rings = [jax.lax.dynamic_index_in_dim(leaf, layer, 0, keepdims=False) for leaf in leaves]
    for start in range(0, size, columns):
        width = min(columns, size - start)
        first = offset + start
        holds = ring_positions(first + jnp.clip(counts - start, 0, width), columns)  # (B, W)
        take = (holds >= first)[:, None, None, :]
        for i, block in enumerate(blocks):
            piece = block[..., start : start + width].astype(rings[i].dtype)
            piece = jnp.pad(piece, [(0, 0)] * 3 + [(0, columns - width)])
            rings[i] = jnp.where(take, jnp.roll(piece, first % columns, axis=-1), rings[i])
    return tuple(
        jax.lax.dynamic_update_slice(leaf, ring[None], (layer, zero, zero, zero, zero))
        for leaf, ring in zip(leaves, rings)
    )
