"""Flash attention (causal, GQA) as a pallas TPU kernel.

Online-softmax blockwise attention: for each query block, stream key/value
blocks through VMEM keeping running (max, sum, output) accumulators in fp32
scratch — O(S) memory instead of the O(S^2) score matrix, and every matmul
lands on the MXU at (BLOCK, head_dim)x(head_dim, BLOCK) granularity.

The KV/cache-block axis is a GRID dimension in both kernels, with the
online-softmax state carried across the innermost axis ("arbitrary"
semantics). Prefill: (batch, q_heads, S // BLOCK_Q, S // BLOCK_K); the index
maps clip each step's block coordinate into the live range (causal diagonal /
sliding-window band), out-of-range steps revisit an already-resident block,
and Mosaic elides the copy when the index map repeats itself — so dead blocks
are never READ from HBM, not merely skipped in compute. Decode: (kv_heads //
heads, n), where a grid step carries ``heads`` kv heads of a row and a cache
block as wide as VMEM allows (``decode_tiling``: every head and 512 slots at
the served shapes) and ``n`` is a DYNAMIC bound: the length of the step's
work list (``decode_work_list``), the (row, cache block) pairs that hold a
live column. A block nobody attends over has no grid step at all. These ops
are HBM-bandwidth-bound, and an earlier design that DMA'd the full operand per
program and skipped only compute lost to XLA's read-it-all path.

GQA is handled in the BlockSpec index maps: query head h reads kv head
h // (H // KH), so grouped KV is never materialized per-query-head in HBM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Built-in block defaults (measured on v5e-1). Call sites resolve the
# ACTIVE blocks through ops/kernel_configs.py — env override, then a tuned
# per-device-kind artifact, then these — with per-shape divisibility
# fallbacks, so the constants remain the floor of the resolution chain, not
# the tiling itself.
BLOCK_Q = 128
BLOCK_K = 128
# flash-decode's NARROWEST cache-slot block (the lane tile of the kv cache,
# and the block write_columns rewrites); the block a call uses comes from
# decode_tiling
BLOCK_C = 128
NEG_INF = -1e30

# What one flash-decode grid step may hold in VMEM: the double-buffered K and V
# (and scale) blocks plus the body's float32 temporaries. A v5e's scoped
# default is 16 MiB; the rest is left to q, the output, the scratch and
# whatever Mosaic spills.
DECODE_VMEM_BUDGET = 10 * 1024 * 1024


def _resolve_block(kernel: str, param: str, default: int) -> int:
    from prime_tpu.ops import kernel_configs

    try:
        return kernel_configs.resolve(kernel, param)
    except KeyError:  # pragma: no cover — registry/kernel name drift
        return default


def _window_scalar(window: int, sliding) -> jnp.ndarray:
    """Effective window as a (1,) prefetch scalar: the layer scan traces
    ``sliding``, so the window can't be folded statically — 0 means global.
    ONE implementation for the prefill and decode kernels, so their window
    semantics cannot drift."""
    if window:
        on = sliding if sliding is not None else jnp.asarray(True)
        return jnp.where(on, jnp.int32(window), jnp.int32(0)).reshape(1)
    return jnp.zeros((1,), jnp.int32)


def _sinks_operand(sinks, rows: int, cols: int) -> tuple[bool, jnp.ndarray]:
    """(use_sinks, operand): a real zeros operand keeps one kernel signature
    when sinks are off (a zero sink would CHANGE the math — exp(0) joins the
    denominator — so use_sinks gates the epilogue statically)."""
    if sinks is None:
        return False, jnp.zeros((rows, cols), jnp.float32)
    return True, sinks.astype(jnp.float32).reshape(rows, cols)


def _finalize_attention(acc, m, l, sink):
    """Shared epilogue: plain normalization, or — with a sink logit — the
    GPT-OSS denominator (the per-head logit joins the softmax normalization
    with no value contribution): rescale the accumulators to the combined
    max, add exp(sink)."""
    if sink is None:
        return acc / jnp.maximum(l, 1e-30)
    m_final = jnp.maximum(m, sink)
    scale = jnp.exp(m - m_final)
    denom = l * scale + jnp.exp(sink - m_final)
    return acc * scale / jnp.maximum(denom, 1e-30)



def _prefill_band(qb, window_ref, block_q: int, block_k: int):
    """This query block's live kv-block range [band_start, causal_last]:
    causal cuts blocks strictly above the diagonal, a sliding window cuts
    blocks entirely before the band. Shared by the kernel's compute gate and
    the k/v index maps — the index-map clip makes out-of-range grid steps
    revisit a resident block: Mosaic elides an operand's copy when its index
    map returns the same block as the previous iteration, so the clip turns
    the mask-level early exit into an actual HBM-bytes saving."""
    window = window_ref[0]
    causal_last = (qb * block_q + block_q - 1) // block_k
    band_start = jnp.where(
        window > 0, jnp.maximum(qb * block_q - window + 1, 0) // block_k, 0
    )
    return band_start, causal_last


def _flash_kernel(
    window_ref,  # (1,) scalar-prefetch: effective window (0 = global layer)
    q_ref,       # (1, 1, BLOCK_Q, D)
    k_ref,       # (1, 1, BLOCK_K, D) this step's live kv block
    v_ref,       # (1, 1, BLOCK_K, D)
    sinks_ref,   # (H, 1) all sink logits; row picked by program id
    o_ref,       # (1, 1, BLOCK_Q, D)
    m_scr,       # (BLOCK_Q, 128) f32: running max, carried across kv steps
    l_scr,       # (BLOCK_Q, 128) f32: running denominator
    acc_scr,     # (BLOCK_Q, D) f32: output accumulator
    *,
    sm_scale: float,
    block_q: int,
    block_k: int,
    softcap: float,
    use_sinks: bool,
):
    # program ids hoisted out of the pl.when closures (the HLO interpreter
    # has no lowering for the primitive inside them)
    h = pl.program_id(1)
    qb = pl.program_id(2)
    kb = pl.program_id(3)
    last_kb = pl.num_programs(3) - 1
    window = window_ref[0]
    band_start, causal_last = _prefill_band(qb, window_ref, block_q, block_k)

    @pl.when(kb == 0)
    def _init():
        m_scr[...] = jnp.full(m_scr.shape, NEG_INF, dtype=jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, dtype=jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, dtype=jnp.float32)

    @pl.when((kb >= band_start) & (kb <= causal_last))
    def _accumulate():
        q = q_ref[0, 0].astype(jnp.float32) * sm_scale  # (BQ, D)
        k = k_ref[0, 0].astype(jnp.float32)             # (BK, D)
        v = v_ref[0, 0].astype(jnp.float32)
        scores = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # (BQ, BK)
        if softcap:
            scores = jnp.tanh(scores / softcap) * softcap
        q_positions = qb * block_q + jax.lax.broadcasted_iota(
            jnp.int32, scores.shape, 0
        )
        kv_positions = kb * block_k + jax.lax.broadcasted_iota(
            jnp.int32, scores.shape, 1
        )
        allowed = kv_positions <= q_positions
        # sliding layer: key must also be within `window` of the query
        # (delta < window, matching ops.attention._window_ok)
        allowed &= (window == 0) | (q_positions - kv_positions < window)
        scores = jnp.where(allowed, scores, NEG_INF)

        m_prev = m_scr[:, :1]
        l_prev = l_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(scores, axis=-1, keepdims=True))
        p = jnp.exp(scores - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(kb == last_kb)
    def _finalize():
        # full-array sinks block (see flash_decode): slice this head's row
        sink = sinks_ref[h, 0].astype(jnp.float32) if use_sinks else None
        o_ref[0, 0] = _finalize_attention(
            acc_scr[...], m_scr[:, :1], l_scr[:, :1], sink
        ).astype(o_ref.dtype)


def decode_work_list(
    lengths: jnp.ndarray, block_c: int, blocks: int, window=0
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """(rows, cache_blocks, n): flash-decode's grid steps for these attend
    lengths (0 for a row that belongs to nobody) over a cache of ``blocks``
    blocks of ``block_c`` slots. The first ``n`` entries of the two
    (B * blocks + 1,) tables are the (row, cache block) pairs that hold a live
    column, rows in order and a row's blocks front to back: blocks
    [first, cdiv(length, block_c)) where ``first`` skips what a sliding
    ``window`` (a static int or a traced scalar, 0 = none) leaves behind. The
    tail repeats the last pair: the pipeline reads the index maps a step ahead,
    and an equal index copies nothing. Every row full, the list is the static
    grid's walk, B * blocks steps. A call whose rows are all dead keeps one
    step (``n`` >= 1), whose result ``flash_decode`` discards. A function of
    the lengths alone, so a model makes it once a decode step, outside its
    layer loop, and hands it to every layer's call."""
    batch = lengths.shape[0]
    lengths = jnp.clip(lengths.astype(jnp.int32), 0, blocks * block_c)
    window = jnp.asarray(window, jnp.int32).reshape(())
    first = jnp.where(window > 0, jnp.maximum(lengths - window, 0), 0) // block_c
    counts = pl.cdiv(lengths, block_c) - first  # 0 for a dead row
    ends = jnp.cumsum(counts)
    n = ends[-1]
    steps = jnp.minimum(jnp.arange(batch * blocks + 1, dtype=jnp.int32), jnp.maximum(n - 1, 0))
    # the row of step i: as many rows as end at or before it (dead rows among
    # them, which is how they are passed over)
    rows = jnp.minimum(jnp.sum(ends[None, :] <= steps[:, None], axis=1), batch - 1)
    # its block: first[row] + (i - the row's first step), picked without a gather
    own = rows[:, None] == jnp.arange(batch, dtype=jnp.int32)[None, :]
    base = jnp.sum(jnp.where(own, (first - ends + counts)[None, :], 0), axis=1)
    return rows.astype(jnp.int32), (base + steps).astype(jnp.int32), jnp.maximum(n, 1)


def _unpack_kv_nibbles(packed):
    """Widen a nibble-packed (D/2, BLOCK_C) uint8 cache block to its fp32
    (D, BLOCK_C) values in VMEM: low nibble = features [0, D/2), high
    nibble = [D/2, D) — the models/quantize.py packing convention. The
    packed bytes are what streamed from HBM; the widening is VMEM-local."""
    # sign-extend in int32, like ops/pallas_quant.py: Mosaic on v5e has no
    # int8 vector subtract ("failed to legalize arith.subi")
    p32 = packed.astype(jnp.int32)
    lo = ((p32 & 0xF) ^ 8) - 8
    hi = ((p32 >> 4) ^ 8) - 8
    return jnp.concatenate([lo, hi], axis=0).astype(jnp.float32)


def decode_step_vmem_bytes(
    heads: int, kv_dim: int, block_c: int, itemsize: int,
    group: int, head_dim: int, quantized: bool,
) -> int:
    """VMEM a flash-decode grid step of ``heads`` kv heads and ``block_c``
    slots needs: the K and V blocks (and their per-slot scale blocks, padded to
    8 sublanes), each double-buffered by the pipeline, and the body's float32
    temporaries for ONE head (the heads are a static loop): the widened V
    block, the widened K block where the cache is not fed to the MXU as it is
    stored (int8 / int4), and the scores, the probabilities and the mask."""
    blocks = 2 * 2 * heads * kv_dim * block_c * itemsize
    if quantized:
        blocks += 2 * 2 * heads * 8 * block_c * 4
    rows = -(-group // 8) * 8
    widened = (2 if quantized else 1) * head_dim * block_c * 4
    return blocks + widened + 3 * rows * block_c * 4


def decode_tiling(
    kv_heads: int, kv_dim: int, capacity: int, itemsize: int,
    group: int, head_dim: int, quantized: bool = False, prefer: int = 0,
) -> tuple[int, int]:
    """(heads, block_c) of one flash-decode grid step, from what the call can
    see of the cache: the widest cache block of ``prefer`` (an explicit
    PRIME_TPU_BLOCK_C or a tuned artifact's value; 0 = none), 512, 256, 128
    that divides the capacity, and with it the most kv heads of a row (a
    divisor of ``kv_heads``) whose step stays inside DECODE_VMEM_BUDGET. A
    step costs 0.2 us on a v5e beside what its block costs (PERF.md, PR 27),
    so fewer and wider steps win until the blocks no longer fit: qwen3-4b's
    8 x 128 x 2048 bf16 rows come out at (8, 512), at most 4 steps a row; a
    576-wide latent of one head at (1, 512); a 32-head MHA cache at (16, 512).
    A capacity that none of the widths divides is one block. Which of a cache's
    blocks get a step is not this function's: ``decode_work_list``."""
    widths = [
        b for b in dict.fromkeys((prefer, 512, 256, BLOCK_C))
        if 0 < b <= capacity and capacity % b == 0
    ] or [capacity]
    divisors = [h for h in range(kv_heads, 0, -1) if kv_heads % h == 0]
    for block_c in widths:
        for heads in divisors:
            need = decode_step_vmem_bytes(
                heads, kv_dim, block_c, itemsize, group, head_dim, quantized
            )
            if need <= DECODE_VMEM_BUDGET:
                return heads, block_c
    return 1, widths[-1]


def decode_call_tiling(num_heads: int, k_cache, quantized: bool) -> tuple[int, int]:
    """``decode_tiling`` for a call's operands (``k_cache`` needs a shape and a
    dtype only). The config registry's value (env override > tuned
    per-device-kind artifact; 0 when neither is set) is the block tried
    first, and the divisibility walk inside ``decode_tiling`` is the fallback
    that keeps an ill-fitting tuned value harmless."""
    kv_heads, kv_dim, capacity = k_cache.shape[-3:]
    int4 = quantized and k_cache.dtype == jnp.uint8
    return decode_tiling(
        kv_heads, kv_dim, capacity, k_cache.dtype.itemsize,
        num_heads // kv_heads, kv_dim * (2 if int4 else 1), quantized=quantized,
        prefer=_resolve_block(
            "flash_decode_int8" if quantized else "flash_decode", "block_c", 0
        ),
    )


def _decode_kernel(
    lengths_ref,  # (B,) scalar-prefetch, SMEM
    rows_ref,     # (B * blocks + 1,) scalar-prefetch: the work list's row and
    blocks_ref,   # cache block of each grid step (decode_work_list)
    window_ref,   # (1,) scalar-prefetch: effective window (0 = global layer)
    layer_ref,    # (1,) scalar-prefetch: read by the K/V index maps only
    q_ref,        # (1, HEADS, G, D): every query head of this step's kv heads
    k_ref,        # (1, HEADS, D, BLOCK_C) the live cache block for this step
                  # (int4: (1, HEADS, D/2, BLOCK_C) nibble-packed uint8)
    v_ref,        # (1, HEADS, D, BLOCK_C)
    *rest,        # int8/int4 path: k_scale_ref, v_scale_ref (1, HEADS, 1, BLOCK_C);
                  # then sinks_ref (KH, G), o_ref (1, HEADS, G, D),
                  # scratch: m (HEADS, G, 128), l (HEADS, G, 128),
                  # acc (HEADS, G, D) — all fp32, carried across a row's steps
    sm_scale: float,
    block_c: int,
    capacity: int,
    softcap: float,
    use_sinks: bool,
    quant: str | None,  # None | "int8" | "int4" cache carrier
):
    if quant is not None:
        k_scale_ref, v_scale_ref, sinks_ref, o_ref, m_scr, l_scr, acc_scr = rest
    else:
        sinks_ref, o_ref, m_scr, l_scr, acc_scr = rest
        k_scale_ref = v_scale_ref = None
    heads, group = q_ref.shape[1], q_ref.shape[2]

    # program ids hoisted out of the pl.when closures: inside them the HLO
    # interpreter (CPU tests) has no lowering for the primitive
    hb = pl.program_id(0)
    step = pl.program_id(1)
    # every step that runs is a live block of a live row: the list says whose
    cb = blocks_ref[step]
    length = jnp.minimum(lengths_ref[rows_ref[step]], capacity)
    window = window_ref[0]
    # the query sits at position length-1; a sliding layer sees slots
    # [length-window, length), a global layer (window 0) sees [0, length)
    first_slot = jnp.where(window > 0, jnp.maximum(length - window, 0), 0)

    # a row's steps are its blocks [first, num), front to back
    @pl.when(cb == first_slot // block_c)
    def _init():
        m_scr[...] = jnp.full(m_scr.shape, NEG_INF, dtype=jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, dtype=jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, dtype=jnp.float32)

    slots = cb * block_c + jax.lax.broadcasted_iota(
        jnp.int32, (group, block_c), 1
    )
    live = (slots < length) & (slots >= first_slot)
    # a cache stored as q is (bf16 under bf16 queries) goes to the MXU as it
    # is: products of bf16 values are exact in the float32 accumulator, so
    # only the summation order differs from widening first, and the K block
    # is not widened. V always is: the probabilities stay float32
    widen = quant is not None or k_ref.dtype != q_ref.dtype
    # the same online softmax for each kv head the step holds: a static
    # loop, so one head's widened block is live at a time
    for h in range(heads):
        q, k, v = q_ref[0, h], k_ref[0, h], v_ref[0, h]  # (G, D), (D, BC) x 2
        if quant == "int4":
            # int4 streams a QUARTER of the bf16 bytes from HBM; the nibble
            # widening happens on the VMEM-resident block, and the same
            # per-slot scales the int8 path uses fold into the epilogues
            k, v = _unpack_kv_nibbles(k), _unpack_kv_nibbles(v)
        else:
            v = v.astype(jnp.float32)
        if widen:
            q, k = q.astype(jnp.float32), k.astype(jnp.float32)
        scores = jax.lax.dot_general(
            q, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        ) * sm_scale  # (G, BC)
        if quant is not None:
            # int8/int4 stream from HBM at reduced bytes and widen to fp32
            # in VMEM; the per-slot scales are column-constant so they fold
            # into the epilogues, no dequantized cache is materialized
            scores = scores * k_scale_ref[0, h].astype(jnp.float32)  # (1, BC)
        if softcap:
            scores = jnp.tanh(scores / softcap) * softcap
        scores = jnp.where(live, scores, NEG_INF)

        m_prev = m_scr[h, :, :1]  # (G, 1)
        l_prev = l_scr[h, :, :1]
        m_new = jnp.maximum(m_prev, jnp.max(scores, axis=-1, keepdims=True))
        p = jnp.exp(scores - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        weighted = (
            p if quant is None else p * v_scale_ref[0, h].astype(jnp.float32)
        )
        acc_scr[h] = acc_scr[h] * alpha + jax.lax.dot_general(
            weighted, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (G, D)
        m_scr[h] = jnp.broadcast_to(m_new, m_scr.shape[1:])
        l_scr[h] = jnp.broadcast_to(l_new, l_scr.shape[1:])

    @pl.when(cb == pl.cdiv(length, block_c) - 1)
    def _finalize():
        for h in range(heads):
            # the sinks block is the FULL (KH, G) array (a (1, G) slice would
            # break the TPU lowering's sublane-divisibility rule); pick this
            # head's row
            sink = (
                sinks_ref[hb * heads + h].astype(jnp.float32).reshape(group, 1)
                if use_sinks
                else None
            )
            o_ref[0, h] = _finalize_attention(
                acc_scr[h], m_scr[h, :, :1], l_scr[h, :, :1], sink
            ).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("sm_scale", "softcap", "window", "interpret")
)
def flash_decode(
    q: jnp.ndarray,              # (B, H, 1, D)
    k_cache: jnp.ndarray,        # (L, B, KH, D, C) stacked, feature-major; or one
    v_cache: jnp.ndarray,        # layer's (B, KH, D, C), served as k[None], layer 0
    cache_lengths: jnp.ndarray,  # (B,) valid entries per sequence
    sm_scale: float | None = None,
    softcap: float = 0.0,                # Gemma2 score softcapping
    window: int = 0,                     # sliding-window size (0 = global)
    sliding: jnp.ndarray | None = None,  # traced per-layer bool for `window`
    sinks: jnp.ndarray | None = None,    # (H,) per-head sink logits (GPT-OSS)
    k_scale: jnp.ndarray | None = None,  # ([L,] B, KH, 1, C) int8-cache dequant scales
    v_scale: jnp.ndarray | None = None,
    layer: jnp.ndarray | int = 0,        # () which layer of the stacked cache to read
    work: tuple | None = None,           # decode_work_list(cache_lengths, ...) for this
    #                                      call's tiling and window, where the caller
    #                                      made it once for all its layers
    interpret: bool = False,
) -> jnp.ndarray:
    """One fused decode step: a grid step takes one cache block of a row, as
    wide as VMEM allows, with as many of the row's kv heads as fit beside it
    (``decode_tiling``: all of them and 512 slots at the served shapes), and
    folds it into the row's online softmax. The steps are the call's work list
    (``decode_work_list``) under a dynamic grid bound: one for every block that
    holds a column some row attends over, rows in order, and none for the rest
    of the cache, so a call's steps and its HBM traffic both track the true
    lengths, to the block. C must be a multiple of BLOCK_C. The feature-major
    cache keeps reads lane-aligned for any head_dim. A row of length 0 (the
    engine's retired slots) is in no step and comes back zero.

    The cache operand is the model's whole stacked (L, B, KH, D, C) buffer and
    ``layer`` a third scalar-prefetch operand that the K/V (and scale) index
    maps put in the leading block coordinate: the layer scan carries one
    buffer and the kernel reads its layer straight out of it, so no
    one-layer slice is ever made in HBM. A caller with a single layer's 4-D
    arrays is served by the same call on ``k[None]`` (a bitcast) at layer 0.

    Gemma/GPT-OSS variants ride the same kernel: ``softcap`` tanh-caps the
    scores, ``window`` (+ the traced per-layer ``sliding`` flag the model
    scan carries) masks AND front-skips cache blocks — a sliding layer
    streams only ~window slots instead of the whole cache — and ``sinks``
    adds each head's learned logit to the softmax denominator. With
    ``k_scale``/``v_scale`` the cache is int8: half the bytes stream from
    HBM (widened to fp32 in VMEM) and the per-slot scales fold into the
    score/value epilogues, so no dequantized cache is ever materialized.
    A uint8 cache with scales is the int4 variant (models/quantize.py
    nibble packing along head_dim — a QUARTER of the bf16 bytes): the
    kernel widens the packed block in VMEM behind the same scales plumbing."""
    quantized = k_scale is not None
    assert quantized == (v_scale is not None), "k_scale and v_scale go together"
    if k_cache.ndim == 4:
        k_cache, v_cache = k_cache[None], v_cache[None]
        if quantized:
            k_scale, v_scale = k_scale[None], v_scale[None]
    batch, num_heads, _, head_dim = q.shape
    kv_heads, capacity = k_cache.shape[2], k_cache.shape[4]
    assert num_heads % kv_heads == 0
    group = num_heads // kv_heads
    if sm_scale is None:
        sm_scale = head_dim**-0.5
    quant = None
    if quantized:
        quant = "int4" if k_cache.dtype == jnp.uint8 else "int8"
    kv_dim = k_cache.shape[3]  # head_dim, or head_dim/2 nibble-packed
    if quant == "int4":
        assert kv_dim * 2 == head_dim, "int4 cache must be nibble-packed along head_dim"
    else:
        assert kv_dim == head_dim
    heads, block_c = decode_call_tiling(num_heads, k_cache, quantized)

    window_arr = _window_scalar(window, sliding)
    use_sinks, sinks_arr = _sinks_operand(sinks, kv_heads, group)

    blocks = capacity // block_c
    lengths = cache_lengths.astype(jnp.int32)
    if work is None:
        work = decode_work_list(lengths, block_c, blocks, window_arr)
    rows, cache_blocks, n = work

    def row_map(hb, step, lens, rows, cbs, win, lay):
        return (rows[step], hb, 0, 0)

    def kv_map(hb, step, lens, rows, cbs, win, lay):
        # shared by k/v AND the int8 scale blocks: the scale block must
        # always ride the same block index as its cache block
        return (lay[0], rows[step], hb, 0, cbs[step])

    # the layer axis is squeezed (None): the kernel sees the same
    # (1, HEADS, D, BLOCK_C) blocks whichever layer the index map picked
    qkv_specs = [
        pl.BlockSpec((1, heads, group, head_dim), row_map),
        pl.BlockSpec((None, 1, heads, kv_dim, block_c), kv_map),
        pl.BlockSpec((None, 1, heads, kv_dim, block_c), kv_map),
    ]
    scale_specs = [
        pl.BlockSpec((None, 1, heads, 1, block_c), kv_map),
        pl.BlockSpec((None, 1, heads, 1, block_c), kv_map),
    ]
    sinks_spec = pl.BlockSpec((kv_heads, group), lambda hb, step, *_: (0, 0))
    kernel = functools.partial(
        _decode_kernel, sm_scale=sm_scale, block_c=block_c, capacity=capacity,
        softcap=softcap, use_sinks=use_sinks, quant=quant,
    )
    if quantized:
        in_specs = qkv_specs + scale_specs + [sinks_spec]
        operands = (k_cache, v_cache, k_scale, v_scale, sinks_arr)
    else:
        in_specs = qkv_specs + [sinks_spec]
        operands = (k_cache, v_cache, sinks_arr)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        # a step is one listed cache block of a row with `heads` of its kv
        # heads (all of them where they fit, so the first axis is 1); the
        # second axis is the work list, its bound traced: at most
        # batch * blocks steps, and as many as the rows' lengths need
        grid=(kv_heads // heads, n),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, heads, group, head_dim), row_map),
        scratch_shapes=[
            pltpu.VMEM((heads, group, 128), jnp.float32),       # running max
            pltpu.VMEM((heads, group, 128), jnp.float32),       # running denominator
            pltpu.VMEM((heads, group, head_dim), jnp.float32),  # output accumulator
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((batch, kv_heads, group, head_dim), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        cost_estimate=pl.CostEstimate(
            # flops/transcendentals stay full-capacity: the ceiling, what a
            # call whose rows are all full computes. bytes_accessed uses the
            # convention of flash_attention_causal: the steps that run copy
            # only ~length/capacity of the cache from HBM, rounded up to the
            # block, and lengths are traced (unknown at estimate time), so
            # charge the mid-generation expectation of capacity/2
            # (docs/kernels.md "Cost estimates").
            flops=2 * 2 * batch * num_heads * capacity * head_dim,
            # (K + V of the one layer read, halved: the stacked operand's
            # other layers are never touched)
            bytes_accessed=batch * kv_heads * kv_dim * capacity * k_cache.dtype.itemsize,
            transcendentals=batch * num_heads * capacity,
        ),
        interpret=interpret,
    )(
        lengths, rows, cache_blocks, window_arr,
        jnp.asarray(layer, jnp.int32).reshape(1),
        q.reshape(batch, kv_heads, group, head_dim), *operands,
    )
    # no step visits a row of length 0: what the call left there is not a result
    out = jnp.where((lengths > 0)[:, None, None, None], out, 0)
    return out.reshape(batch, num_heads, 1, head_dim)


def column_work_list(live: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """(live, rows, n): ``write_columns``' grid steps for a (B,) bool mask of
    the rows that belong to somebody. The first ``n`` entries of the (B + 1,)
    table are the live rows in order and its tail repeats the last (the
    pipeline reads the index maps a step ahead, and an equal index copies
    nothing): ``decode_work_list`` over a cache of one block. No live row:
    ``n`` is 1 and the one step finds its row dead in ``live`` (int32, as the
    kernel reads it) and passes its block through. A function of the mask
    alone, so a model makes it once a decode step for every layer's write."""
    live = live.astype(jnp.int32)
    rows, _, n = decode_work_list(live, 1, 1)
    return live, rows, n


def column_heads(kv_heads: int, widest: int, lanes: int) -> int:
    """KV heads of a row one ``write_columns`` program carries: the most (a
    divisor of ``kv_heads``) whose widest block (``widest`` bytes a head and
    slot, ``lanes`` slots) stays under 512 KB; each leaf's block is held in,
    out and double-buffered. qwen3-4b's 8 heads of 128 bf16 (262 KB), phi4's 10
    and a 576-wide latent's one go whole; 30 heads of 128 (983 KB) go 15 a
    program, two programs a row."""
    for heads in range(kv_heads, 0, -1):
        if kv_heads % heads == 0 and heads * widest * lanes <= 512 * 1024:
            return heads
    return 1


def _column_kernel(
    slots_ref,  # (B,) scalar-prefetch: the slot each row's column goes to
    live_ref,   # (B,) scalar-prefetch: 1 where the row belongs to somebody
    rows_ref,   # (B + 1,) scalar-prefetch: the work list's row of each grid step
    layer_ref,  # (1,) scalar-prefetch: read by the index maps only
    *refs,      # per leaf: the column (1, HB, d, 1); then per leaf the cache
                # block (1, HB, d, LANES) holding that slot; then the same
                # blocks again as outputs (aliased to the cache operands)
    lanes: int,
    capacity: int,
):
    count = len(refs) // 3
    row = rows_ref[pl.program_id(1)]
    # every step that runs is a live row's, but for the one step of a call with
    # no live row: no lane is its column's, and the block goes back as it came
    # (the outputs alias the cache: a block not written would be written back
    # as the buffer found it)
    slot = jnp.clip(slots_ref[row], 0, capacity - 1)
    lane_of_slot = jnp.where(live_ref[row] > 0, slot % lanes, -1)
    for col_ref, block_ref, out_ref in zip(
        refs[:count], refs[count : 2 * count], refs[2 * count :]
    ):
        # widen for the select: the v5e has no 16- or 8-bit vector select, and
        # bf16 -> f32 -> bf16 and int8 -> int32 -> int8 are exact
        wide = jnp.float32 if jnp.issubdtype(out_ref.dtype, jnp.floating) else jnp.int32
        lane = jax.lax.broadcasted_iota(jnp.int32, out_ref.shape, out_ref.ndim - 1)
        out_ref[...] = jnp.where(
            lane == lane_of_slot, col_ref[...].astype(wide), block_ref[...].astype(wide)
        ).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def write_columns(
    leaves: tuple[jnp.ndarray, ...],   # stacked cache leaves (L, B, KH, d_i, C)
    columns: tuple[jnp.ndarray, ...],  # this step's columns (B, KH, d_i, 1)
    layer: jnp.ndarray | int,          # () the layer to write
    slots: jnp.ndarray,                # (B,) the slot of each row's column
    work: tuple | None = None,         # column_work_list(live) for the step's live
    #                                    rows, made once for all its layers; None:
    #                                    every row is live
    interpret: bool = False,
) -> tuple[jnp.ndarray, ...]:
    """Decode's cache write as ONE kernel for every live row and leaf: each
    program brings the 128-slot block that holds its row's slot into VMEM, puts
    the column in its lane and writes the block back, into the SAME buffers
    (``input_output_aliases``), so the layer scan still carries one cache. As
    per-row ``dynamic_update_slice`` the same write is 8 operations a leaf of
    4-5 us each, 16 a layer for a bf16 cache of 8 rows: 2.4 ms of a 22.6 ms
    decode step on the v5e (PR 25). A slot past the end is clamped to the
    last one, as ``dynamic_update_slice`` clamps its start.

    The programs are the call's work list (``column_work_list``) under a
    dynamic grid bound, the form ``flash_decode`` and the state kernel have: one
    for every live row and block of its kv heads (``column_heads``), and none
    for a row that belongs to nobody, whose column is NOT written and whose
    blocks are neither read nor touched. Without ``work`` every row is live and
    the list is the static walk over the rows."""
    batch, kv_heads, capacity = leaves[0].shape[1], leaves[0].shape[2], leaves[0].shape[4]
    lanes = BLOCK_C if capacity % BLOCK_C == 0 else capacity
    widest = max(leaf.shape[3] * leaf.dtype.itemsize for leaf in leaves)
    heads = column_heads(kv_heads, widest, lanes)
    if work is None:
        live = jnp.ones((batch,), jnp.int32)
        rows, n = jnp.minimum(jnp.arange(batch + 1, dtype=jnp.int32), batch - 1), batch
    else:
        live, rows, n = work

    def column_spec(leaf):
        return pl.BlockSpec(
            (1, heads, leaf.shape[3], 1), lambda h, i, slots, live, rows, lay: (rows[i], h, 0, 0)
        )

    def block_spec(leaf):
        return pl.BlockSpec(
            (None, 1, heads, leaf.shape[3], lanes),
            # (the clamp is here and in the body, on the scalar core: as an
            # operation before the call it is one more launch a layer)
            lambda h, i, slots, live, rows, lay: (
                lay[0], rows[i], h, 0, jnp.clip(slots[rows[i]], 0, capacity - 1) // lanes
            ),
        )

    count = len(leaves)
    blocks = [block_spec(leaf) for leaf in leaves]
    return tuple(
        pl.pallas_call(
            functools.partial(_column_kernel, lanes=lanes, capacity=capacity),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=4,
                # a program is one listed row with `heads` of its kv heads (all
                # of them where they fit, so the first axis is 1); the second
                # axis is the work list, its bound traced: at most `batch`
                grid=(kv_heads // heads, n),
                in_specs=[column_spec(leaf) for leaf in leaves] + blocks,
                out_specs=blocks,
            ),
            out_shape=[jax.ShapeDtypeStruct(leaf.shape, leaf.dtype) for leaf in leaves],
            # operands count the four prefetched scalars and the columns first
            input_output_aliases={4 + count + i: i for i in range(count)},
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary"),
            ),
            interpret=interpret,
        )(
            slots.astype(jnp.int32), live, rows,
            jnp.asarray(layer, jnp.int32).reshape(1),
            *columns, *leaves,
        )
    )


@functools.partial(
    jax.jit, static_argnames=("sm_scale", "softcap", "window", "interpret")
)
def flash_attention_causal(
    q: jnp.ndarray,  # (B, H, S, D)
    k: jnp.ndarray,  # (B, KH, S, D)
    v: jnp.ndarray,  # (B, KH, S, D)
    sm_scale: float | None = None,
    softcap: float = 0.0,                # Gemma2 score softcapping
    window: int = 0,                     # sliding-window size (0 = global)
    sliding: jnp.ndarray | None = None,  # traced per-layer bool for `window`
    sinks: jnp.ndarray | None = None,    # (H,) per-head sink logits (GPT-OSS)
    interpret: bool = False,
) -> jnp.ndarray:
    """Causal flash attention. S must be a multiple of BLOCK_Q; D a multiple
    of 128 (pad upstream). Returns (B, H, S, D) in q.dtype.

    Same Gemma/GPT-OSS variants as flash_decode: softcap, sliding window
    (the kernel skips KV blocks entirely before each query block's band —
    a sliding layer's prefill is O(S·window), not O(S²/2)), and sinks."""
    batch, num_heads, seq_len, head_dim = q.shape
    kv_heads = k.shape[1]
    assert num_heads % kv_heads == 0, "query heads must be a multiple of kv heads"
    group = num_heads // kv_heads
    if sm_scale is None:
        sm_scale = head_dim**-0.5

    # registry-resolved tiling (env > tuned artifact > 128 defaults), with
    # the same shape fallbacks as before: a preferred block_q that doesn't
    # divide the sequence drops back to the default
    pref_q = _resolve_block("flash_prefill", "block_q", BLOCK_Q)
    block_q = pref_q if seq_len % pref_q == 0 else BLOCK_Q
    block_k = min(_resolve_block("flash_prefill", "block_k", BLOCK_K), seq_len)
    # the kv-block axis is a GRID dimension (see flash_decode): the index
    # map clips each step into the query block's live [band_start,
    # causal_last] range, so blocks above the diagonal — and, on a sliding
    # layer, before the band — are never read from HBM, not just skipped in
    # compute. Causal prefill reads ~half the k/v bytes; a sliding layer
    # reads O(S*window).
    grid = (batch, num_heads, pl.cdiv(seq_len, block_q), pl.cdiv(seq_len, block_k))

    window_arr = _window_scalar(window, sliding)
    use_sinks, sinks_arr = _sinks_operand(sinks, num_heads, 1)

    def kv_map(b, h, qb, kb, win):
        band_start, causal_last = _prefill_band(qb, win, block_q, block_k)
        last = jnp.minimum(causal_last, pl.cdiv(seq_len, block_k) - 1)
        return (b, h // group, jnp.clip(kb, band_start, last), 0)

    kernel = functools.partial(
        _flash_kernel, sm_scale=sm_scale, block_q=block_q, block_k=block_k,
        softcap=softcap, use_sinks=use_sinks,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, head_dim), lambda b, h, qb, kb, *_: (b, h, qb, 0)),
            pl.BlockSpec((1, 1, block_k, head_dim), kv_map),
            pl.BlockSpec((1, 1, block_k, head_dim), kv_map),
            pl.BlockSpec((num_heads, 1), lambda b, h, qb, kb, *_: (0, 0)),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, block_q, head_dim), lambda b, h, qb, kb, *_: (b, h, qb, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),      # running max
            pltpu.VMEM((block_q, 128), jnp.float32),      # running denominator
            pltpu.VMEM((block_q, head_dim), jnp.float32),  # output accumulator
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        cost_estimate=pl.CostEstimate(
            flops=2 * 2 * batch * num_heads * seq_len * seq_len * head_dim // 2,  # causal half
            # causal halves the k/v bytes actually read too (the index-map
            # clip elides above-diagonal block copies) — keep flops and
            # bytes on the same convention
            bytes_accessed=(
                q.size + (k.size * group + v.size * group) // 2 + q.size
            ) * q.dtype.itemsize,
            transcendentals=batch * num_heads * seq_len * seq_len // 2,
        ),
        interpret=interpret,
    )(window_arr, q, k, v, sinks_arr)
