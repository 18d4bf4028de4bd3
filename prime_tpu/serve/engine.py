"""Continuous-batching inference engine: slot-based serving on static shapes.

The reference platform serves models behind a hosted OpenAI-compatible API
(reference: packages/prime/src/prime_cli/api/inference.py is the client side);
this module is the TPU-native serving interior that plays the server role
locally. Design follows the JetStream/vLLM-era insight adapted to XLA's
compilation model:

- **Slots, not requests.** The KV cache is one fixed (L, S, KH, hd, C) block
  where S = max concurrent slots. A request is *admitted* into a free slot
  (bucketed prefill writes its KV row), decoded as part of the batched decode
  program, and *retired* on EOS/max_tokens — the slot is immediately reusable
  while other slots keep decoding. No shape ever changes, so XLA compiles
  exactly one decode program plus one prefill program per prompt bucket.
- **Chunked decode.** Decode dispatches in chunks of T steps (one
  ``lax.scan``), amortizing host dispatch over T tokens while keeping
  admission latency bounded at T steps.
- **Per-slot sampling state is traced.** temperature/top_p enter as (S,)
  vectors, so requests with different sampling settings share one compiled
  program — a per-request recompile would defeat continuous batching. The
  nucleus (top-p) sort only runs when some active request asked for it
  (``lax.cond`` on the traced predicate).
- **One-chunk-deep decode pipeline.** JAX dispatch is asynchronous: a decode
  chunk's tokens stay on the device until the host asks for them. ``tick()``
  exploits that by dispatching chunk N+1 (using the last-known active mask)
  *before* fetching chunk N's tokens, so emit, EOS/budget retirement,
  cancellation sweeps, prefix indexing, and admission planning all execute
  inside the device-compute window instead of serializing with it.
  Retirement takes effect at the next chunk boundary — a slot that finished
  in chunk N still decodes through chunk N+1 (bounded waste, counted by
  ``serve_wasted_decode_tokens_total``). ``PRIME_SERVE_OVERLAP=0`` restores
  the strictly synchronous loop. See docs/architecture.md "Engine pipeline".
- **Device-resident speculative decoding.** ``speculative=True`` replaces
  the decode chunk with ONE fused dispatch: n-gram draft proposal over a
  per-slot device history ring (``models/speculative.propose_ngram_drafts``),
  a (S, D+1) verify forward, acceptance bookkeeping, and the history-ring
  update all execute inside the program — the host never reads tokens back
  to draft, so spec mode composes with the overlap pipeline (a spec chunk is
  dispatched on the last-known active mask exactly like a decode chunk) and
  with the sharded mesh. A retired-but-lagged slot wastes at most one
  accepted-length window (counted by ``serve_wasted_decode_tokens_total``),
  which is why admission reserves ``2*(draft_len+1)`` verify slots per row
  under overlap. See docs/architecture.md "Speculative decoding".

Single-chip by default. A **sharded replica** spans a multi-chip slice from
one declarative knob: ``mesh_config`` (a serve/mesh_config.ServeMeshConfig,
a ``"dp=1,fsdp=2,tp=2"`` spec string, or the ``PRIME_SERVE_MESH`` env
default behind ``prime serve --mesh``) makes the engine build the
``(dp, fsdp, tp[, sp])`` mesh itself, place params and the paged KV cache
as ``NamedSharding`` arrays, and pin staging rows/prefix segments to the
same layout so cache hits assemble without a gather-to-host. Decode
attention dispatches ``attn_impl="sharded"``: the flash kernel under
``shard_map`` (parallel/decode_sharded.py) when the TPU cache shape is
eligible, the SPMD-partitioned XLA path otherwise. The historical surface
— caller-sharded params plus explicit ``mesh`` + ``cache_spec`` — still
works and wins when both are given. See docs/architecture.md "Sharded
replica".

- **Batched multi-LoRA serving.** ``adapters={name: artifact dir}`` (or
  ``PRIME_SERVE_ADAPTERS``) loads a registry of LoRA adapters UNMERGED into
  a stacked device-resident A/B bank (serve/adapters.py); each slot carries
  an int32 adapter index next to the paged KV state, and every adapted
  projection fuses the gathered ``y += (x @ A[idx]) @ B'[idx]`` delta into
  the existing donated decode/spec/chunk-prefill dispatches — a
  mixed-adapter wave runs as ONE program, riding the overlap pipeline,
  speculative mode, and the sharded mesh unchanged. Admission is
  per-tenant fair (round-robin across per-adapter buckets, optional
  ``adapter_max_inflight`` cap), and the prefix cache keys each adapter's
  paths in a salted token space so cross-adapter KV reuse is impossible.
  See docs/architecture.md "Multi-LoRA serving".

- **Block-granular prefix reuse.** Prompt prefixes are cached in a radix
  tree of MIN_BUCKET-aligned KV segments (serve/prefix_cache.py) under a
  byte budget (``--prefix-cache-mb`` / ``PRIME_SERVE_PREFIX_CACHE_MB``):
  common blocks are stored once and shared by reference, matching is
  partial (two prompts sharing only a system preamble both hit), and a hit
  seeds its staging row with ONE jitted ``assemble_row`` dispatch instead
  of a per-leaf copy/pad chain. See docs/architecture.md "Prefix cache".

Observability: each engine owns a prime_tpu.obs metrics Registry (queue-wait
/ TTFT / per-token latency histograms next to the legacy counters) exposed
through the server's ``GET /metrics?format=prometheus``; see
docs/architecture.md "Observability". ``stats()`` returns the engine loop's
cross-field-consistent snapshot (refreshed at every tick under a small
lock), so an HTTP scrape never reads live counters mid-tick.
"""

from __future__ import annotations

import contextlib
import itertools
import math
import queue
import threading
import time
import warnings
from collections import deque
from dataclasses import dataclass, field
from typing import Any

import numpy as np

from prime_tpu.core.config import env_flag, env_float, env_int, env_str
from prime_tpu.obs.flight import FlightRecorder
from prime_tpu.obs.metrics import (
    DEFAULT_LATENCY_BUCKETS,
    DEFAULT_SIZE_BUCKETS,
    DEFAULT_TOKEN_BUCKETS,
    Registry,
)
from prime_tpu.obs.phase import PhaseClock
from prime_tpu.obs.profiler import DeviceProfiler
from prime_tpu.obs.trace import TRACER, TraceContext
from prime_tpu.serve.errors import DrainingError, QueueFullError
from prime_tpu.serve.prefix_cache import BlockPrefixCache

MIN_BUCKET = 16
NEG_INF = -1e30
# multi-LoRA prefix-key salt: a cached KV segment is only valid under the
# adapter that computed it, so non-base adapters store/match radix paths in
# a disjoint token-key space (token + idx*STRIDE — vocab ids never reach the
# stride, so adapters can never collide with each other or with base paths,
# and base traffic keeps byte-identical cache keys to a bankless engine)
ADAPTER_KEY_STRIDE = 1 << 32
# default byte budget for the radix prefix-KV cache: roughly what the old
# 4-entry whole-row list held for a 1B model at 2048-slot rows
DEFAULT_PREFIX_CACHE_MB = 256.0
# default host-RAM spill tier budget: 0 = disabled (device eviction deletes,
# the single-tier behavior). Host RAM is typically an order of magnitude
# larger than HBM, so deployments chasing millions-of-users prefix reuse set
# this to several GB (--prefix-cache-host-mb / PRIME_SERVE_PREFIX_CACHE_HOST_MB)
DEFAULT_PREFIX_CACHE_HOST_MB = 0.0
def bucket_for(length: int, capacity: int) -> int:
    """Smallest power-of-two bucket (>= MIN_BUCKET, <= capacity) holding
    ``length`` — bounds the number of compiled prefill programs."""
    if length > capacity:
        raise ValueError(f"prompt of {length} tokens does not fit capacity {capacity}")
    b = MIN_BUCKET
    while b < length:
        b *= 2
    return min(b, capacity)


def row_capacity_for(length: int, max_chunk: int, capacity: int) -> int:
    """Staging-row capacity for a prompt of ``length``: a power-of-two bucket
    up to ``max_chunk``, then multiples of ``max_chunk``. Every chunk_plan
    size (power of two <= max_chunk, self-aligned) divides this, which is the
    invariant that keeps chunk writes inside the row for ANY slot capacity —
    including non-power-of-two ones, where bucket_for alone would produce a
    row a mid-prompt chunk could overflow (dynamic_update_slice would then
    clamp the write while the attention mask assumed the true offset: silent
    KV corruption)."""
    if length <= max_chunk:
        row = MIN_BUCKET
        while row < length:
            row *= 2
    else:
        row = max_chunk * -(-length // max_chunk)
    if row > capacity:
        raise ValueError(
            f"prompt of {length} tokens needs a {row}-slot staging row, which "
            f"exceeds the slot capacity ({capacity}); raise --slot-capacity "
            f"to a multiple of the prefill chunk ({max_chunk})"
        )
    return row


def chunk_plan(start: int, length: int, max_chunk: int, row_capacity: int) -> list[tuple[int, int]]:
    """Buddy-style decomposition of [start, length) into (offset, size) prefill
    chunks: each chunk is a power of two, aligned to its own size, capped at
    ``max_chunk``. With ``row_capacity`` from row_capacity_for, every chunk
    size divides the row capacity, so offset+size never exceeds the row — a
    dynamic_update_slice can therefore never clamp — and the size set is
    O(log) distinct shapes, so chunked prefill compiles a bounded number of
    programs. ``start`` must be a multiple of MIN_BUCKET (align a prefix
    match down before calling). The final chunk may pad past ``length``; pad
    slots are masked by the true length downstream."""
    if start % MIN_BUCKET:
        raise ValueError(f"start ({start}) must be a multiple of {MIN_BUCKET}")
    plan = []
    off = start
    while off < length:
        size = min(max_chunk, row_capacity) if off == 0 else min(off & -off, max_chunk)
        plan.append((off, size))
        if off + size > row_capacity:  # invariant guard; unreachable via submit()
            raise AssertionError(
                f"chunk [{off}, {off + size}) overflows row capacity {row_capacity}"
            )
        off += size
    return plan


# what a prompt's chunk and an admission wave are sized from (``prefill_shapes``)
PREFILL_CHUNK = 512  # the chunk cap the rule starts from, and every dense model's
SCORE_BUDGET = 2**30  # bytes of fp32 attention scores one chunk-prefill program may hold


def row_capacities(max_chunk: int, capacity: int) -> list[int]:
    """Every staging-row capacity row_capacity_for can produce: powers of two
    up to ``max_chunk``, then its multiples up to the slot capacity: the
    bounded row set that keys the chunk-prefill and finalize program shapes."""
    rows: set[int] = set()
    r = MIN_BUCKET
    while r < max_chunk and r <= capacity:
        rows.add(r)
        r *= 2
    if r <= capacity:
        rows.add(r)  # smallest power of two >= max_chunk
    m = max_chunk * 2
    while m <= capacity:
        rows.add(m)
        m += max_chunk
    return sorted(rows)


def prefill_shapes(
    config: Any,
    capacity: int,
    *,
    prefill_chunk: int | None = None,
    max_wave: int | None = None,
    dropless_experts: bool = False,
) -> tuple[int, int]:
    """(chunk cap, widest admission wave) of an engine, from its shapes: THE
    rule; the waves ``_admit`` forms, the plans ``chunk_plan`` cuts, the row
    capacities and the warm-up's program set all read these two numbers.

    **Width first.** A chunk prefill's fp32 score tensor is wave x heads x
    chunk x row capacity (by kind of layer where a model has more than one: 48
    heads over a row of 8192 beside 72 over a ring of 512 and the chunk, the
    larger counts), and past ``SCORE_BUDGET`` (1 GiB) of it a wide
    model's wave no longer fits beside its weights: the widest wave is the
    largest power of two whose scores at a ``PREFILL_CHUNK``-token chunk (or
    the explicit ``prefill_chunk``) over a whole slot stay inside it (64 heads
    at 512 x 2048: 4 rows; 32 heads: 8). ``max_wave`` asks for narrower waves
    (a power of two, rounded down): the warm-up builds a chunk prefill and a
    finalize a row capacity AND width, so a deployment whose programs are slow
    to build or large to keep starts sooner on fewer widths. (Since PR 43 a
    full layer's scores are a column block at a time,
    ``ops/attention.py::cache_prefill_attention``, so the term prices a tensor
    that is gone and the rule is conservative; it stands so that every
    configuration keeps its chunks, waves and warm-up set: PERF.md section 7.)

    **Then length**, where nobody gave one. A chunk-prefill program reads
    every held expert's weights once whatever its length, and a dropless
    expert layer (ops/moe.py ``moe_infer``) gives an expert ``chunk x
    experts_per_token / n_experts`` of a prompt's rows to show for the read
    (512 x 10 / 512 = 10). Under one ``MOE_ROW_TILE`` of rows the grouped
    product computes a whole row tile for a part of one, so the read and the
    tile are both paid for rows that are not there. While an expert's rows a
    chunk do not fill a tile, and the scores of ``max_wave`` rows at twice the
    length still fit the budget, and a slot is a whole number of chunks of
    twice the length (rows are multiples of the cap: no prompt that fit is
    refused one), the chunk doubles. Past a full tile an expert's rows span
    more tile products (each tile a group overlaps is one) and a longer chunk
    buys nothing more: on the chip 16 heads at 4096 served the same token
    time at 2048 and at 4096 (PERF.md, PR 38), at twice the temporaries. So an
    expert model that spends its budget on width (64 heads at 2048: a wave of
    4) keeps 512, and one that gives width up gets length for it (16 heads at
    4096, ``max_wave`` 1: 2048 tokens, up to 40 rows an expert a read). A
    dense model's rows share its weights whatever the length, and a mesh or
    quantized experts keep the capacity einsum (``dropless_experts`` False):
    512.

    An explicit ``prefill_chunk`` stands as given (at least ``MIN_BUCKET``)."""
    from prime_tpu.ops.moe import MOE_ROW_TILE

    chunk = PREFILL_CHUNK if prefill_chunk is None else max(MIN_BUCKET, prefill_chunk)

    def scores(chunk: int) -> int:
        """Bytes of scores a row of the wave holds in its widest layer: heads x
        chunk x the columns that kind of layer attends over (a full layer the
        row's capacity; a window layer of a ``layer_types`` model its ring and
        the chunk's own columns, at its own head count)."""
        columns = [config.n_heads * capacity]
        if getattr(config, "window_layers", 0):
            heads = config.window_heads or config.n_heads
            columns.append(heads * (config.sliding_window + chunk))
        return 4 * chunk * max(columns)

    row_scores = scores(chunk)
    wave = max(1, 2 ** ((SCORE_BUDGET // row_scores).bit_length() - 1))
    if max_wave is not None:
        if max_wave < 1:
            raise ValueError(f"max_wave must be at least 1, got {max_wave}")
        wave = min(wave, 2 ** (max_wave.bit_length() - 1))
    if prefill_chunk is None and dropless_experts:
        while (
            chunk * config.experts_per_token < MOE_ROW_TILE * config.n_experts
            and wave * scores(2 * chunk) <= SCORE_BUDGET
            and capacity % (2 * chunk) == 0
        ):
            chunk *= 2
    return chunk, wave


def snapshot_stride(cache: Any, page: int = MIN_BUCKET) -> int:
    """The stride in tokens at which a prefix cache keeps snapshots of the
    state a slot of ``cache`` holds, from its shapes: the smallest power-of-two
    multiple of the prefix cache's page that is at least a snapshot's bytes
    over the K/V bytes a token. So a snapshot never costs more than the K/V
    of the tokens it rides with, and the cache's budget splits about evenly at
    worst. 0 where the slots hold no state. Olmo-Hybrid's 16 layers: 27.4 MB of
    state a slot over 61.4 KB of K/V a token is 446, so 512. Qwen3-Next's one
    period: 6.4 MB over 2 KB is 3144, so 4096, which is its slots' whole length:
    no prompt that fits reaches a boundary, and the engine refuses that model a
    prefix cache by name (PERF.md section 7)."""
    state, token = slot_bytes(cache)
    if not state:
        return 0
    stride = page
    while stride * token < state:
        stride *= 2
    return stride


def slot_bytes(cache: Any) -> tuple[int, int]:
    """(bytes of recurrent state a slot of ``cache`` holds, which is what one
    snapshot in the prefix cache takes; bytes of K/V a token), from the leaves'
    shapes."""
    held = cache_leaves(cache)

    def nbytes(leaf, *axes):  # of one slot (and one column) of a leaf
        return leaf.size * leaf.dtype.itemsize // math.prod(leaf.shape[a] for a in axes)

    state = sum(nbytes(leaf, spec.slot_axis) for leaf, spec in held.values() if spec.kind == "state")
    token = sum(
        nbytes(leaf, spec.slot_axis, spec.capacity_axis)
        for leaf, spec in held.values() if spec.capacity_axis is not None
    )
    return state, token


def _snapshot_leaves(cache: Any) -> dict[str, Any]:
    """The leaves of kind "state" that ``cache`` holds, by field name: what a
    snapshot is made of."""
    return {
        name: leaf for name, (leaf, spec) in cache_leaves(cache).items() if spec.kind == "state"
    }


def _parse_inject_spec(raw: str) -> tuple[float, int]:
    """Parse PRIME_SENTINEL_INJECT_MS: ``"MS"`` or ``"MS@AFTER"`` — a
    per-dispatch delay in milliseconds and the dispatch count after which
    it activates. Junk degrades to inactive (0.0, 0), matching utils/env
    knob semantics: a malformed knob must not take the engine down."""
    raw = raw.strip()
    if not raw:
        return 0.0, 0
    ms, _, after = raw.partition("@")
    try:
        delay_s = max(0.0, float(ms)) / 1e3
        start = max(0, int(after)) if after else 0
    except ValueError:
        return 0.0, 0
    return delay_s, start


def _power_batches(n: int, cap: int | None = None) -> list[int]:
    """Greedy power-of-two decomposition, largest first: 7 -> [4, 2, 1]; no
    part above ``cap``: 9 under a cap of 4 -> [4, 4, 1]."""
    out = []
    p = 1
    while p * 2 <= (n if cap is None else min(n, cap)):
        p *= 2
    while n:
        if p <= n:
            out.append(p)
            n -= p
        else:
            p //= 2
    return out


def cache_leaves(cache: Any) -> dict[str, tuple[Any, Any]]:
    """``{field: (leaf, its models.llama.CacheLeaf)}`` of the leaves ``cache``
    holds (lengths aside): THE walk over a cache. What a leaf is (its slot
    axis, whether it grows with the capacity, what memory reports it as) is
    read from the declaration beside ``init_cache``, never from the model's
    kind."""
    from prime_tpu.models.llama import CACHE_LEAVES

    return {
        name: (leaf, spec)
        for name, spec in CACHE_LEAVES.items()
        if (leaf := getattr(cache, name, None)) is not None
    }


def _capacity_leaves(cache: Any) -> dict[str, Any]:
    """The capacity-axis leaves ``cache`` holds, by field name: what the prefix
    cache cuts into segments (a bf16 cache has no scales)."""
    return {
        name: leaf for name, (leaf, spec) in cache_leaves(cache).items()
        if spec.capacity_axis is not None
    }


def _map_cache_leaves(fn: Any, cache: Any, *others: Any, capacity_only: bool = False) -> Any:
    """``cache`` with ``fn(spec, leaf, *the same leaf of others)`` in place of
    each leaf: the one walk behind placing, pinning and splicing a cache
    (lengths is per slot and stays). ``capacity_only`` leaves out the leaves
    that have no capacity axis: a mesh's cache spec describes the
    (L, B, KH, D, C) leaves and no other."""
    return cache._replace(**{
        name: fn(spec, leaf, *(getattr(other, name) for other in others))
        for name, (leaf, spec) in cache_leaves(cache).items()
        if not capacity_only or spec.capacity_axis is not None
    })


def _constrain(cache: Any, constraint: Any) -> Any:
    """Pin the capacity-axis leaves of ``cache`` (the engine's cache or a
    staging row) to ``constraint`` inside a traced program; None pins
    nothing."""
    if constraint is None:
        return cache
    import jax

    return _map_cache_leaves(
        lambda _, leaf: jax.lax.with_sharding_constraint(leaf, constraint),
        cache, capacity_only=True,
    )


def _segment_to_host(segment: Any) -> Any:
    """Spill-tier demotion: device KV slices -> host-RAM copies. device_get
    blocks until the segment's producing dispatch finishes and lands plain
    numpy arrays in host memory (on runtimes with a pinned-host allocator the
    transfer staging is pinned; the cache only needs the bytes off HBM).
    Paged segments materialize to a loose dict first and return their pages
    to the pool — the host tier holds bytes, never page ids, so a later
    promote comes back as a loose device segment (the copy seeding path),
    matching the 'host-resident -> fallback' contract."""
    import jax

    if hasattr(segment, "materialize"):
        host = jax.device_get(segment.materialize())
        segment.close()
        return host
    return jax.device_get(segment)


def _segment_to_device(segment: Any) -> Any:
    """Spill-tier promotion: host copies -> device arrays, ready for the
    jitted assemble_row dispatch (shapes/dtypes round-trip exactly, so the
    assemble program cache keys are identical to never-spilled segments)."""
    import jax
    import jax.numpy as jnp

    return jax.tree_util.tree_map(jnp.asarray, segment)


@dataclass
class _InflightChunk:
    """A dispatched-but-unfetched decode chunk. ``mask`` and ``requests``
    are snapshots from dispatch time: between dispatch and sync, slots may
    retire and even be re-admitted to NEW requests, and chunk tokens must
    only ever reach the request that was decoding when the chunk launched."""

    seq: int
    toks: Any  # (S, T) device array — a future until synced
    mask: np.ndarray
    requests: dict[int, EngineRequest]
    dispatched_at: float
    # speculative chunks only: the (S,) per-slot accepted-run lengths (device
    # array, synced with toks). None marks a plain decode chunk whose every
    # row holds `chunk` valid tokens.
    run_len: Any = None
    # MoE engines: the chunk's dropless expert-layer counts (ops.moe.MOE_STATS,
    # a small device vector synced with toks); None where no such layer ran
    moe_stats: Any = None
    # plain decode chunks: the (2,) device vector of the attention kernel's grid
    # steps, made and at most, summed over the chunk (forward's
    # return_attn_steps; synced with toks)
    attn_steps: Any = None
    # the same chunks: the (2,) device vector of the column-write kernel's rows,
    # written and offered (forward's return_column_writes)
    column_writes: Any = None
    # the gated_delta block's decode chunks: the same pair for its state kernel
    # (forward's return_state_steps)
    state_steps: Any = None
    # a layer_types model's decode chunks: the cache columns its attentions
    # read, in full rows, in rings, and beyond the window (host integers from
    # the dispatch's mask and lengths, ``_attended_columns``)
    attn_columns: tuple[int, int, int] | None = None
    # False once an admission prefill ran inside this chunk's window: its
    # dispatch-to-sync wall time then includes host prefill blocking and must
    # not feed the per-step decode histogram
    clean: bool = True


@dataclass
class EngineRequest:
    """One in-flight generation. ``events`` receives lists of token ids as
    they decode, then ``None`` when the request is finished."""

    id: int
    prompt_ids: list[int]
    max_new_tokens: int
    temperature: float
    top_p: float
    # multi-LoRA: the adapter this request selected (None = base) and its
    # resolved bank slot (0 = base) — per-slot gathered matmuls key on it
    adapter: str | None = None
    adapter_idx: int = 0
    events: queue.Queue = field(default_factory=queue.Queue)
    emitted: int = 0
    # decode steps dispatched for it so far, ahead of ``emitted`` by what is in
    # flight (counted for a layer_types model only: ``_attended_columns``)
    steps_dispatched: int = 0
    slot: int = -1
    done: bool = False
    cancelled: bool = False
    error: str | None = None
    # monotonic-clock request timeline (obs histograms: queue wait = admitted
    # - submitted, TTFT = first token - submitted, TPOT over the decode tail)
    submitted_at: float = 0.0
    admitted_at: float = 0.0
    first_token_at: float = 0.0
    # W3C trace context from the inbound hop (server → submit): engine spans
    # for this request join the caller's distributed trace through it
    trace: TraceContext | None = None

    def cancel(self) -> None:
        """Abandon the request (e.g. the streaming client disconnected). The
        engine retires the slot at the next chunk boundary instead of decoding
        the rest of max_new_tokens for nobody."""
        self.cancelled = True

    def tokens(self, timeout: float | None = 120.0):
        """Iterate over token-id batches until the request finishes.
        ``timeout`` bounds the wait for each event; on expiry the request is
        cancelled (so the engine stops decoding for nobody) and a descriptive
        TimeoutError raised instead of a bare queue.Empty."""
        while True:
            try:
                item = self.events.get(timeout=timeout)
            except queue.Empty:
                self.cancel()
                raise TimeoutError(
                    f"no tokens within {timeout}s (queued behind busy slots "
                    "or a slow first-compile); request cancelled"
                ) from None
            if item is None:
                if self.error:
                    raise RuntimeError(self.error)
                return
            yield item

    def all_tokens(self, timeout: float | None = 120.0) -> list[int]:
        out: list[int] = []
        for batch in self.tokens(timeout=timeout):
            out.extend(batch)
        return out


class ContinuousBatchingEngine:
    """Slot-based continuous batching over prime_tpu.models.llama.

    Thread model: callers ``submit()`` from any thread; one background engine
    thread (``start()``) owns all device state and alternates admission
    (prefill) with decode chunks. Tests drive it synchronously with ``tick()``.

    ``prefill_chunk`` (the longest chunk of a prompt's plan) and ``max_wave``
    (the widest admission wave) default to None: ``prefill_shapes``, the rule
    from shapes, whose docstring is the one place that says it.
    """

    def __init__(
        self,
        params: Any,
        config: Any,
        *,
        eos_id: int = -1,
        pad_id: int = 0,
        max_slots: int = 8,
        capacity: int = 2048,
        chunk: int = 8,
        prefill_chunk: int | None = None,
        prefix_cache_mb: float | None = None,
        prefix_cache_host_mb: float | None = None,
        min_prefix: int = MIN_BUCKET,
        mesh: Any = None,
        mesh_config: Any = None,
        cache_spec: Any = None,
        attn_impl: str = "auto",
        kv_quant: bool = False,
        speculative: bool | None = None,
        draft_len: int | None = None,
        overlap: bool | None = None,
        warmup: bool | None = None,
        profile: bool | None = None,
        max_queue: int | None = None,
        max_wave: int | None = None,
        prefix_store_all: bool = False,
        paged_prefix: bool | None = None,
        adapters: Any = None,
        adapter_max_inflight: int | None = None,
        adapter_weights: Any = None,
        registry: Registry | None = None,
    ) -> None:
        import jax
        import jax.numpy as jnp

        from prime_tpu.models.llama import init_cache

        self.params = params
        self.config = config
        self.eos_id = eos_id
        self.pad_id = pad_id
        self.max_slots = max_slots
        self.capacity = capacity
        self.chunk = chunk
        # declarative sharded replica (docs/architecture.md "Sharded
        # replica"): a mesh_config — a ServeMeshConfig, a "--mesh"-style
        # spec string, or the PRIME_SERVE_MESH env default — makes THIS
        # engine span a multi-chip slice. The engine does the placement
        # itself: params go down as NamedSharding-placed arrays
        # (parallel.sharding.shard_params) and the cache spec derives from
        # cache_spec_for pruned to the mesh, so callers declare a topology
        # instead of pre-sharding pytrees. An explicit `mesh` kwarg (the
        # historical surface: caller shards params, passes cache_spec) wins.
        if mesh is None:
            from prime_tpu.serve.mesh_config import ServeMeshConfig, parse_mesh_spec

            if mesh_config is None:
                mesh_config = env_str("PRIME_SERVE_MESH", "")
            if isinstance(mesh_config, str):
                mesh_config = parse_mesh_spec(mesh_config, jax.device_count())
            if mesh_config is not None and not isinstance(mesh_config, ServeMeshConfig):
                raise TypeError(
                    "mesh_config must be a ServeMeshConfig or a spec string "
                    f"like 'dp=1,fsdp=2,tp=2', got {type(mesh_config).__name__}"
                )
            if mesh_config is not None and mesh_config.total_devices > 1:
                from prime_tpu.parallel.sharding import serving_cache_spec, shard_params

                mesh = mesh_config.build()
                params = shard_params(params, mesh, config)
                self.params = params
                if cache_spec is None:
                    cache_spec = serving_cache_spec(config, mesh)
        self.mesh = mesh
        self.cache_spec = cache_spec
        self.mesh_devices = int(getattr(mesh, "size", 1) or 1) if mesh is not None else 1
        self.mesh_axes: dict[str, int] = {
            str(k): int(v) for k, v in dict(getattr(mesh, "shape", None) or {}).items()
        }
        # a pallas_call cannot partition under SPMD jit: a multi-device mesh
        # takes the "sharded" dispatch — decode attention runs the flash
        # kernel under shard_map (parallel/decode_sharded.py) when eligible
        # and falls back to the SPMD-safe XLA einsum path everywhere else
        # (same divisibility rules as evals.runner.JaxGenerator)
        if mesh is not None and getattr(mesh, "size", 1) > 1 and attn_impl == "auto":
            attn_impl = "sharded"
        # int8 caches ride the flash kernel on single-device engines (auto
        # dispatch, round 4); on meshes the "sharded" dispatch above falls
        # back to the SPMD-safe XLA path for them (the shard_map wrapper
        # does not plumb the scale epilogue yet)
        self.attn_impl = attn_impl
        self.kv_quant = kv_quant
        # multi-LoRA adapter bank (serve/adapters.py, docs/architecture.md
        # "Multi-LoRA serving"): a {name: artifact dir} registry (or a
        # "name=path,..." spec string; None reads PRIME_SERVE_ADAPTERS)
        # loads UNMERGED into stacked (L, A, ...) device buffers — every
        # adapted projection runs y = x@W + (x@A[idx])@B'[idx] with idx the
        # per-slot int32 adapter index living next to the paged KV state,
        # so a mixed-adapter wave is ONE program. Bank slot 0 is the
        # all-zeros base adapter: a bankless engine and base traffic on a
        # banked engine emit bit-identical tokens. An AdapterBank instance
        # passes through as-is (tests build tiny banks directly).
        from prime_tpu.serve.adapters import AdapterBank, load_adapter_bank, parse_adapter_spec

        if adapters is None:
            adapters = env_str("PRIME_SERVE_ADAPTERS", "")
        if isinstance(adapters, str):
            adapters = parse_adapter_spec(adapters)
        if isinstance(adapters, AdapterBank):
            self.adapter_bank: AdapterBank | None = adapters
        elif adapters:
            self.adapter_bank = load_adapter_bank(
                adapters, self.params, config, mesh=mesh,
                dtype=jax.tree_util.tree_leaves(self.params)[0].dtype,
            )
        else:
            self.adapter_bank = None
        # the stacks pytree every compiled program takes next to params
        # (None = empty pytree: the jitted signatures stay uniform and XLA
        # prunes the unused adapter-id input on bankless engines)
        self._adapters = self.adapter_bank.stacks if self.adapter_bank else None
        # per-tenant fair admission (the PR 4 queue gates, one level down):
        # with a bank, _pop_pending drains the ingress queue into per-adapter
        # buckets and round-robins across them, skipping adapters already at
        # adapter_max_inflight admitted slots (0 = uncapped). None reads
        # PRIME_SERVE_ADAPTER_MAX_INFLIGHT.
        if adapter_max_inflight is None:
            adapter_max_inflight = env_int("PRIME_SERVE_ADAPTER_MAX_INFLIGHT", 0)
        self.adapter_max_inflight = max(0, int(adapter_max_inflight))
        # fairness buckets: adapter idx -> FIFO of popped-but-unadmitted
        # requests, plus the round-robin cursor. The DICT is fixed at
        # construction (one bucket per bank slot, never inserted into or
        # deleted from): queue_depth()/drained read it from HTTP handler
        # threads while the engine thread mutates the deques, and a
        # size-stable dict is what makes those cross-thread iterations safe
        # (deque append/popleft/len are atomic under the GIL).
        self._fair: dict[int, deque[EngineRequest]] = {
            i: deque() for i in range(len(self.adapter_bank or ()))
        }
        # WEIGHTED shares (ROADMAP item 3 follow-up): "name=K,..." (or a
        # {name: K} dict; None reads PRIME_SERVE_ADAPTER_WEIGHTS) gives a
        # tenant K pops per rotation instead of 1 — `base` is tenant 0 and
        # may carry its own share; unlisted tenants default to 1. The pop
        # runs smooth weighted round-robin (nginx's algorithm): per-tenant
        # credit accumulates by weight, the richest credit pops and pays
        # the candidates' total back — deterministic, well-interleaved
        # (weight 2 serves a-a-b never starves b), and with uniform weights
        # it IS the plain rotation the unweighted engine ran.
        from prime_tpu.serve.adapters import parse_adapter_weights

        if adapter_weights is None:
            adapter_weights = env_str("PRIME_SERVE_ADAPTER_WEIGHTS", "")
        if isinstance(adapter_weights, str):
            adapter_weights = parse_adapter_weights(adapter_weights)
        self.adapter_weights: dict[str, int] = {}
        self._fair_weights: dict[int, int] = {i: 1 for i in self._fair}
        if adapter_weights:
            if self.adapter_bank is None:
                raise ValueError(
                    "adapter_weights needs a multi-LoRA adapter bank "
                    "(weighted shares split tenants; a bankless engine has one)"
                )
            for name, weight in adapter_weights.items():
                # KeyError on an unknown name -> loud config error at
                # construction, same as an unknown adapter path
                idx = self.adapter_bank.index_of(None if name == "base" else name)
                self._fair_weights[idx] = max(1, int(weight))
                self.adapter_weights[name] = max(1, int(weight))
        self._fair_credit: dict[int, int] = {i: 0 for i in self._fair}
        self._burst_pops: dict[int, int] = {}  # reset per _admit wave
        # prompt-lookup speculation: each spec chunk is ONE fused dispatch —
        # propose draft_len n-gram drafts per slot from the slot's device-
        # resident history ring, run one (S, D+1) verify forward, and fold
        # acceptance bookkeeping + the history update into the same program.
        # The host only ever reads the RESULT (tokens + run lengths), never
        # feeds drafts in, so speculation pipelines like a decode chunk.
        if speculative is None:
            speculative = env_flag("PRIME_SERVE_SPEC", False)
        self.speculative = bool(speculative)
        if draft_len is None:
            draft_len = env_int("PRIME_SERVE_DRAFT_LEN", 4)
        self.draft_len = max(1, int(draft_len))
        # overlapped decode pipeline (module docstring): on by default,
        # PRIME_SERVE_OVERLAP=0 restores the synchronous loop. Speculative
        # mode rides the same pipeline since drafting moved on-device (the
        # historical serial-loop pin existed because drafts needed chunk N's
        # tokens on the host).
        if overlap is None:
            overlap = env_flag("PRIME_SERVE_OVERLAP", True)
        self.overlap = bool(overlap)
        # AOT-style warmup (see warmup()): opt-in via PRIME_SERVE_WARMUP
        # because compiling the full program set up front trades startup
        # seconds for the guarantee that no cold compile lands mid-pipeline
        if warmup is None:
            warmup = env_flag("PRIME_SERVE_WARMUP", False)
        self.warmup_enabled = bool(warmup)
        # device-time observatory (obs/profiler.py): opt-in via
        # PRIME_SERVE_PROFILE because each step-clock sample fences the
        # pipeline; off means the dispatch path gains zero syncs (the
        # profiler object itself always exists so /admin/profile can open a
        # capture window on a live engine)
        if profile is None:
            profile = env_flag("PRIME_SERVE_PROFILE", False)
        self.profile_enabled = bool(profile)
        # dispatched-but-unfetched decode chunks, oldest first (depth <= 1
        # outside tick(); owned by the engine thread)
        self._inflight: list[_InflightChunk] = []
        self._chunk_seq = itertools.count()

        self._dtype = jax.tree_util.tree_leaves(params)[0].dtype
        self._requests: dict[int, EngineRequest] = {}  # slot -> request
        self._active = np.zeros((max_slots,), dtype=bool)  # host-side admission map
        self._rng = jax.random.PRNGKey(0)
        self._init_device_state()
        # submit()/shutdown() set this to wake an idle engine loop; the loop
        # never pops the queue outside tick()'s _admit (a popped-but-unadmitted
        # request held on the loop's stack would be invisible to `drained`)
        self._wake = threading.Event()
        # True while tick() runs: _admit holds popped-but-unregistered
        # requests in locals mid-tick, so drain-completion checks must not
        # trust the (momentarily empty) queue/slot structures until the tick
        # finishes (GIL ordering makes the flag visible before the pop is)
        self._tick_busy = False
        # admission control: a bounded pending queue. submit() past the bound
        # raises QueueFullError (the server maps it to 429 + Retry-After)
        # instead of queueing unboundedly — under sustained overload an
        # unbounded queue converts every request into a timeout, the worst of
        # both worlds. 0 = unbounded (the historical behavior).
        if max_queue is None:
            max_queue = env_int("PRIME_SERVE_MAX_QUEUE", 0)
        self.max_queue = max(0, int(max_queue))
        # drain: set by drain(); submit() refuses new work (DrainingError)
        # while the loop keeps ticking until in-flight requests finish
        self._draining = False
        self._pending: queue.Queue[EngineRequest | None] = queue.Queue()
        # prefix-KV wire jobs (export/import for disaggregated serving):
        # HTTP handler threads enqueue, the engine loop executes — the radix
        # tree is engine-thread-owned, so /admin/kv must marshal onto the
        # loop instead of walking it cross-thread
        self._kv_jobs: queue.Queue = queue.Queue()
        # requests the idle loop popped and handed back for batched
        # admission: consumed by _admit before _pending (engine thread only)
        self._requeued: deque[EngineRequest] = deque()
        self._ids = itertools.count()
        self._thread: threading.Thread | None = None
        self._running = False
        # one jitted program each: jit's own shape-keyed cache gives
        # one-compile-per-shape-bucket without bucket-keyed dicts here
        self._chunk_fn: Any = None
        self._finalize_batch_fn: Any = None
        self._decode_fn: Any = None
        self._spec_fn: Any = None
        self._hist_seed_fn: Any = None
        self._assemble_fn: Any = None
        # prompt-prefix KV reuse: a radix tree of MIN_BUCKET-aligned KV
        # segments under a byte budget (serve/prefix_cache.py) — an admission
        # whose prompt shares cached blocks assembles them into its staging
        # row with one jitted dispatch and only prefills the suffix.
        # prefix_cache_mb=0 disables; None reads PRIME_SERVE_PREFIX_CACHE_MB.
        # prefix_cache_host_mb > 0 adds the host-RAM spill tier: the device
        # LRU demotes segments to host buffers instead of freeing them, and a
        # hit on a host-resident node re-uploads through the same one-dispatch
        # assemble path (None reads PRIME_SERVE_PREFIX_CACHE_HOST_MB; 0 = off).
        # the chunk cap and the widest admission wave: one rule from shapes,
        # prefill_shapes (its docstring says it). None = the rule; an explicit
        # prefill_chunk stands, an explicit max_wave narrows.
        from prime_tpu.models.llama import runs_dropless_experts

        self.prefill_chunk, self.max_wave = prefill_shapes(
            config, capacity, prefill_chunk=prefill_chunk, max_wave=max_wave,
            dropless_experts=self.mesh is None and runs_dropless_experts(params, config),
        )
        # the dropless expert layer (ops/moe.py moe_infer) returns its counts
        # with every decode chunk and prefill (zeros on engines whose forward
        # keeps the capacity einsum: a mesh, quantized experts)
        self._moe = bool(getattr(config, "is_moe", False))
        self.min_prefix = max(min_prefix, MIN_BUCKET)
        # what a slot holds beside K/V columns, from the cache's declaration:
        # leaves with no capacity axis (a ring, a recurrent state) move whole
        held = cache_leaves(self._cache)
        whole = sorted(n for n, (_, spec) in held.items() if spec.capacity_axis is None)
        self._state_leaves = any(spec.kind == "state" for _, spec in held.values())
        self._ring_columns = next(
            (leaf.shape[-1] for leaf, spec in held.values() if spec.kind == "ring"), 0
        )
        rings = sorted(n for n, (_, spec) in held.items() if spec.kind == "ring")
        if config.layer_types is not None and mesh is not None and getattr(mesh, "size", 1) > 1:
            raise ValueError(
                f"a layer_types model ({config.name}) does not serve a mesh: its rings and "
                "expert share have no sharding rule (ROADMAP.md R7)"
            )
        if whole and self.speculative:
            # a verify window that is rejected would have to rewind what has no
            # columns to drop (ROADMAP R7: speculation beside state)
            raise ValueError(
                f"a {config.block!r} model's slots hold what is not K/V columns "
                f"({', '.join(whole)}): speculative decoding cannot rewind it. "
                "Serve without --speculative"
            )
        if rings and prefix_cache_mb:
            # a ring holds the last `window` columns wherever a prompt ended: a
            # stored K/V segment says nothing of it, and the prefix cache keeps
            # snapshots of recurrent state only (ROADMAP R7: snapshots for a ring)
            raise ValueError(
                f"a {config.block!r} model's slots hold a ring ({', '.join(rings)}): the "
                "prefix cache stores K/V segments and snapshots of recurrent state, and "
                "no ring. Serve with prefix_cache_mb=0 (--prefix-cache-mb 0)"
            )
        if whole and prefix_cache_mb is None:
            # the default budget is sized for K/V segments; a snapshot of state is
            # tens of megabytes, so such a model's prefix cache is asked for by size
            prefix_cache_mb = 0.0
        if prefix_cache_mb is None:
            prefix_cache_mb = env_float(
                "PRIME_SERVE_PREFIX_CACHE_MB", DEFAULT_PREFIX_CACHE_MB
            )
        self.prefix_cache_mb = float(prefix_cache_mb)
        if prefix_cache_host_mb is None:
            prefix_cache_host_mb = env_float(
                "PRIME_SERVE_PREFIX_CACHE_HOST_MB", DEFAULT_PREFIX_CACHE_HOST_MB
            )
        self.prefix_cache_host_mb = float(prefix_cache_host_mb)
        # role-tuned store policy (docs/architecture.md "Disaggregated
        # serving"): batched admission waves store only member 0's prefix by
        # default (slicing every member costs per-leaf tree ops per request,
        # and colocated serving only needs the recurring-preamble hit). A
        # PREFILL-role replica's whole job is producing exportable KV — with
        # prefix_store_all every wave member's row is stored, so a migrated
        # request's GET /admin/kv always finds its path whether admission
        # batched it or not. serve_model flips this on for --role prefill.
        self.prefix_store_all = bool(prefix_store_all)
        # where the slots hold recurrent state, the prefix cache keeps snapshots
        # of it at this stride (snapshot_stride: a rule from shapes). The state
        # is in the row at a chunk's end, so with such a cache on, no chunk is
        # longer than the stride: every boundary is then the end of a chunk of
        # real tokens (chunks are powers of two aligned to their own size), and
        # the chunked delta rule need not hand states out from inside a chunk.
        # Olmo-Hybrid's stride is its chunk cap (512 = 512), so its plan does not
        # change for the cache
        self.state_stride = snapshot_stride(self._cache) if self._state_leaves else 0
        if self.state_stride and self.prefix_cache_mb > 0:
            if self.state_stride >= self.capacity:
                # no prompt that fits a slot reaches the first boundary: the cache
                # would store every prompt's K/V and never start from any of it
                raise ValueError(
                    f"a {config.block!r} model's prefix cache keeps a snapshot of its state "
                    f"every {self.state_stride} tokens (a snapshot's bytes over the K/V bytes "
                    f"a token), and its slots hold {self.capacity}: no prompt reaches a "
                    "snapshot, so nothing stored could be started from. Serve with "
                    "prefix_cache_mb=0 (--prefix-cache-mb 0) (ROADMAP.md R7)"
                )
            self.prefill_chunk = min(self.prefill_chunk, self.state_stride)
        if self._state_leaves and self.prefix_cache_mb > 0 and self.prefix_cache_host_mb > 0:
            raise ValueError(
                "snapshots of state have no host tier: serve a model whose slots hold "
                "recurrent state with prefix_cache_host_mb=0 (ROADMAP.md R7)"
            )
        self._host_tier_gated = False
        if self.prefix_cache_host_mb > 0 and mesh is not None and getattr(mesh, "size", 1) > 1:
            # the spill tier's converters are not sharding-preserving:
            # device_get raises on non-fully-addressable multi-host arrays,
            # and a plain asarray re-upload would drop cache_spec (forcing a
            # fresh assemble_row compile and an unconstrained seeded row).
            # Until segments spill sharding-aware (ROADMAP Open item 1),
            # multi-device engines keep the single-tier cache.
            warnings.warn(
                "prefix_cache_host_mb > 0 is not supported with a multi-device "
                "mesh yet; disabling the host spill tier for this engine",
                stacklevel=2,
            )
            self.prefix_cache_host_mb = 0.0
            # remembered for the serve_prefix_host_tier_disabled gauge and
            # the stats() key below (the registry doesn't exist yet here):
            # an operator who configured a host tier must see the gate in
            # metrics, not only in a startup log line that scrolled away
            self._host_tier_gated = True
        self.prefix_cache: BlockPrefixCache | None = (
            BlockPrefixCache(
                int(self.prefix_cache_mb * 2**20), block=MIN_BUCKET,
                host_budget_bytes=int(self.prefix_cache_host_mb * 2**20),
                to_host=_segment_to_host, to_device=_segment_to_device,
                state_stride=self.state_stride,
            )
            if self.prefix_cache_mb > 0
            else None
        )
        # paged prefix KV (docs/kernels.md "Kernel campaign & autotune"):
        # device-resident cached segments live as fixed MIN_BUCKET-token
        # pages in a pooled buffer (serve/kv_pool.PagedKVPool); hit-seeding
        # gathers the pages straight into the decode row via the paged-gather
        # kernel's scalar-prefetched page table, skipping assemble_row's
        # contiguous copy. Copy path remains the fallback for host-resident
        # matches and segments the pool couldn't hold. Gated off under a
        # mesh: the bare pallas_call cannot partition under SPMD and the
        # gathered row would drop the cache_spec constraint (same rule as the
        # flash-kernel dispatch above).
        if paged_prefix is None:
            paged_prefix = env_flag("PRIME_SERVE_PAGED_PREFIX", True)
        self.paged_prefix = (
            bool(paged_prefix) and self.prefix_cache is not None and mesh is None
        )
        self._kv_pool = None  # lazy: leaf specs known at first stored segment
        self._snapshot_fn: Any = None
        self._restore_fn: Any = None
        # observability: registry-backed counters + latency histograms
        # (surfaced by stats(), the server's /metrics JSON, and the
        # Prometheus exposition at /metrics?format=prometheus). One Registry
        # per engine — its single lock makes every stats() read mutually
        # consistent across counters (closes the ADVICE r5 note about
        # cross-field inconsistency of the old bare ints).
        self.registry = registry if registry is not None else Registry()
        r = self.registry
        self._m_admitted = r.counter(
            "serve_requests_admitted_total", "Requests admitted into a KV slot"
        )
        self._m_completed = r.counter(
            "serve_requests_completed_total", "Requests finished (EOS or max_tokens)"
        )
        self._m_cancelled = r.counter(
            "serve_requests_cancelled_total", "Requests abandoned by their client"
        )
        self._m_failed = r.counter(
            "serve_requests_failed_total", "Requests failed by a dead dispatch"
        )
        self._m_tokens = r.counter(
            "serve_tokens_emitted_total", "Decoded tokens delivered to clients"
        )
        self._m_prefix_hits = r.counter(
            "serve_prefix_hits_total", "Admissions seeded from the prefix-KV cache"
        )
        self._m_prefix_hit_tokens = r.histogram(
            "serve_prefix_hit_tokens",
            "Cached tokens reused per prefix hit, by serving tier "
            "(device = assembled from HBM, host = re-uploaded from the spill tier)",
            buckets=DEFAULT_TOKEN_BUCKETS, labelnames=("tier",),
        )
        self._m_prefix_bytes = r.gauge(
            "serve_prefix_cache_bytes", "Device bytes held by cached KV segments"
        )
        self._m_prefix_host_bytes = r.gauge(
            "serve_prefix_cache_host_bytes",
            "Host-RAM bytes held by spilled KV segments",
        )
        self._m_prefix_nodes = r.gauge(
            "serve_prefix_cache_nodes", "Segment nodes in the prefix radix tree (both tiers)"
        )
        self._m_prefix_host_nodes = r.gauge(
            "serve_prefix_cache_host_nodes", "Host-tier segment nodes in the radix tree"
        )
        self._m_prefix_evictions = r.counter(
            "serve_prefix_evictions_total",
            "Segment nodes deleted outright by the byte-budget LRU",
        )
        self._m_prefix_spills = r.counter(
            "serve_prefix_spills_total",
            "Segments demoted from device HBM to the host-RAM spill tier",
        )
        self._m_prefix_spilled_bytes = r.counter(
            "serve_prefix_spilled_bytes_total", "Bytes demoted to the host spill tier"
        )
        self._m_prefix_reuploads = r.counter(
            "serve_prefix_reuploads_total",
            "Host-resident segments re-uploaded to device for a prefix hit",
        )
        self._m_prefix_reupload_bytes = r.counter(
            "serve_prefix_reupload_bytes_total", "Bytes re-uploaded from the host spill tier"
        )
        # snapshots of recurrent state on the radix tree's nodes (a model whose
        # slots hold state; all zero for a K/V-only one)
        self._m_state_snapshots = r.counter(
            "serve_prefix_state_snapshots_total",
            "Snapshots of a slot's recurrent state in the prefix cache, by event: "
            "stored | restored (copied into an admission's row) | evicted (with their node)",
            labelnames=("event",),
        )
        self._m_prefix_state_bytes = r.gauge(
            "serve_prefix_state_bytes",
            "Device bytes of state snapshots in the prefix cache (a part of serve_prefix_cache_bytes)",
        )
        self._m_prefix_match_tokens = r.counter(
            "serve_prefix_match_tokens_total",
            "Tokens of K/V the radix walk matched at an admission, by kind: used (seeded; "
            "under a snapshot where the slots hold state) | beyond_snapshot (matched past "
            "the deepest snapshot, or on a path with none: prefilled again)",
            labelnames=("kind",),
        )
        self._m_state_starts = r.counter(
            "serve_state_starts_total",
            "Admissions into slots that hold recurrent state, by where the state "
            "started from: zero | snapshot",
            labelnames=("from",),
        )
        self._m_prefix_assembles = r.counter(
            "serve_prefix_assembles_total",
            "assemble_row dispatches (one per COPY-path prefix-seeded admission)",
        )
        self._m_prefix_paged_seeds = r.counter(
            "serve_prefix_paged_seeds_total",
            "Prefix hits seeded by the paged-gather path (pool pages gathered "
            "in place; no assemble_row copy)",
        )
        self._m_prefix_seed_s = r.histogram(
            "serve_prefix_seed_seconds",
            "Hit-seeding dispatch wall time by path (paged = pooled page "
            "gather, copy = contiguous assemble_row)",
            labelnames=("path",),
        )
        # which tier feeds pallas block-size resolution on this replica
        # (ops/kernel_configs.py): 0 = built-in defaults, 1 = tuned
        # per-device-kind artifact, 2 = a PRIME_TPU_BLOCK_* env override
        from prime_tpu.ops import kernel_configs

        self._m_kernel_config_source = r.gauge(
            "serve_kernel_config_source",
            "Kernel block-config resolution tier "
            "(0=default, 1=tuned artifact, 2=env override)",
        )
        self._m_kernel_config_source.set(
            {"default": 0, "tuned": 1, "env": 2}[kernel_configs.source()]
        )
        # the tiling the decode-attention kernel resolved for the served cache
        # (ops/pallas_attention.py::decode_tiling): grid steps a call, slots a
        # cache block, kv heads a step; all 0 when decode takes an XLA path
        from prime_tpu.ops.attention import decode_kernel_tiling

        grid_steps, block_c, heads_per_step = decode_kernel_tiling(
            self.attn_impl, config.n_heads, self._cache.k, self._cache.quantized,
            mesh=self.mesh,
        )
        r.gauge(
            "serve_decode_attn_grid_steps",
            "The most grid steps one decode-attention kernel call can make over "
            "the served cache, every row full (0 = decode takes the XLA path)",
        ).set(grid_steps)
        r.gauge(
            "serve_decode_attn_block_c",
            "Cache slots in one block of the decode-attention kernel (0 = XLA path)",
        ).set(block_c)
        r.gauge(
            "serve_decode_attn_heads_per_step",
            "KV heads of a row one grid step of the decode-attention kernel "
            "carries (0 = XLA path)",
        ).set(heads_per_step)
        # the steps its calls did make: a call walks its work list, a step for
        # every cache block that holds a column some row attends over
        # (ops/pallas_attention.py::decode_work_list); walked / (walked +
        # spared) is the share of the static grid that was there to walk
        self._m_decode_attn_steps = r.counter(
            "serve_decode_attn_steps_total",
            "Grid steps of the decode-attention kernel over the decode chunks "
            "dispatched, by kind: walked (made) | spared (of the most the calls "
            "could have made, not made)",
            labelnames=("kind",),
        )
        # a layer_types model's attentions read rows and rings (models/kinds.py):
        # the columns each kind read, counted where the steps above are
        self._m_decode_attn_columns = r.counter(
            "serve_decode_attn_columns_total",
            "Cache columns the decode steps' attentions read, live rows x layers x "
            "steps, by kind: full (in the full layers' rows) | window (in the sliding "
            "layers' rings) | window_spared (beyond the window, which a ring does not "
            "hold); all 0 for a model of one kind of layer",
            labelnames=("kind",),
        )
        # the kernel that writes the step's K/V column beside it
        # (ops/pallas_attention.py::write_columns) walks the rows that belong to
        # somebody: written / (written + spared) is the share of the slots'
        # rows whose 128-slot blocks it moved
        self._m_decode_column_writes = r.counter(
            "serve_decode_column_writes_total",
            "Rows x calls of the decode column-write kernel over the decode "
            "chunks dispatched, by kind: written | spared (offered, belonging "
            "to nobody, not written); 0 / 0 where the write is XLA's",
            labelnames=("kind",),
        )
        # the gated_delta block's state kernel (ops/pallas_delta.py), counted the
        # same way: a step for every live row's head block of every linear layer
        self._m_delta_state_steps = r.counter(
            "serve_delta_state_steps_total",
            "Grid steps of the gated delta rule's state kernel over the decode "
            "chunks dispatched, by kind: walked (made) | spared (of slots x head "
            "blocks x linear layers, not made); 0 / 0 where the step is plain XLA",
            labelnames=("kind",),
        )
        # how often that kernel's dead-row path engages: a retired slot's row is
        # attended at length 0 and fetches no cache block (models/llama.py
        # forward's token_mask); retired / total is the share of rows it spares
        self._m_decode_rows = r.counter(
            "serve_decode_rows_total",
            "Rows x steps of the decode chunks dispatched, by the row's state in "
            "the dispatch mask (live | retired)",
            labelnames=("state",),
        )
        # disaggregated serving (docs/architecture.md "Disaggregated
        # serving"): prefix-KV segments shipped over the versioned wire
        # format — exports serve GET /admin/kv on a prefill replica, imports
        # land PUT /admin/kv payloads on a decode replica. Export bytes are
        # payload bytes on the wire; import bytes are the KV bytes actually
        # planted (shared blocks dedup to zero, exactly like a local insert).
        self._m_kv_exports = r.counter(
            "serve_kv_exports_total",
            "Prefix-KV wire exports served (GET /admin/kv with a cached prefix)",
        )
        self._m_kv_export_bytes = r.counter(
            "serve_kv_export_bytes_total", "Wire payload bytes exported"
        )
        self._m_kv_imports = r.counter(
            "serve_kv_imports_total",
            "Prefix-KV wire imports applied (PUT /admin/kv)",
        )
        self._m_kv_import_bytes = r.counter(
            "serve_kv_import_bytes_total",
            "KV bytes planted by wire imports (after radix dedup)",
        )
        # last-seen cache counter values: the cache owns the monotonic truth,
        # _sync_prefix_metrics publishes deltas into the registry counters
        self._prefix_seen = {
            "snapshots_stored": 0, "snapshots_evicted": 0,
            "spills": 0, "spilled_bytes": 0, "reuploads": 0,
            "reupload_bytes": 0, "evictions": 0,
        }
        self._m_batched_waves = r.counter(
            "serve_batched_admission_waves_total", "Multi-request admission prefills"
        )
        # multi-LoRA serving (docs/architecture.md "Multi-LoRA serving"):
        # bank width, per-tenant token attribution, and the per-tenant
        # queue-wait/TTFT splits fair admission is judged by. The labeled
        # families only ever grow series on engines that loaded a bank
        # (label cardinality is the bank width, bounded at load).
        self._m_adapters_loaded = r.gauge(
            "serve_adapters_loaded",
            "LoRA adapters resident in the serving bank (base excluded)",
        )
        self._m_adapters_loaded.set(
            len(self.adapter_bank.adapter_names) if self.adapter_bank else 0
        )
        self._m_adapter_tokens = r.counter(
            "serve_adapter_tokens_total",
            "Decoded tokens delivered, by serving adapter (base included)",
            labelnames=("adapter",),
        )
        self._m_adapter_queue_wait = r.histogram(
            "serve_adapter_queue_wait_seconds",
            "Submit to admission-start wait per request, by serving adapter "
            "(the per-tenant fairness split of serve_queue_wait_seconds)",
            labelnames=("adapter",),
        )
        self._m_adapter_ttft = r.histogram(
            "serve_adapter_ttft_seconds",
            "Submit to first emitted token per request, by serving adapter",
            labelnames=("adapter",),
        )
        self._m_active_slots = r.gauge("serve_active_slots", "Slots decoding right now")
        self._m_queue_depth = r.gauge("serve_queue_depth", "Requests waiting for a slot")
        self._m_queue_wait = r.histogram(
            "serve_queue_wait_seconds", "Submit to admission-start wait per request"
        )
        self._m_ttft = r.histogram(
            "serve_ttft_seconds", "Submit to first emitted token per request"
        )
        self._m_tpot = r.histogram(
            "serve_tpot_seconds", "Mean per-token decode latency per completed request"
        )
        self._m_prefill_s = r.histogram(
            "serve_prefill_seconds", "Prefill wall time per admission dispatch"
        )
        self._m_decode_step_s = r.histogram(
            "serve_decode_step_seconds",
            "Decode wall time per generated step (overlap mode: the full "
            "dispatch-to-sync loop window of admission-free chunks, an upper "
            "bound on device step time; sync mode: the blocking decode call)",
        )
        self._m_admit_batch = r.histogram(
            "serve_admission_batch_size", "Requests admitted per prefill wave",
            buckets=DEFAULT_SIZE_BUCKETS,
        )
        # the decode the one-chunk retirement lag threw away (what the host
        # hid inside a chunk's window, and what it did not, is the phase
        # clock's: serve_loop_seconds_total, serve_loop_starved_seconds_total)
        self._m_wasted_tokens = r.counter(
            "serve_wasted_decode_tokens_total",
            "Tokens decoded for slots already retired at dispatch (one-chunk lag)",
        )
        # the dropless expert layer's counts (ops/moe.py moe_infer), fed from
        # a small vector the decode and prefill programs return with their
        # tokens; phase = decode | prefill
        self._m_moe_pairs = r.counter(
            "serve_moe_pairs_total",
            "Token-expert pairs by kind: local (computed by an expert held here), "
            "zero (a zero-compute expert: gate * input), elsewhere (held on another chip)",
            labelnames=("kind", "phase"),
        )
        self._m_moe_touched = r.counter(
            "serve_moe_experts_touched_total",
            "Held experts that had at least one token, summed over expert-layer calls",
            labelnames=("phase",),
        )
        self._m_moe_rows = r.counter(
            "serve_moe_rows_computed_total",
            "Rows the grouped expert products computed (row tiles a non-empty group overlaps)",
            labelnames=("phase",),
        )
        self._m_moe_weight_reads = r.counter(
            "serve_moe_weight_reads_total",
            "Expert matrices the grouped products fetched (3 x experts touched where "
            "every product's contraction is one tile; a row-tile visit each otherwise)",
            labelnames=("phase",),
        )
        self._m_moe_calls = r.counter(
            "serve_moe_layer_calls_total",
            "Expert-layer calls (layers x token groups x steps) the counts above sum over",
            labelnames=("phase",),
        )
        self._m_inflight_depth = r.gauge(
            "serve_inflight_depth", "Dispatched-but-unfetched decode chunks"
        )
        # what the slots hold, by kind of leaf (models/llama.py CACHE_LEAVES):
        # full-length K/V rows, window rings, recurrent state
        cache_bytes = r.gauge(
            "serve_cache_bytes",
            "Bytes of the slot cache at ready, by kind of leaf (full | ring | state)",
            labelnames=("kind",),
        )
        for kind in ("full", "ring", "state"):
            cache_bytes.set(
                sum(leaf.nbytes for leaf, spec in held.values() if spec.kind == kind),
                kind=kind,
            )
        self._m_state_resets = r.counter(
            "serve_state_resets_total",
            "Slots whose recurrent state an admission started from zero (one an "
            "admitted request on a model that carries state; none on one that does not)",
        )
        self._m_prefill_layer_tokens = r.counter(
            "serve_prefill_layer_tokens_total",
            "Token-layers the chunk prefills computed, by half of the model: self "
            "(the layers that keep K/V or state, at every prompt token) | cross (the "
            "layers only the head reads: at a prompt's last position alone)",
            labelnames=("half",),
        )
        self._m_prefill_chunks = r.counter(
            "serve_prefill_chunks_total",
            "Chunk-prefill programs dispatched (one a chunk of a wave's plan, whatever its width)",
        )
        self._m_prefill_chunk_tokens = r.counter(
            "serve_prefill_chunk_tokens_total",
            "Token positions the chunk-prefill programs carried, by kind: prompt "
            "(a request's own token) | pad (the fill of a last chunk, or of a "
            "shorter member of the wave)",
            labelnames=("kind",),
        )
        # a chunk's attention walks its row in column blocks and stops at the
        # last one it can see (ops/attention.py::cache_prefill_attention):
        # visited / (visited + spared) is the share of the rows it scored
        self._m_prefill_attn_columns = r.counter(
            "serve_prefill_attn_columns_total",
            "Row columns of the chunk-prefill programs' attentions, wave rows x "
            "layers that attend over a row x chunks, by kind: visited (in a block "
            "the walk scored) | spared (the rest of the row's capacity)",
            labelnames=("kind",),
        )
        self._m_ring_wraps = r.counter(
            "serve_ring_wraps_total",
            "Decode steps of live rows that overwrote a live column of a window ring "
            "(a position at or past the ring's width)",
        )
        self._m_warmup_programs = r.gauge(
            "serve_warmup_programs", "Programs executed by the AOT warmup pass"
        )
        self._m_warmup_s = r.gauge(
            "serve_warmup_seconds", "Wall seconds the AOT warmup pass took"
        )
        # cold-start attribution: the end-to-end gauge above says warmup was
        # slow; this histogram says WHICH program family (decode / spec /
        # hist_seed / chunk_prefill / finalize / assemble) ate the time —
        # one observation per family block the pass executed
        self._m_warmup_program_s = r.histogram(
            "serve_warmup_program_seconds",
            "Wall seconds of one AOT warmup block, by program family",
            buckets=DEFAULT_LATENCY_BUCKETS,
            labelnames=("program",),
        )
        # speculative decoding: per-window acceptance evidence. The histogram
        # observes the accepted DRAFT count per verify window per slot (the
        # bonus/correction token is excluded — it arrives even at 0 accepts),
        # the counter accumulates the proposed drafts (the denominator), and
        # the gauge publishes the lifetime ratio for scrapes that cannot
        # window deltas themselves.
        self._m_spec_accepted = r.histogram(
            "serve_spec_accepted_tokens",
            "Draft tokens accepted per speculative verify window (per slot)",
            buckets=DEFAULT_SIZE_BUCKETS,
        )
        self._m_spec_drafts = r.counter(
            "serve_spec_draft_tokens_total",
            "Draft tokens proposed by the device-side n-gram drafter",
        )
        self._m_spec_ratio = r.gauge(
            "serve_spec_accept_ratio",
            "Lifetime accepted/proposed draft-token ratio (0 until a verify window ran)",
        )
        self._spec_proposed = 0
        self._spec_accepted = 0
        # sharded replica: how many devices this engine's mesh spans (1 =
        # single-chip), and whether a configured prefix-cache host tier was
        # gated off because the mesh makes the spill converters unsafe
        self._m_mesh_devices = r.gauge(
            "serve_mesh_devices", "Devices in this replica's serving mesh (1 = single-chip)"
        )
        self._m_mesh_devices.set(self.mesh_devices)
        self._m_host_tier_disabled = r.gauge(
            "serve_prefix_host_tier_disabled",
            "1 when a configured prefix-cache host tier was disabled because "
            "the engine runs on a multi-device mesh (spill converters are "
            "not sharding-preserving yet)",
        )
        self._m_host_tier_disabled.set(1 if self._host_tier_gated else 0)
        # sharded-dispatch trace evidence: device-program spans on a meshed
        # engine carry the mesh width so a waterfall distinguishes a
        # single-chip dispatch from one spanning the slice (single-chip
        # span schemas stay byte-identical — the attr only exists on meshes)
        self._span_mesh: dict[str, int] = (
            {"mesh_devices": self.mesh_devices} if self.mesh_devices > 1 else {}
        )
        # always-on flight recorder (obs/flight.py): bounded per-request
        # timelines readable at GET /debug/requests even with tracing off;
        # PRIME_SERVE_SLOW_MS auto-persists slow timelines to the trace sink
        self.flight = FlightRecorder()
        # deterministic latency injection for the sentinel's planted-
        # regression e2e (loadgen/smoke.py _sentinel_section, CI serve-smoke
        # sentinel leg): "MS@N" delays every dispatch by MS milliseconds
        # once N dispatches have gone out, manufacturing a genuine mid-run
        # change-point (an always-on delay would shift fast and slow
        # windows alike and never look like one). Unset costs nothing.
        self._inject_delay_s, self._inject_after = _parse_inject_spec(
            env_str("PRIME_SENTINEL_INJECT_MS", "")
        )
        self._dispatch_count = 0
        # phase clock (obs/phase.py): every second of the thread that drives
        # this engine charged to one phase, always on; the same call opens
        # the jax.profiler annotation and the PRIME_TRACE span of each site,
        # and program builds are accounted through jax.monitoring
        self._clock = PhaseClock(r, tracer=TRACER)
        # device-time observatory: sampled step clock + HBM/MFU
        # accounting into this registry (docs/observability.md "Device
        # time"). Constructed even when disabled so the metric families and
        # the /admin/profile capture surface exist on every engine.
        self.profiler = DeviceProfiler(
            r,
            enabled=self.profile_enabled,
            mesh_devices=self.mesh_devices,
            clock=self._clock,
        )
        self._t0 = time.monotonic()
        # stats() snapshot, ticked by the engine loop (ADVICE engine.py:1008):
        # HTTP handler threads read the last end-of-tick snapshot under this
        # lock instead of live counters and queue sizes mid-tick, so one
        # /metrics response is cross-field consistent with the loop state
        self._stats_lock = threading.Lock()
        self._stats_snapshot: dict | None = None
        # hot-prefix digest snapshot for /healthz advertisement: recomputed
        # by the engine loop (never by HTTP threads — the radix tree is
        # engine-thread-owned) at most every digest_refresh_s
        self._digest_snapshot: list[int] = []
        self._digest_at = 0.0
        self.digest_refresh_s = 1.0

    # legacy counter attributes (older callers read these as plain ints) —
    # now views over the registry-backed counters
    @property
    def prefix_hits(self) -> int:
        return int(self._m_prefix_hits.value())

    @property
    def requests_admitted(self) -> int:
        return int(self._m_admitted.value())

    @property
    def requests_completed(self) -> int:
        return int(self._m_completed.value())

    @property
    def requests_cancelled(self) -> int:
        return int(self._m_cancelled.value())

    @property
    def requests_failed(self) -> int:
        return int(self._m_failed.value())

    @property
    def tokens_emitted(self) -> int:
        return int(self._m_tokens.value())

    @property
    def batched_waves(self) -> int:
        return int(self._m_batched_waves.value())

    def _init_device_state(self) -> None:
        """(Re)allocate the slot cache and per-slot vectors — used at
        construction and to recover after a failed decode dispatch (donated
        buffers are invalid once their call raises)."""
        import jax
        import jax.numpy as jnp

        from prime_tpu.models.llama import init_cache

        cache = init_cache(
            self.config, self.max_slots, self.capacity, dtype=self._dtype,
            quantized=self.kv_quant,
        )
        if self.cache_spec is not None and self.mesh is not None:
            from jax.sharding import NamedSharding

            sharding = NamedSharding(self.mesh, self.cache_spec)
            cache = _map_cache_leaves(
                lambda _, leaf: jax.device_put(leaf, sharding), cache, capacity_only=True
            )
        # lengths ride inside the cache pytree (one donated unit per dispatch)
        self._cache = cache
        self._last = jnp.zeros((self.max_slots,), dtype=jnp.int32)
        self._temps = jnp.zeros((self.max_slots,), dtype=jnp.float32)
        self._top_ps = jnp.ones((self.max_slots,), dtype=jnp.float32)
        # multi-LoRA: each slot's adapter bank index, updated by finalize
        # exactly like the sampling vectors (0 = base; stale values on
        # retired slots are harmless — their outputs are discarded and the
        # next admission overwrites the slot)
        self._adapter_slots = jnp.zeros((self.max_slots,), dtype=jnp.int32)
        # speculative decoding: the device-resident per-slot token history
        # ring (prompt + decoded so far) the fused spec program drafts from —
        # updated INSIDE the program, seeded at admission, never read back to
        # the host. Padded past capacity so a (draft_len+1) scatter window
        # starting at any valid length stays in bounds (mirrors
        # spec_generate's history sizing).
        self._hist = None
        self._hist_len = None
        if self.speculative:
            self._alloc_hist()

    def _alloc_hist(self) -> None:
        """(Re)allocate the cold speculative history ring — shared by
        construction, post-failure recovery, and the end-of-warmup reset."""
        import jax
        import jax.numpy as jnp

        hist = jnp.full(
            (self.max_slots, self.capacity + self.draft_len + 1),
            self.pad_id, dtype=jnp.int32,
        )
        constraint = self._hist_constraint()
        if constraint is not None:
            # place the ring consistently with the paged KV's slot-axis
            # layout up front — the fused program constrains it anyway, but
            # an explicit placement avoids a reshard inside the first
            # donated dispatch
            hist = jax.device_put(hist, constraint)
        self._hist = hist
        self._hist_len = jnp.zeros((self.max_slots,), dtype=jnp.int32)

    def _mesh_ctx(self):
        """Mesh context for compiled calls — the engine thread does not
        inherit a caller's jax.set_mesh, so every dispatch site enters it."""
        import contextlib

        import jax

        if self.mesh is None:
            return contextlib.nullcontext()
        return jax.set_mesh(self.mesh)

    def _cache_constraint(self):
        """The sharding constraint for the engine cache inside compiled
        programs: a NamedSharding when a mesh is attached (resolves without
        an ambient mesh), else the raw
        spec for historical callers that manage their own mesh context."""
        if self.cache_spec is None:
            return None
        if self.mesh is None:
            return self.cache_spec
        from jax.sharding import NamedSharding

        return NamedSharding(self.mesh, self.cache_spec)

    def _row_constraint(self):
        """Sharding constraint for batch-1..N staging rows and assembled
        prefix rows: the cache spec's layer/kv-head/head-dim placement with
        the batch and capacity entries replicated (a staging row's batch is
        a wave size that need not divide the data axes, and its capacity is
        a power-of-two bucket the sp axis need not divide). Keeping rows —
        and therefore the radix cache's stored segments, which are lazy
        slices of them — tp-sharded is what lets a prefix hit feed
        assemble_row without ever gathering KV to one device. None when
        nothing would shard (single chip, or an MLA cache whose single
        latent head stays replicated)."""
        if self.mesh is None or self.cache_spec is None:
            return None
        from jax.sharding import NamedSharding, PartitionSpec

        spec = tuple(self.cache_spec)
        if len(spec) < 4:
            return None
        row_spec = PartitionSpec(spec[0], None, spec[2], spec[3], None)
        if all(entry is None for entry in row_spec):
            return None
        return NamedSharding(self.mesh, row_spec)

    def _hist_constraint(self):
        """Sharding constraint for the speculative history ring and its draft
        buffers: the paged KV cache's SLOT-axis placement (entry 1 of the
        cache spec — sharded only under an sp layout) with the token axis
        replicated, so the ring lives wherever each slot's KV lives and the
        fused propose+verify program never gathers history cross-device.
        None when nothing would shard (single chip, or a layout whose slot
        axis is replicated — the common (dp, fsdp, tp) case, where the tiny
        int32 ring simply replicates like the sampling vectors)."""
        if self.mesh is None or self.cache_spec is None:
            return None
        spec = tuple(self.cache_spec)
        if len(spec) < 2 or spec[1] is None:
            return None
        from jax.sharding import NamedSharding, PartitionSpec

        return NamedSharding(self.mesh, PartitionSpec(spec[1], None))

    @property
    def spec_overhead(self) -> int:
        """Verify-window slots a speculative admission must reserve past
        prompt + max_new_tokens: each window scribbles up to draft_len+1
        positions beyond a row's valid length, and under the overlap pipeline
        ONE stale in-flight window can still advance a just-retired slot by
        another draft_len+1 before the host's retirement lands — so a slot
        may hold up to 2*(draft_len+1) unretired token positions."""
        if not self.speculative:
            return 0
        return (2 if self.overlap else 1) * (self.draft_len + 1)

    # ---- compiled programs ----

    def _make_chunk_prefill(self):
        import jax
        import jax.numpy as jnp

        from prime_tpu.models.llama import forward

        config, attn_impl, mesh = self.config, self.attn_impl, self.mesh
        row_constraint = self._row_constraint()
        moe_counts = self._moe

        def chunk_prefill(params, adapters, row, tokens, offset, last_in_chunk, wave_ids):
            # write-at-offset + attend-over-row (models.llama chunked prefill):
            # the staging row pytree is donated, so chunks update it in place
            # (scale leaves ride along on int8 caches). Only ONE position's
            # logits ever get used (the prompt's last, in the final chunk), so
            # gather it before the unembedding: a (1, chunk, V) fp32 logits
            # buffer plus chunk x the head FLOPs per chunk would be pure waste
            # on the admission hot path (non-final chunks' logits are unused).
            # wave_ids are the wave members' adapter bank slots: the staged
            # KV is computed UNDER each request's adapter, which is why the
            # prefix cache keys adapter paths in a salted token space.
            # a row's padding past its last prompt position belongs to nobody:
            # the experts skip it, and a ring or a recurrent state is left as
            # the row's last real token left it
            live = jnp.arange(tokens.shape[1])[None, :] <= last_in_chunk[:, None]
            logits, row, *stats = forward(
                params, tokens, config, cache=row, decode=False,
                attn_impl=attn_impl, prefill_offset=offset,
                last_positions=last_in_chunk, mesh=mesh,
                adapters=adapters, adapter_ids=wave_ids, token_mask=live,
                return_moe_stats=moe_counts,
            )
            # sharded replica: pin the staged row's kv-head/tp placement so
            # the prefix segments sliced from it stay sharded in the cache
            return (_constrain(row, row_constraint), logits, *stats)

        return jax.jit(chunk_prefill, donate_argnums=(2,))

    def _make_decode(self):
        import jax
        import jax.numpy as jnp

        from prime_tpu.models.llama import forward

        config, attn_impl, chunk = self.config, self.attn_impl, self.chunk
        mesh = self.mesh
        cache_spec = self._cache_constraint()
        moe_counts = self._moe
        # what the step scan carries out with the tokens: the expert layers'
        # counts on MoE engines, then the attention kernel's grid steps and the
        # column write's rows, then, for the gated_delta block, its state kernel's
        kernel_steps = {"return_attn_steps": True, "return_column_writes": True}
        if config.block == "gated_delta":
            kernel_steps["return_state_steps"] = True
        zero_stats = [*self._moe_zero_stats(), *(jnp.zeros((2,), jnp.int32) for _ in kernel_steps)]

        def decode(params, adapters, cache, last, temps, top_ps, active, adapter_slots, rng):
            # neutralize retired slots' stale sampling params: a finished
            # nucleus request must not keep the vocab-sort branch live for
            # later greedy-only traffic (outputs of inactive slots are
            # discarded host-side, so forcing them greedy is free)
            temps = jnp.where(active, temps, 0.0)
            top_ps = jnp.where(active, top_ps, 1.0)

            def step(carry, _):
                cache, tok, rng, *stats = carry
                logits, new_cache, *step_stats = forward(
                    params,
                    tok[:, None],
                    config,
                    positions=cache.lengths[:, None],
                    cache=cache,
                    decode=True,
                    attn_impl=attn_impl,
                    mesh=mesh,
                    adapters=adapters,
                    adapter_ids=adapter_slots,
                    # retired slots belong to nobody: they attend over nothing
                    # and route to no expert
                    token_mask=active[:, None],
                    return_moe_stats=moe_counts,
                    **kernel_steps,
                )
                stats = [total + step for total, step in zip(stats, step_stats)]
                new_cache = _constrain(new_cache, cache_spec)
                # inactive slots must not advance: their next admission
                # prefills the slot from position 0 again
                new_cache = new_cache._replace(
                    lengths=jnp.where(active, new_cache.lengths, cache.lengths)
                )
                rng, step_rng = jax.random.split(rng)
                sampled = _sample_batch(logits[:, 0, :], temps, top_ps, step_rng)
                return (new_cache, sampled, rng, *stats), sampled

            (cache, tok, rng, *stats), toks = jax.lax.scan(
                step, (cache, last, rng, *zero_stats), None, length=chunk
            )
            return (cache, tok, toks.T, *stats)  # toks (S, T)

        return jax.jit(decode, donate_argnums=(2, 3))

    def _moe_zero_stats(self) -> list:
        """[a zeroed counts vector] on MoE engines, [] otherwise: what the
        decode scan carries its layers' counts in."""
        import jax.numpy as jnp

        from prime_tpu.ops.moe import MOE_STATS

        return [jnp.zeros((len(MOE_STATS),), jnp.int32)] if self._moe else []

    @staticmethod
    def _sum_moe(total, stats):
        """An admission's counts so far and one chunk call's (``stats`` is
        what the chunk program returned past row and logits: [] or [counts]).
        Kept as a list and summed on the host once synced: an eager add here
        would be one more program built on the loop thread."""
        if not stats:
            return total
        return [*(total or []), stats[0]]

    def _count_moe(self, stats, phase: str) -> None:
        """Publish synced counts: one vector (a decode chunk's) or a list of
        them (an admission's chunk calls)."""
        from prime_tpu.ops.moe import MOE_STATS

        if isinstance(stats, list):
            stats = np.sum([np.asarray(s) for s in stats], axis=0)
        counts = dict(zip(MOE_STATS, np.asarray(stats).tolist()))
        for kind in ("local", "zero", "elsewhere"):
            self._m_moe_pairs.inc(counts[kind], kind=kind, phase=phase)
        self._m_moe_touched.inc(counts["experts_touched"], phase=phase)
        self._m_moe_rows.inc(counts["rows_computed"], phase=phase)
        self._m_moe_weight_reads.inc(counts["weight_reads"], phase=phase)
        self._m_moe_calls.inc(counts["calls"], phase=phase)

    def _make_spec_decode(self):
        """The fused device-resident speculative step: n-gram draft proposal
        over the per-slot history ring, one (S, D+1) verify forward, the
        accept/correct math, the cache-length advance, AND the history-ring
        update — one donated dispatch with no host data dependency, so spec
        chunks pipeline exactly like decode chunks. Accept/correct math is
        verify_window_tokens — the one owner shared with
        models/speculative.spec_generate — with per-slot traced temps mixing
        greedy and sampled slots in one program."""
        import jax
        import jax.numpy as jnp

        from prime_tpu.models.llama import forward
        from prime_tpu.models.speculative import (
            propose_ngram_drafts,
            verify_window_tokens,
        )

        config, attn_impl = self.config, self.attn_impl
        mesh, draft_len = self.mesh, self.draft_len
        cache_spec = self._cache_constraint()
        hist_spec = self._hist_constraint()

        def spec_decode(
            params, adapters, cache, hist, hist_len, last, temps, top_ps,
            active, adapter_slots, rng,
        ):
            temps = jnp.where(active, temps, 0.0)
            top_ps = jnp.where(active, top_ps, 1.0)
            # device-side prompt-lookup: copy the tokens after the most
            # recent earlier occurrence of each slot's trailing bigram.
            # Inactive rows propose garbage off their stale rings — their
            # run_len is forced to 0 below, so nothing escapes.
            drafts = propose_ngram_drafts(hist, hist_len, draft_len)  # (S, D)
            offsets = cache.lengths
            window = jnp.concatenate([last[:, None], drafts], axis=1)  # (S, D+1)
            logits, new_cache = forward(
                params, window, config, cache=cache, decode=False,
                attn_impl=attn_impl, prefill_offset=offsets, mesh=mesh,
                adapters=adapters, adapter_ids=adapter_slots,
            )
            new_cache = _constrain(new_cache, cache_spec)

            rng, accept_rng, fix_rng = jax.random.split(rng, 3)
            tokens_round, n_acc = verify_window_tokens(
                logits, drafts, temps, top_ps, accept_rng, fix_rng
            )
            run_len = jnp.where(active, n_acc + 1, 0)
            # forward advanced lengths by the full window; only run_len stay
            new_cache = new_cache._replace(lengths=offsets + run_len)
            last_out = jax.vmap(lambda t, i: t[jnp.maximum(i - 1, 0)])(
                tokens_round, run_len
            )
            last_out = jnp.where(active, last_out, last)
            # extend each slot's ring with this round's emissions (accepted
            # drafts + bonus/correction) at its current length — tokens past
            # run_len (incl. everything on inactive rows) merge the old
            # window back, leaving the ring untouched there
            emit_ids = jnp.arange(draft_len + 1)[None, :]
            keep = emit_ids < run_len[:, None]

            def scatter_row(row, start, vals, m):
                window_old = jax.lax.dynamic_slice(row, (start,), (draft_len + 1,))
                merged = jnp.where(m, vals, window_old)
                return jax.lax.dynamic_update_slice(row, merged, (start,))

            new_hist = jax.vmap(scatter_row)(hist, hist_len, tokens_round, keep)
            if hist_spec is not None:
                new_hist = jax.lax.with_sharding_constraint(new_hist, hist_spec)
            new_hist_len = hist_len + run_len
            return new_cache, new_hist, new_hist_len, last_out, tokens_round, run_len

        return jax.jit(spec_decode, donate_argnums=(2, 3, 4, 5))

    def _make_hist_seed(self):
        """One jitted program per admission-wave width: write each admitted
        slot's full history row (prompt tokens + the finalize dispatch's
        first sampled token at position ``length``) and reset its ring
        length — the device-side counterpart of what finalize does for the
        KV cache, keeping drafting fully device-resident."""
        import jax

        hist_spec = self._hist_constraint()

        def seed(hist, hist_len, rows, lengths, slots, firsts):
            rows = jax.vmap(lambda row, n, f: row.at[n].set(f))(rows, lengths, firsts)
            hist = hist.at[slots].set(rows)
            if hist_spec is not None:
                hist = jax.lax.with_sharding_constraint(hist, hist_spec)
            return hist, hist_len.at[slots].set(lengths + 1)

        return jax.jit(seed, donate_argnums=(0, 1))

    def _seed_hist(self, reqs, lengths, slots, firsts) -> None:
        """Seed the device history ring for a just-finalized admission wave
        (speculative engines only). ``firsts`` is the finalize dispatch's
        device array — passing it through keeps the whole seed on-device,
        ordered after finalize by dispatch order."""
        import jax.numpy as jnp

        if self._hist_seed_fn is None:
            self._hist_seed_fn = self._make_hist_seed()
        width = self._hist.shape[1]
        rows = np.full((len(reqs), width), self.pad_id, dtype=np.int32)
        for i, req in enumerate(reqs):
            rows[i, : len(req.prompt_ids)] = req.prompt_ids
        self._hist, self._hist_len = self._hist_seed_fn(
            self._hist, self._hist_len, jnp.asarray(rows),
            _upload(lengths, np.int32),
            _upload(slots, np.int32), firsts,
        )

    # ---- AOT warmup ----

    def warmup(self) -> int:
        """Execute the engine's bounded program set once so no cold XLA
        compile ever lands mid-pipeline: the decode chunk (and spec-verify
        when speculative), plus every chunk-prefill and finalize shape —
        (row capacity x power-of-two sub-batch) for the cold admission plans,
        the n=1 prefix-suffix chunk sizes, and the single-segment
        assemble_row shapes at every power-of-two matched length. Runs on the engine's own
        device state BEFORE any admission: decode executes with an
        all-inactive mask (slot lengths are restored, so the scribbled KV is
        invisible), and finalize splices zero-length rows, so post-warmup
        state is indistinguishable from cold state. Returns the number of
        programs executed; ``start()`` runs it when ``warmup`` /
        ``PRIME_SERVE_WARMUP`` asked for it, and a failure there is fatal.
        A raised dispatch reallocates device state before propagating — the
        warmup calls donate the cache/last/temps buffers, and leaving them
        consumed would fail every later admission on a deleted array."""
        # the zero-length finalize splices and donated-state chaining are
        # only safe against an idle engine whose loop is not running
        if self._requests or any(self._active) or self._inflight:
            raise RuntimeError(
                "warmup() requires an idle engine (admitted or in-flight "
                "requests would be corrupted by the warmup splices)"
            )
        if self._thread is not None:
            raise RuntimeError(
                "warmup() must run before start() (set warmup=True / "
                "PRIME_SERVE_WARMUP=1 and start() runs it)"
            )
        try:
            return self._warmup()
        except Exception:
            self._init_device_state()
            raise

    def _warmup(self) -> int:
        import jax
        import jax.numpy as jnp

        from prime_tpu.models.llama import init_cache

        if self._chunk_fn is None:
            self._chunk_fn = self._make_chunk_prefill()
        if self._finalize_batch_fn is None:
            self._finalize_batch_fn = self._make_finalize_batch()
        if self._decode_fn is None:
            self._decode_fn = self._make_decode()
        if self.speculative and self._spec_fn is None:
            self._spec_fn = self._make_spec_decode()
        if self.speculative and self._hist_seed_fn is None:
            self._hist_seed_fn = self._make_hist_seed()
        if self.prefix_cache is not None and self._assemble_fn is None:
            self._assemble_fn = self._make_assemble_row()
        dispatches = 0
        t0 = time.monotonic()
        # per-family cold-start attribution: each block below compiles one
        # program family; the wall time between block boundaries lands in
        # serve_warmup_program_seconds{program=...} so a slow warmup names
        # its culprit instead of reporting one opaque end-to-end gauge
        family_t = t0

        def _observe_family(program: str) -> None:
            nonlocal family_t
            now = time.monotonic()
            self._m_warmup_program_s.observe(now - family_t, program=program)
            family_t = now

        # throwaway rng stream: warmup outputs are discarded, and the
        # engine's own stream must stay untouched so a warmed engine's
        # sampled requests are bit-identical to a cold one's
        warm_rng = jax.random.PRNGKey(0)
        self._clock.attach()  # the pass's builds are this engine's, as `warmup`
        with self._clock.span("serve.warmup", "warmup"), self._mesh_ctx():
            inactive = jnp.zeros((self.max_slots,), dtype=bool)
            warm_rng, rng = jax.random.split(warm_rng)
            self._cache, self._last, toks, *_ = self._decode_fn(
                self.params, self._adapters, self._cache, self._last,
                self._temps, self._top_ps, inactive, self._adapter_slots, rng,
            )
            jax.block_until_ready(toks)
            dispatches += 1
            _observe_family("decode")
            if self.speculative:
                warm_rng, rng = jax.random.split(warm_rng)
                (
                    self._cache, self._hist, self._hist_len, self._last, toks, _,
                ) = self._spec_fn(
                    self.params, self._adapters, self._cache, self._hist,
                    self._hist_len, self._last, self._temps, self._top_ps,
                    inactive, self._adapter_slots, rng,
                )
                jax.block_until_ready(toks)
                dispatches += 1
                _observe_family("spec")
            batch_sizes = [1]
            while batch_sizes[-1] * 2 <= min(self.max_slots, self.max_wave):
                batch_sizes.append(batch_sizes[-1] * 2)
            if self.speculative:
                # history-ring seed shapes: one program per admission-wave
                # width (the same power-of-two set the finalize warmup runs)
                for n in batch_sizes:
                    self._hist, self._hist_len = self._hist_seed_fn(
                        self._hist, self._hist_len,
                        jnp.full((n, self._hist.shape[1]), self.pad_id, dtype=jnp.int32),
                        jnp.zeros((n,), dtype=jnp.int32),
                        jnp.arange(n, dtype=jnp.int32),
                        jnp.zeros((n,), dtype=jnp.int32),
                    )
                    jax.block_until_ready(self._hist_len)
                    dispatches += 1
                _observe_family("hist_seed")
            for row_cb in row_capacities(self.prefill_chunk, self.capacity):
                cold_sizes = {s for _, s in chunk_plan(0, row_cb, self.prefill_chunk, row_cb)}
                # prefix-hit suffixes admit singly with mid-prompt plans:
                # every power-of-two chunk size up to min(prefill_chunk, row)
                # is reachable at batch 1 (with a prefix cache; without one no
                # plan starts mid-prompt and the cold sizes are all there is)
                prefix_sizes = set(cold_sizes)
                s = MIN_BUCKET
                while self.prefix_cache is not None and s <= min(self.prefill_chunk, row_cb):
                    prefix_sizes.add(s)
                    s *= 2
                for n in batch_sizes:
                    sizes = sorted(prefix_sizes if n == 1 else cold_sizes)
                    row = init_cache(
                        self.config, n, row_cb, dtype=self._dtype,
                        quantized=self.kv_quant,
                    )
                    logits = None
                    for size in sizes:
                        # offset is traced (not a program key): 0 warms the
                        # same program every real plan offset hits
                        tokens = jnp.full((n, size), self.pad_id, dtype=jnp.int32)
                        row, logits, *_ = self._chunk_fn(
                            self.params, self._adapters, row, tokens,
                            jnp.asarray(0, dtype=jnp.int32),
                            jnp.zeros((n,), dtype=jnp.int32),
                            jnp.zeros((n,), dtype=jnp.int32),
                        )
                        dispatches += 1
                    if logits is not None:
                        # fence before finalize so the chunk-prefill compiles
                        # are billed to their own family, not finalize's
                        jax.block_until_ready(logits)
                    _observe_family("chunk_prefill")
                    warm_rng, rng = jax.random.split(warm_rng)
                    (
                        self._cache, self._last, self._temps, self._top_ps,
                        self._adapter_slots, firsts,
                    ) = self._finalize_batch_fn(
                        self._cache, self._last, self._temps, self._top_ps,
                        self._adapter_slots, row, logits,
                        jnp.zeros((n,), dtype=jnp.int32),
                        jnp.arange(n, dtype=jnp.int32),
                        jnp.zeros((n,), dtype=jnp.float32),
                        jnp.ones((n,), dtype=jnp.float32),
                        jnp.zeros((n,), dtype=jnp.int32),
                        rng,
                    )
                    jax.block_until_ready(firsts)
                    dispatches += 1
                    _observe_family("finalize")
                if self.prefix_cache is not None:
                    # assemble_row coverage: the common single-segment hit
                    # (one donor path, no branch point) at every power-of-two
                    # matched length this row can hold. Multi-segment and
                    # odd-length assembles are tiny data-movement programs
                    # that compile lazily on first branchy hit.
                    seg_len = MIN_BUCKET
                    while seg_len < row_cb:
                        donor = init_cache(
                            self.config, 1, seg_len, dtype=self._dtype,
                            quantized=self.kv_quant,
                        )
                        assembled = self._assemble_fn(
                            (_capacity_leaves(donor),), (seg_len,), row_cb,
                            _snapshot_leaves(donor) or None,
                        )
                        jax.block_until_ready(assembled.k)
                        dispatches += 1
                        seg_len *= 2
                    _observe_family("assemble")
            if self.prefix_cache is not None and self.state_stride:
                # a snapshot out of a row of each wave width, and one restored
                snapshot, restore = self._state_programs()
                for n in batch_sizes:
                    leaves = _snapshot_leaves(
                        init_cache(self.config, n, MIN_BUCKET, dtype=self._dtype)
                    )
                    taken = snapshot(leaves, jnp.asarray(0, dtype=jnp.int32))
                    dispatches += 1
                jax.block_until_ready(restore(taken))
                dispatches += 1
                _observe_family("state_snapshot")
        if self.speculative:
            # the hist-seed warmups scribbled slot rings (lengths 1, pad
            # rows); restore exact cold history state so a warmed engine is
            # indistinguishable from a cold one in EVERY device buffer
            self._alloc_hist()
        self._m_warmup_programs.set(dispatches)
        self._m_warmup_s.set(time.monotonic() - t0)
        return dispatches

    # ---- public API ----

    def submit(
        self,
        prompt_ids: list[int],
        max_new_tokens: int = 128,
        temperature: float = 0.0,
        top_p: float = 1.0,
        trace: TraceContext | None = None,
        adapter: str | None = None,
    ) -> EngineRequest:
        if not prompt_ids:
            raise ValueError("empty prompt")
        if self._draining:
            raise DrainingError("engine is draining; not accepting new requests")
        # multi-LoRA: resolve the adapter name to its bank slot up front so
        # an unknown name fails on the submitting thread (the server maps it
        # to a 404 on the OpenAI `model` field), never inside the loop
        adapter_idx = 0
        if adapter is not None and adapter != "base":
            if self.adapter_bank is None:
                raise ValueError(
                    f"no adapter bank loaded; cannot serve adapter {adapter!r}"
                )
            try:
                adapter_idx = self.adapter_bank.index_of(adapter)
            except KeyError as e:
                raise ValueError(str(e)) from None
        if self.max_queue:
            depth = self.queue_depth()
            if depth >= self.max_queue:
                raise QueueFullError(
                    f"pending queue is full ({depth}/{self.max_queue})",
                    retry_after=self.retry_after_estimate(depth),
                )
        # speculation scribbles up to draft_len+1 verify slots past a row's
        # valid length — and under the overlap pipeline one stale in-flight
        # window can advance a just-retired slot by another draft_len+1
        # before retirement lands, so the slot must hold 2*(draft_len+1)
        # (spec_overhead owns the formula; pinned by the capacity test)
        overhead = self.spec_overhead
        if len(prompt_ids) + max_new_tokens + overhead > self.capacity:
            raise ValueError(
                f"prompt ({len(prompt_ids)}) + max_new_tokens ({max_new_tokens})"
                + (f" + verify window ({overhead})" if overhead else "")
                + f" exceeds slot capacity ({self.capacity})"
            )
        # fail oversized staging rows here, not at admission inside the loop
        row_capacity_for(len(prompt_ids), self.prefill_chunk, self.capacity)
        req = EngineRequest(
            id=next(self._ids),
            prompt_ids=list(prompt_ids),
            max_new_tokens=max_new_tokens,
            temperature=temperature,
            top_p=top_p,
            adapter=adapter if adapter_idx else None,
            adapter_idx=adapter_idx,
            submitted_at=time.monotonic(),
            trace=trace,
        )
        self.flight.begin(
            req.id,
            trace_id=trace.trace_id if trace is not None else None,
            prompt_tokens=len(prompt_ids),
            max_new_tokens=max_new_tokens,
            **({"adapter": adapter} if adapter_idx else {}),
        )
        self._pending.put(req)
        self._wake.set()
        return req

    def retry_after_estimate(self, depth: int | None = None) -> float:
        """Seconds until a retried submit is likely to be admitted: the mean
        observed queue wait scaled by how many slot-widths of work are queued
        ahead. Clamped to [0.1, 60] so a cold histogram still produces a
        usable Retry-After and a pathological backlog cannot tell clients to
        go away for an hour."""
        if depth is None:
            depth = self.queue_depth()
        per_wave = self._m_queue_wait.mean(default=1.0)
        waves = (depth + 1) / max(1, self.max_slots)
        return max(0.1, min(60.0, per_wave * waves))

    def queue_depth(self) -> int:
        """Requests accepted but not yet admitted: the ingress queue, the
        requeued head, and (multi-LoRA) the per-adapter fairness buckets the
        engine thread drains the ingress into — all three must count, or a
        bucketed burst would make max_queue/drained lie."""
        return (
            self._pending.qsize()
            + len(self._requeued)
            + sum(len(dq) for dq in self._fair.values())
        )

    def drain(self) -> None:
        """Stop taking new work (submit() raises DrainingError) while the
        engine loop finishes every queued and in-flight request. Idempotent;
        ``drained`` flips True once nothing is pending, admitted, or in the
        decode pipeline. The caller (server /admin/drain, fleet router) polls
        ``drained`` — the loop itself needs no extra wake-up because it is
        already ticking while work remains."""
        self._draining = True

    @property
    def draining(self) -> bool:
        return self._draining

    @property
    def drained(self) -> bool:
        """True when a drain has fully quiesced the engine: no queued,
        requeued, admitted, or dispatched-but-unfetched work remains — and
        no tick is mid-flight (a running _admit holds popped requests in
        locals where none of those structures can see them). Safe to read
        from any thread; a drain-gated kill must never observe True while a
        request the engine accepted is still unfinished. Read order matters:
        queue state before slot state, _tick_busy first AND last — a tick
        that pops the final request between our reads either shows up as
        busy, or has already registered the request in _requests (checked
        later), so every interleaving reports False until truly quiet."""
        if not self._draining or self._tick_busy:
            return False
        if not self._pending.empty() or self._requeued:
            return False
        if any(self._fair.values()):
            return False  # fairness buckets hold popped-but-unadmitted work
        if self._requests or self._inflight:
            return False
        return not self._tick_busy

    def join_drain(self, timeout: float | None = 30.0) -> bool:
        """Block until ``drained`` (polling — the engine thread owns all the
        state being watched). Returns False on timeout."""
        deadline = None if timeout is None else time.monotonic() + timeout
        while not self.drained:
            if deadline is not None and time.monotonic() > deadline:
                return False
            time.sleep(0.01)
        return True

    def start(self) -> None:
        if self._thread is not None:
            return
        if self.warmup_enabled:
            # compile before the loop exists, so no request can land
            # mid-pipeline on a cold program — and so a warmup that fails
            # (a kernel that does not lower, a program set that does not fit
            # the device) stops the caller instead of becoming a line on
            # stderr in front of a server that then fails request by request
            self.warmup()  # reallocates its donated state before raising
        # seed the snapshot before the loop owns it: a scrape landing between
        # start() and the first tick must not observe None
        self._refresh_stats()
        self._running = True
        self._thread = threading.Thread(
            target=self._run, daemon=True, name="prime-engine-loop"
        )
        self._thread.start()

    def shutdown(self) -> None:
        self._running = False
        self._pending.put(None)  # sentinel: _pop_pending skips it
        self._wake.set()  # wake the engine thread
        if self._thread is not None:
            self._thread.join(timeout=60)
            self._thread = None
        # fail everything still waiting so clients get a prompt error instead
        # of hanging until their events.get timeout. The flush bypasses the
        # fair scheduler's caps: a capped tenant's bucketed backlog must be
        # failed too, not leaked to its clients' timeouts.
        self._fail_in_flight("engine shut down")

        def flush():
            if self._requeued:
                return self._requeued.popleft()
            return self._pending.get_nowait()

        pending_reqs: list[EngineRequest | None] = []
        while True:
            try:
                pending_reqs.append(flush())
            except queue.Empty:
                break
        for dq in self._fair.values():
            pending_reqs.extend(dq)
            dq.clear()  # empty the deques, never the dict (see _fair's note)
        for req in pending_reqs:
            if req is not None:
                req.error = "engine shut down"
                req.done = True
                self._retire_flight(req, "failed", error="engine shut down")
                req.events.put(None)

    def _retire_flight(self, req: EngineRequest, outcome: str, **fields: Any) -> None:
        """Close a request's flight-recorder timeline and emit its summary
        span (``serve.request``, submit → retirement) under the request's
        distributed trace — the one engine span a cross-process waterfall is
        guaranteed to have per request. Idempotent via FlightRecorder.end."""
        self.flight.end(req.id, outcome, tokens=req.emitted, **fields)
        if req.submitted_at:
            TRACER.emit(
                "serve.request",
                time.monotonic() - req.submitted_at,
                context=req.trace,
                request=req.id,
                outcome=outcome,
                tokens=req.emitted,
            )

    def _fail_in_flight(self, message: str) -> None:
        # drop any dispatched-but-unfetched lookahead chunks: their donated
        # input buffers are gone and their outputs must never be emitted
        self._inflight.clear()
        self._m_inflight_depth.set(0)
        for slot, req in list(self._requests.items()):
            req.error = message
            req.done = True
            self._m_failed.inc()
            self._retire_flight(req, "failed", error=message[:200])
            req.events.put(None)
            self._active[slot] = False
            self._requests.pop(slot, None)

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *exc):
        self.shutdown()

    # ---- engine loop ----

    def _run(self) -> None:
        while self._running:
            if not self.tick():
                # idle: wait for a submit/shutdown wake rather than popping
                # the queue here — a request popped into this frame's locals
                # would be invisible to `drained` (and to queue-depth reads)
                # for the instant before it was requeued, which let a
                # drain-gated kill land on a replica that still held work.
                # The wake costs nothing batched: the next tick's _admit
                # drains the whole queued burst into one prefill wave, same
                # as the old requeue-at-front path.
                with self._clock.enter("no_work"):
                    if self._wake.wait(timeout=0.2):
                        self._wake.clear()

    def _requeue(self, req: EngineRequest) -> None:
        """Hand a popped request back to admission ahead of the pending
        queue (_pop_pending consumes _requeued first, preserving arrival
        order without reaching into queue.Queue internals)."""
        self._requeued.append(req)

    def _pop_pending(self) -> EngineRequest | None:
        """The ONE owner of admission-drain order: requeued head first, then
        the pending queue. Raises queue.Empty when both are drained; may
        return the None shutdown sentinel (callers skip it).

        Multi-LoRA engines interpose the per-tenant fair scheduler: the
        ingress queue drains into per-adapter FIFO buckets (engine thread
        only) and requests pop round-robin across adapters, skipping any
        adapter already holding ``adapter_max_inflight`` admitted slots —
        one tenant's burst can no longer starve every other tenant's
        admission, and a capped tenant's backlog waits in its bucket
        without blocking the rotation."""
        if self._requeued:
            return self._requeued.popleft()
        if self.adapter_bank is None:
            return self._pending.get_nowait()
        while True:
            try:
                req = self._pending.get_nowait()
            except queue.Empty:
                break
            if req is None:
                return None  # shutdown sentinel: callers skip it
            self._fair[req.adapter_idx].append(req)
        return self._fair_pop()

    def _fair_pop(self) -> EngineRequest:
        """WEIGHTED round-robin pop across the non-empty per-adapter
        buckets, honoring the per-adapter inflight cap (0 = uncapped).
        Smooth-WRR (constructor comment): each poppable tenant's credit
        grows by its weight, the richest credit (lowest index on ties) pops
        and pays back the candidates' total — so a weight-2 tenant admits
        twice per rotation, interleaved (a,a,b... never a whole burst),
        and uniform weights reproduce the historical plain rotation.
        Raises queue.Empty when nothing is poppable — capped tenants'
        requests stay bucketed (still counted by queue_depth/drained)
        until a retirement frees their budget."""
        candidates = sorted(idx for idx, dq in self._fair.items() if dq)
        if not candidates:
            raise queue.Empty
        cap = self.adapter_max_inflight
        if cap:
            # admitted slots PLUS pops earlier in this same admission burst
            # (they are not in _requests yet but will be): without the
            # burst-local counts, one _admit wave could blow past the cap
            inflight: dict[int, int] = {}
            for live in self._requests.values():
                inflight[live.adapter_idx] = inflight.get(live.adapter_idx, 0) + 1
            for idx, count in self._burst_pops.items():
                inflight[idx] = inflight.get(idx, 0) + count
            candidates = [
                idx for idx in candidates if inflight.get(idx, 0) < cap
            ]
            if not candidates:
                raise queue.Empty
        total = sum(self._fair_weights[idx] for idx in candidates)
        for idx in candidates:
            self._fair_credit[idx] += self._fair_weights[idx]
        pick = max(candidates, key=lambda idx: (self._fair_credit[idx], -idx))
        self._fair_credit[pick] -= total
        req = self._fair[pick].popleft()
        if cap:
            self._burst_pops[pick] = self._burst_pops.get(pick, 0) + 1
        return req

    def tick(self) -> bool:
        """One engine iteration. Returns False when there was nothing to do.

        Overlap mode (default): dispatch the next decode chunk on the
        last-known active mask FIRST, then do all host work — fetching the
        previous chunk's tokens, emit/retire, cancellation sweep, admission —
        inside the new chunk's device-compute window. Synchronous mode
        (``PRIME_SERVE_OVERLAP=0`` or speculative): admit, then decode one
        chunk and block for its tokens.

        Every tick ends by publishing the stats() snapshot — the engine loop
        is the one writer, so HTTP readers always see a loop-consistent view.
        """
        self._tick_busy = True
        self._clock.attach()  # whoever ticks is the loop's thread
        try:
            with self._clock.enter("kv_jobs"):
                serviced = self._service_kv_jobs()
            return self._tick_inner() or serviced
        finally:
            self._tick_busy = False
            self._refresh_stats()

    def _tick_inner(self) -> bool:
        if not self.overlap:
            return self._tick_sync()
        did = False
        try:
            if any(self._active):
                self._dispatch_decode()
                did = True
            # one-deep pipeline: with a fresh chunk dispatched, sync the
            # previous one now (its host work overlaps the new chunk's device
            # window); with nothing dispatched, drain what is still in flight
            while len(self._inflight) > (1 if did else 0):
                self._sync_decode()
                did = True
        except Exception as e:  # noqa: BLE001 — a dead engine hangs every client
            return self._recover_decode(e)
        with self._clock.enter("admit"):
            self._retire_cancelled()
            admitted = self._admit()
        if admitted:
            for chunk in self._inflight:
                chunk.clean = False
        return admitted or did

    def _tick_sync(self) -> bool:
        """The strictly serial loop: admit, then dispatch one chunk and block
        for its tokens before any emit/admission work — the bit-identity
        reference the pipelined order is pinned against."""
        with self._clock.enter("admit"):
            admitted = self._admit()
            self._retire_cancelled()
        if not any(self._active):
            return admitted
        try:
            self._dispatch_decode()
            self._sync_decode()
        except Exception as e:  # noqa: BLE001 — a dead engine hangs every client
            return self._recover_decode(e)
        return True

    def _recover_decode(self, e: Exception) -> bool:
        """The chunk programs donate the cache buffers, so a raised dispatch
        or sync leaves them (and any in-flight lookahead chunk) invalid: drop
        the pipeline, fail the in-flight requests promptly, and reallocate
        device state so the engine keeps serving. Recovery is always
        synchronous — _init_device_state must not race an in-flight donated
        dispatch. Returns True: the tick did something."""
        self._fail_in_flight(f"decode failed: {e}")
        self._init_device_state()
        return True

    def _maybe_inject_delay(self) -> None:
        """PRIME_SENTINEL_INJECT_MS hook (every chunk dispatch): counts
        dispatches and, once past the activation threshold, stalls the host
        for the configured delay so the step clock and TPOT genuinely
        regress mid-run. A no-op (one int increment) when the knob is
        unset."""
        self._dispatch_count += 1
        if self._inject_delay_s and self._dispatch_count > self._inject_after:
            time.sleep(self._inject_delay_s)

    def _dispatch_decode(self) -> None:
        """Launch one chunk and return WITHOUT waiting for it: the tokens
        stay on the device inside an _InflightChunk until _sync_decode
        fetches them. JAX's async dispatch makes this the whole pipeline —
        the host returns as soon as the computation is enqueued. A
        speculative engine launches its fused propose+verify program the
        same way: the history ring rides beside the cache, and the run
        lengths ride the _InflightChunk as a device array the sync path
        slices each slot's emissions by."""
        import jax
        import jax.numpy as jnp

        self._maybe_inject_delay()
        seq = next(self._chunk_seq)
        spec = self.speculative
        if spec:
            if self._spec_fn is None:
                self._spec_fn = self._make_spec_decode()
            fn, kind, steps = self._spec_fn, "spec", self.draft_len + 1
            span = self._clock.span(
                "serve.spec_dispatch", "dispatch", seq=seq,
                draft_len=self.draft_len, **self._span_mesh,
            )
        else:
            if self._decode_fn is None:
                self._decode_fn = self._make_decode()
            fn, kind, steps = self._decode_fn, "decode", self.chunk
            span = self._clock.span(
                "serve.dispatch", "dispatch", seq=seq, steps=self.chunk,
                **self._span_mesh,
            )
        # the phase holds all of the enqueue: the rng split and the mask's
        # upload are dispatches of their own, and the device waits for them
        with span, self._mesh_ctx():
            self._rng, rng = jax.random.split(self._rng)
            mask = self._active.copy()
            hist = (self._hist, self._hist_len) if spec else ()
            args = (
                self.params, self._adapters, self._cache, *hist, self._last,
                self._temps, self._top_ps, jnp.asarray(mask),
                self._adapter_slots, rng,
            )
            # step clock: a sampled dispatch drains the in-flight predecessor
            # (pre=self._last syncs the pipeline), times this program to
            # readiness, and captures its XLA cost analysis once. Inactive
            # profiler -> shared no-op: zero added syncs on the overlap path.
            live = int(mask.sum())
            with self.profiler.step(
                kind, pre=self._last, batch=live,
                steps=steps, cost_fn=fn, cost_args=args,
            ) as prof_step:
                if spec:
                    (
                        self._cache, self._hist, self._hist_len, self._last,
                        toks, run_len,
                    ) = fn(*args)
                    carried = {"run_len": run_len}
                else:
                    self._cache, self._last, toks, *stats = fn(*args)
                    names = (["moe_stats"] if self._moe else []) + [
                        "attn_steps", "column_writes", "state_steps"
                    ]
                    carried = dict(zip(names, stats))
                    if self.config.layer_types is not None:
                        carried["attn_columns"] = self._attended_columns(mask, steps)
                    self._m_decode_rows.inc(live * steps, state="live")
                    self._m_decode_rows.inc((mask.size - live) * steps, state="retired")
                self._clock.enqueued(toks)
                prof_step.fence(toks)
        self._inflight.append(
            _InflightChunk(
                seq=seq, toks=toks, mask=mask,
                requests=dict(self._requests),
                dispatched_at=self._clock.switched_at, **carried,
            )
        )
        self._m_inflight_depth.set(len(self._inflight))

    def _attended_columns(self, mask: np.ndarray, steps: int) -> tuple[int, int, int]:
        """The cache columns a decode chunk of ``steps`` steps over the rows of
        ``mask`` attends, by kind, for a ``config.layer_types`` model: in the
        full layers' rows, in the sliding layers' rings, and beyond the window
        where a ring holds nothing. From what the host knows at dispatch: a
        row's length is its prompt and the steps dispatched for it so far (its
        request counts them here), and step ``s`` attends ``length + s + 1``
        columns, its own among them."""
        config = self.config
        live = [req for slot, req in self._requests.items() if mask[slot]]
        lengths = np.asarray([len(req.prompt_ids) + req.steps_dispatched for req in live], np.int64)
        for req in live:
            req.steps_dispatched += steps
        attended = lengths[:, None] + 1 + np.arange(steps)
        in_ring = np.minimum(attended, config.sliding_window)
        return (
            config.full_layers * int(attended.sum()),
            config.window_layers * int(in_ring.sum()),
            config.window_layers * int((attended - in_ring).sum()),
        )

    def _sync_decode(self) -> None:
        """Fetch the oldest in-flight chunk's tokens and emit them. Tokens
        route via the dispatch-time request snapshot: a slot retired (and
        possibly re-admitted) after dispatch gets its whole chunk counted as
        wasted decode instead of leaking old tokens into the new request.
        Speculative chunks carry per-slot run lengths: each row emits only
        its accepted run, acceptance feeds the spec metrics, and a stale
        slot's waste is the accepted-length window it decoded for nobody."""
        chunk = self._inflight.pop(0)
        spec = chunk.run_len is not None
        with self._clock.span("serve.sync", "sync_wait", seq=chunk.seq):
            toks_host = np.asarray(chunk.toks)  # blocks until the chunk lands
            runs = np.asarray(chunk.run_len) if spec else None
            if chunk.moe_stats is not None:
                self._count_moe(chunk.moe_stats, "decode")
            for steps, counter, made_kind in (
                (chunk.attn_steps, self._m_decode_attn_steps, "walked"),
                (chunk.column_writes, self._m_decode_column_writes, "written"),
                (chunk.state_steps, self._m_delta_state_steps, "walked"),
            ):
                if steps is not None:
                    made, most = (int(n) for n in np.asarray(steps))
                    counter.inc(made, kind=made_kind)
                    counter.inc(most - made, kind="spared")
            if chunk.attn_columns is not None:
                for kind, columns in zip(("full", "window", "window_spared"), chunk.attn_columns):
                    self._m_decode_attn_columns.inc(columns, kind=kind)
        t_done = self._clock.switched_at  # the sync_wait phase's end
        if chunk.clean:
            # steady-state decode only: windows that contained an admission
            # prefill are dominated by host work already recorded in
            # serve_prefill_seconds and would corrupt the per-step histogram.
            # A verify window advances each slot by >=1 token: charge it as
            # one step (per-token attribution rides the TPOT histogram).
            self._m_decode_step_s.observe(
                (t_done - chunk.dispatched_at) / (1 if spec else self.chunk)
            )
        self._m_inflight_depth.set(len(self._inflight))
        with self._clock.enter("emit"):
            self._emit_chunk(chunk, toks_host, runs)

    def _emit_chunk(self, chunk: _InflightChunk, toks_host, runs) -> None:
        """Route a synced chunk's tokens to their streams (the slot loop of
        _sync_decode)."""
        spec = runs is not None
        for slot in range(self.max_slots):
            if not chunk.mask[slot]:
                continue
            accepted = 0
            if spec:
                accepted = max(0, int(runs[slot]) - 1)
                self._spec_proposed += self.draft_len
                self._spec_accepted += accepted
                self._m_spec_drafts.inc(self.draft_len)
                self._m_spec_accepted.observe(accepted)
            req = chunk.requests.get(slot)
            if req is None or req.done or req.cancelled:
                # dispatched on a stale mask: the slot retired between
                # dispatch and sync — the bounded cost of one-chunk-lag
                # retirement is this whole chunk row (for spec, the
                # accepted-length window the device advanced it by)
                self._m_wasted_tokens.inc(int(runs[slot]) if spec else self.chunk)
                continue
            if spec:
                self.flight.event(req.id, "chunk", seq=chunk.seq, accepted=accepted)
                self._emit(req, toks_host[slot][: int(runs[slot])].tolist())
            else:
                self.flight.event(req.id, "chunk", seq=chunk.seq)
                if self._ring_columns:
                    # the chunk's steps wrote positions first .. first + chunk - 1
                    first = len(req.prompt_ids) + req.emitted - 1
                    self._m_ring_wraps.inc(
                        min(self.chunk, max(0, first + self.chunk - self._ring_columns))
                    )
                self._emit(req, toks_host[slot].tolist())

    def _retire_cancelled(self) -> None:
        """Free slots whose client abandoned the request (disconnected
        stream): decoding the rest of max_new_tokens for nobody would delay
        admission of live requests."""
        for slot, req in list(self._requests.items()):
            if req.cancelled:
                req.done = True
                self._m_cancelled.inc()
                self._retire_flight(req, "cancelled")
                req.events.put(None)
                self._active[slot] = False
                self._requests.pop(slot, None)

    def _admit(self) -> bool:
        admitted = False
        self._burst_pops = {}  # fairness cap: fresh burst-local counts
        while True:
            free = [s for s in range(self.max_slots) if not self._active[s]]
            if not free:
                return admitted
            # drain up to the free-slot count so a burst can be admitted as
            # ONE batched prefill: per-request b=1 prefills underuse the MXU
            # (the weights stream once per request instead of once per wave)
            # and pay two dispatches each
            burst: list[EngineRequest] = []
            while len(burst) < len(free):
                try:
                    req = self._pop_pending()
                except queue.Empty:
                    break
                if req is None:
                    continue
                if req.cancelled:
                    # client went away while queued: don't pay the prefill
                    req.done = True
                    self._retire_flight(req, "cancelled")
                    req.events.put(None)
                    continue
                burst.append(req)
            if not burst:
                return admitted
            # cold requests sharing a (row capacity, chunk plan) batch
            # together; a prefix-cache hit is a wave of one (its plan starts
            # mid-prompt and its seeded row is its own)
            groups: dict[tuple, list[EngineRequest]] = {}
            hits: list[tuple[EngineRequest, int, list[int]]] = []
            for req in burst:
                ids = req.prompt_ids
                try:
                    row_cb = row_capacity_for(
                        len(ids), self.prefill_chunk, self.capacity
                    )
                except ValueError as e:
                    self._fail_admission([req], e)
                    continue
                key = self._prefix_key(ids, req.adapter_idx)
                usable, matched = self._prefix_match_lens(key)
                if usable > 0:
                    hits.append((req, row_cb, key))
                else:
                    if matched >= self.min_prefix:
                        # K/V on a path with no snapshot is a miss: all of it is
                        # prefilled again, and counted as the stride's price
                        self._m_prefix_match_tokens.inc(matched, kind="beyond_snapshot")
                    plan = tuple(chunk_plan(0, len(ids), self.prefill_chunk, row_cb))
                    groups.setdefault((row_cb, plan), []).append(req)
            for req, row_cb, key in hits:
                try:
                    with self._clock.enter("prefix_seed"):
                        start, row = self._prefix_seed(key, row_cb, ctx=req.trace)
                    plan = chunk_plan(start, len(key), self.prefill_chunk, row_cb)
                    self._prefill([req], [free.pop(0)], row_cb, plan, row, start)
                    admitted = True
                except Exception as e:  # noqa: BLE001 — keep the loop alive
                    self._fail_admission([req], e)
            for (row_cb, plan), reqs in groups.items():
                # power-of-two sub-batches (largest first): the compile set
                # per plan stays O(log slots) instead of one program per
                # arbitrary wave size — a size-7 wave runs as 4+2+1, all
                # shapes a warmup can enumerate
                remaining = reqs
                for size in _power_batches(len(reqs), self.max_wave):
                    sub, remaining = remaining[:size], remaining[size:]
                    try:
                        slots = [free.pop(0) for _ in sub]
                        self._prefill(sub, slots, row_cb, list(plan))
                        admitted = True
                    except Exception as e:  # noqa: BLE001 — keep the loop alive
                        self._fail_admission(sub, e)

    def _fail_admission(self, reqs: list[EngineRequest], e: Exception) -> None:
        """A raised admission fails its own requests and nothing else: the
        loop stays alive for the rest of the burst."""
        for req in reqs:
            req.error = f"prefill failed: {e}"
            req.done = True
            self._retire_flight(req, "failed", error=str(e)[:200])
            req.events.put(None)

    def _prefill(
        self,
        reqs: list[EngineRequest],
        slots: list[int],
        row_cb: int,
        plan: list[tuple[int, int]],
        row: Any = None,
        hit_tokens: int = 0,
    ) -> None:
        """Admit a wave of same-plan requests in one batched prefill: the
        chunk forwards run at batch N (weights stream once per wave, not
        once per request) and ONE finalize dispatch splices every staged row
        and samples every first token. A cold wave starts from empty rows; a
        prefix hit is a wave of one whose ``row`` _admit seeded with its
        first ``hit_tokens`` positions and whose plan starts there. The
        prefix cache is fed from the FIRST member's row only (slicing every
        member costs tree ops per request) — enough that a recurring
        shared-prefix burst prefix-hits from its second wave on — unless
        ``prefix_store_all`` (prefill-role replicas) asks for every member's
        path to be exportable. The store comes BEFORE the wait for the first
        tokens, so its host time (and the programs it builds on first use)
        runs while the chip is still busy with the prefill."""
        import jax

        from prime_tpu.models.llama import init_cache

        if self._chunk_fn is None:
            self._chunk_fn = self._make_chunk_prefill()
        if self._finalize_batch_fn is None:
            self._finalize_batch_fn = self._make_finalize_batch()
        n = len(reqs)
        t_start = time.monotonic()
        for slot, req in zip(slots, reqs):
            if req.submitted_at:
                wait = t_start - req.submitted_at
                self._m_queue_wait.observe(wait)
                if self.adapter_bank is not None:
                    self._m_adapter_queue_wait.observe(
                        wait, adapter=req.adapter or "base"
                    )
                TRACER.emit("serve.queue_wait", wait, context=req.trace, request=req.id)
            req.admitted_at = t_start
            self.flight.event(req.id, "admitted", slot=slot, wave=n)
        self._rng, rng = jax.random.split(self._rng)
        # the new tenants' recurrent state starts from a fresh row's zeros;
        # finalize copies it over whatever the slots' last tenants left
        state_resets = n if row is None and self._state_leaves else 0
        if row is None:
            row = init_cache(self.config, n, row_cb, dtype=self._dtype, quantized=self.kv_quant)
        lengths = [len(r.prompt_ids) for r in reqs]
        real_tokens = 0
        adapter_ids = _upload([r.adapter_idx for r in reqs], np.int32)
        logits = None
        moe_stats = None
        # the members whose rows go to the prefix cache: member 0 by default,
        # every member on a prefix_store_all (prefill-role) engine, none
        # without a prefix cache
        stored = 0 if self.prefix_cache is None else n if self.prefix_store_all else 1
        snapshots: list[dict[int, Any]] = [{} for _ in range(stored if self.state_stride else 0)]
        with self._clock.span(
            "serve.prefill", "prefill", batch=n, row_capacity=row_cb,
            **self._span_mesh,
        ), self._mesh_ctx(), self.profiler.step(
            "prefill", pre=self._last, batch=n, steps=max(lengths),
        ) as prof_step:
            for off, size in plan:
                chunk_rows = []
                rels = []
                for req in reqs:
                    ids = req.prompt_ids
                    chunk_ids = ids[off : off + size]
                    chunk_ids = list(chunk_ids) + [self.pad_id] * (size - len(chunk_ids))
                    chunk_rows.append(chunk_ids)
                    # chunk-relative last prompt position, clamped into this
                    # chunk: the gathered row only matters for the final
                    # chunk (finalize consumes that one), clamping keeps
                    # earlier chunks' gathers in bounds
                    rels.append(min(max(len(ids) - 1 - off, 0), size - 1))
                chunk_args = (
                    self.params, self._adapters, row,
                    _upload(chunk_rows, np.int32),
                    _upload(off, np.int32),
                    _upload(rels, np.int32),
                    adapter_ids,
                )
                self.profiler.note_cost("prefill", self._chunk_fn, chunk_args)
                row, logits, *stats = self._chunk_fn(*chunk_args)
                self._clock.enqueued(logits)
                moe_stats = self._sum_moe(moe_stats, stats)
                real_tokens += sum(min(max(length - off, 0), size) for length in lengths)
                end = off + size
                if snapshots and end % self.state_stride == 0:
                    # a stride boundary is this chunk's end: the row holds the
                    # state after its last token, for the members whose prompt
                    # reaches it (a shorter one's state stopped at its own end)
                    self._take_snapshots(row, end, lengths, snapshots)
            # what the plan's programs carried: the prompts' tokens, and fill
            self._m_prefill_chunks.inc(len(plan))
            self._m_prefill_chunk_tokens.inc(real_tokens, kind="prompt")
            self._m_prefill_chunk_tokens.inc(
                n * sum(size for _, size in plan) - real_tokens, kind="pad"
            )
            visited, spared = self._prefill_columns(n, row_cb, plan)
            self._m_prefill_attn_columns.inc(visited, kind="visited")
            self._m_prefill_attn_columns.inc(spared, kind="spared")
            # a chunk computes the cross half at one position a row
            # (last_positions), every other layer at each real token
            cross_half = self.config.cross_half_layers
            self._m_prefill_layer_tokens.inc(
                real_tokens * (self.config.n_layers - cross_half), half="self"
            )
            self._m_prefill_layer_tokens.inc(len(plan) * n * cross_half, half="cross")
            # the splice copies K/V by the row's columns and, where the slots
            # hold them, ring and recurrent state whole: that copy has a span
            state_copy = (
                TRACER.span(
                    "serve.state_copy", batch=n, row_capacity=row_cb,
                    ring_columns=self._ring_columns,
                )
                if self._state_leaves else contextlib.nullcontext()
            )
            with state_copy:
                (
                    self._cache, self._last, self._temps, self._top_ps,
                    self._adapter_slots, firsts,
                ) = self._finalize_batch_fn(
                    self._cache, self._last, self._temps, self._top_ps,
                    self._adapter_slots, row, logits,
                    _upload(lengths, np.int32),
                    _upload(slots, np.int32),
                    _upload([r.temperature for r in reqs], np.float32),
                    _upload([r.top_p for r in reqs], np.float32),
                    adapter_ids,
                    rng,
                )
            self._clock.enqueued(firsts)
            prof_step.fence(firsts)
        if self.speculative:
            # seed the device history ring before the host sync below — the
            # seed dispatch rides the same device queue as finalize, so the
            # first spec chunk can draft from the prompt immediately
            with self._mesh_ctx():
                self._seed_hist(reqs, lengths, slots, firsts)
        # A wave of one stores its row as it is: taking a member out of a
        # wider row is a copy and a program a shape
        for i in range(stored):
            row_i = row if n == 1 else jax.tree_util.tree_map(
                lambda x, i=i: x[:, i : i + 1] if x.ndim >= 2 else x[i : i + 1],
                row,
            )
            self._store_prefix(
                self._prefix_key(reqs[i].prompt_ids, reqs[i].adapter_idx), row_i,
                snapshots[i] if snapshots else None,
            )
        with self._clock.enter("prefill_sync"):
            # host sync: the prefill really finished here
            firsts_host = [int(t) for t in np.asarray(firsts)]
            if moe_stats is not None:
                self._count_moe(moe_stats, "prefill")
        prefill_s = self._clock.switched_at - t_start
        prefill_ms = round(prefill_s * 1e3, 3)
        self._m_prefill_s.observe(prefill_s)
        for req in reqs:
            self.flight.event(
                req.id, "prefill_done", ms=prefill_ms, wave=n,
                prefix_hit_tokens=hit_tokens,
            )
            # per-request prefill attribution under each request's OWN trace
            # (the wave span above is process-local): the wave's wall time is
            # every member's prefill time — they shared the dispatch
            TRACER.emit(
                "serve.prefill", prefill_s, context=req.trace,
                request=req.id, batch=n, prompt_len=len(req.prompt_ids),
                **self._span_mesh,
            )
        self._m_admit_batch.observe(n)
        self._m_admitted.inc(n)
        self._m_state_resets.inc(state_resets)  # beside the admissions it is read against
        if self._state_leaves:
            self._m_state_starts.inc(n, **{"from": "snapshot" if hit_tokens else "zero"})
        if n > 1:
            self._m_batched_waves.inc()
        with self._clock.enter("emit"):
            for req, slot, first in zip(reqs, slots, firsts_host):
                req.slot = slot
                self._active[slot] = True
                self._requests[slot] = req
                self._emit(req, [first])

    def _prefill_columns(
        self, rows: int, row_capacity: int, plan: list[tuple[int, int]]
    ) -> tuple[int, int]:
        """(visited, spared) row columns of a plan's chunk prefills over a wave of
        ``rows``: what ``cache_prefill_attention``'s walk scores of each row
        (``prefill_columns_visited``: its trip count x its block, from the
        chunk's offset and length and the row's capacity) and the rest of the
        row, a layer that attends over a row; those that slide over a row
        (``ModelConfig.sliding_row_attentions``) start at their window."""
        from prime_tpu.ops.attention import prefill_columns_visited

        config, sliding = self.config, self.config.sliding_row_attentions
        visited = sum(
            (config.row_attentions - sliding) * prefill_columns_visited(off, size, row_capacity)
            + sliding * prefill_columns_visited(off, size, row_capacity, config.sliding_window)
            for off, size in plan
        )
        offered = config.row_attentions * len(plan) * row_capacity
        return rows * visited, rows * (offered - visited)

    def _state_programs(self):
        """(snapshot, restore), built on first use: a wave member's state leaves
        taken out of a staging row, and a stored snapshot copied for a new row.
        Both give new buffers: the row is donated to the next chunk's program,
        and a snapshot on the radix tree must outlive the row it seeds. A
        program a wave width (the leaves do not grow with the row's capacity),
        one for the restore."""
        if self._snapshot_fn is not None:
            return self._snapshot_fn, self._restore_fn
        import jax
        import jax.numpy as jnp

        from prime_tpu.models.llama import CACHE_LEAVES

        def snapshot(leaves, member):
            with jax.named_scope("serve.state_snapshot"):
                return {
                    name: jax.lax.dynamic_slice_in_dim(
                        leaf, member, 1, axis=CACHE_LEAVES[name].slot_axis
                    )
                    for name, leaf in leaves.items()
                }

        def restore(leaves):
            with jax.named_scope("serve.state_restore"):
                return {name: jnp.copy(leaf) for name, leaf in leaves.items()}

        self._snapshot_fn, self._restore_fn = jax.jit(snapshot), jax.jit(restore)
        return self._snapshot_fn, self._restore_fn

    def _take_snapshots(self, row, position: int, lengths: list[int], snapshots: list) -> None:
        """Copy the state leaves of the stored members whose prompt reaches
        ``position`` out of ``row``. The copy is the loop's own work between two
        chunks: its seconds are ``prefix_store``'s, and the device trace shows it
        under ``serve.state_snapshot``."""
        snapshot, _ = self._state_programs()
        with self._clock.span("serve.state_snapshot", "prefix_store", position=position):
            leaves = _snapshot_leaves(row)
            for member, taken in enumerate(snapshots):
                if position <= lengths[member]:
                    taken[position] = snapshot(leaves, _upload(member, np.int32))

    def _make_finalize_batch(self):
        import jax
        import jax.numpy as jnp

        cache_spec = self._cache_constraint()

        def finalize_batch(
            cache, last, temps, top_ps, adapter_slots, rows, logits, lengths,
            slots, temps_new, top_ps_new, adapter_ids_new, rng,
        ):
            # splice every staged row (batch axis N on the rows' slot dim)
            # into the engine cache and sample all first tokens — one
            # dispatch for the whole admission wave
            n = lengths.shape[0]
            zero = jnp.zeros((), jnp.int32)

            def splice_all(spec, cache_leaf, rows_leaf):
                # a staged row lands at the start of its slot: a leaf with a
                # capacity axis by the row's columns (the slot's length masks
                # the rest), any other leaf whole (a ring, a recurrent state:
                # the new tenant's, so nothing of the old one's outlives it)
                def body(i, acc):
                    row_i = jax.lax.dynamic_slice_in_dim(rows_leaf, i, 1, axis=spec.slot_axis)
                    at = [zero] * acc.ndim
                    at[spec.slot_axis] = slots[i]
                    return jax.lax.dynamic_update_slice(acc, row_i, tuple(at))

                return jax.lax.fori_loop(0, n, body, cache_leaf)

            new_cache = _constrain(_map_cache_leaves(splice_all, cache, rows), cache_spec)
            firsts = _sample_batch(logits[:, 0, :], temps_new, top_ps_new, rng)
            # the first sampled tokens' KV is not in the cache yet: the next
            # decode step writes each at position ``length`` (put() scatters
            # at cache_lengths), so slot lengths stay the prompt lengths here
            new_cache = new_cache._replace(
                lengths=cache.lengths.at[slots].set(lengths)
            )
            return (
                new_cache,
                last.at[slots].set(firsts),
                temps.at[slots].set(temps_new),
                top_ps.at[slots].set(top_ps_new),
                adapter_slots.at[slots].set(adapter_ids_new),
                firsts,
            )

        return jax.jit(finalize_batch, donate_argnums=(0, 1, 2, 3, 4))

    # ---- prompt-prefix KV reuse (block radix tree, serve/prefix_cache.py) ----

    def _prefix_key(self, ids: list[int], adapter_idx: int) -> list[int]:
        """The radix-tree key space for a request's prompt: raw token ids
        for base traffic (byte-identical to a bankless engine), salted by
        ``adapter_idx * ADAPTER_KEY_STRIDE`` for adapter traffic — cached KV
        is only valid under the adapter that computed it, so each adapter's
        paths live in a disjoint key space and a cross-adapter prefix hit is
        impossible by construction. /admin/kv export/import stays in the
        base space (adapter paths never ship over the disagg wire — a
        migrated adapter request degrades to an honest cold resume)."""
        if not adapter_idx:
            return list(ids)
        off = adapter_idx * ADAPTER_KEY_STRIDE
        return [t + off for t in ids]

    def _prefix_match(self, ids: list[int]):
        """ONE owner of the prefix-hit math (clamp to len-1 so at least one
        real token is always prefilled — the finalize step needs the last
        prompt position's logits — block alignment via the cache's walk,
        min_prefix threshold): returns a PINNED PrefixMatch or None. The
        caller must release() it after consuming the segments."""
        if self.prefix_cache is None:
            return None
        match = self.prefix_cache.match(ids, limit=len(ids) - 1)
        if match is None:
            return None
        if match.length < self.min_prefix:
            self.prefix_cache.release(match)
            return None
        return match

    def _prefix_match_lens(self, ids: list[int]) -> tuple[int, int]:
        """Routing peek for _admit: (usable cached-prefix length, K/V tokens the
        walk matched: more than the first only past a cache's deepest snapshot)
        without pinning or LRU touches (the seeded path re-matches and pins)."""
        if self.prefix_cache is None:
            return 0, 0
        length, matched = self.prefix_cache.match_lens(ids, limit=len(ids) - 1)
        return (length if length >= self.min_prefix else 0), matched

    def _make_assemble_row(self):
        """One jitted program per (segment-shape tuple, takes, target
        capacity): dynamic-update-slice concatenation of matched segments
        into a FRESH staging row (jit outputs are new buffers, so the row is
        donation-safe for chunk_prefill and never aliases cached segments).
        Partial takes slice inside the program — no host-side per-leaf ops."""
        import jax

        from prime_tpu.models.llama import init_cache

        config, dtype, quantized = self.config, self._dtype, self.kv_quant
        row_constraint = self._row_constraint()

        def assemble(segments, takes, target_cb, state=None):
            row = init_cache(config, 1, target_cb, dtype=dtype, quantized=quantized)
            if state is not None:  # the snapshot the match ends at: new buffers, as all of the row
                row = row._replace(**state)
            out = _capacity_leaves(row)
            off = 0
            for seg, take in zip(segments, takes):
                for name, leaf in seg.items():
                    piece = leaf[..., :take]
                    start = (0,) * (leaf.ndim - 1) + (off,)
                    out[name] = jax.lax.dynamic_update_slice(out[name], piece, start)
                off += take
            # lengths stay init_cache's zeros: chunked prefill masks via
            # prefill_offset, and finalize sets slot lengths explicitly.
            # Sharded replica: the assembled row keeps the segments' tp
            # placement (cached segments were sliced from constrained rows),
            # so a prefix hit never funnels KV through one device.
            return _constrain(row._replace(**out), row_constraint)

        return jax.jit(assemble, static_argnums=(1, 2))

    def _restored_state(self, match) -> dict[str, Any] | None:
        """A copy of the snapshot a match ends at, for the admission's row (None
        for a model whose slots hold no state)."""
        if match.state is None:
            return None
        return self._state_programs()[1](match.state)

    def _prefix_seed(self, ids: list[int], row_cb: int, ctx: TraceContext | None = None):
        """Seed an admission's staging row: on a hit, ONE assemble_row
        dispatch splices every matched segment into a fresh row at ``row_cb``
        capacity and returns (start, row) with [0, start) already computed;
        on a miss, a fresh empty row. start is block-aligned (chunk_plan's
        invariant). The matched path is pinned until the dispatch is
        enqueued, so a concurrent store's eviction can never free a segment
        mid-assembly."""
        from prime_tpu.models.llama import init_cache

        match = self._prefix_match(ids)
        if match is None:
            return 0, init_cache(
                self.config, 1, row_cb, dtype=self._dtype, quantized=self.kv_quant
            )
        host_tokens = match.host_tokens
        # paged fast path: every matched segment device-resident as pool
        # pages and the whole run fits the row — gather in place, no copy.
        # Anything else (host-resident entries, loose fallback segments,
        # over-long runs) takes the contiguous assemble as before.
        table = self._paged_seed_table(match, row_cb)
        path = "paged" if table is not None else "copy"
        try:
            # tier annotates the span so trace evidence distinguishes a pure
            # HBM hit from one that paid a host->device re-upload first
            with self._clock.span(
                "serve.assemble", "prefix_seed", context=ctx,
                hit_tokens=match.length, segments=len(match.entries),
                row_capacity=row_cb, tier="host" if host_tokens else "device",
                host_tokens=host_tokens, path=path,
            ), self.profiler.step(
                "assemble", pre=self._last, batch=1, steps=match.length
            ) as prof_step:
                t_seed = self._clock.switched_at  # the span's start
                if table is not None:
                    row = self._paged_seed_row(table, row_cb, self._restored_state(match))
                else:
                    if self._assemble_fn is None:
                        self._assemble_fn = self._make_assemble_row()
                    if host_tokens:
                        # re-upload the spilled segments in place (still
                        # pinned — the rebalance this may trigger skips the
                        # match path)
                        self.prefix_cache.promote(match)
                    segments = [
                        seg.materialize() if hasattr(seg, "materialize") else seg
                        for seg in match.segments()
                    ]
                    row = self._assemble_fn(segments, match.takes(), row_cb, match.state)
                prof_step.fence(row.k)
        finally:
            self.prefix_cache.release(match)
        # the span's end: the seeding dispatch, as before
        self._m_prefix_seed_s.observe(self._clock.switched_at - t_seed, path=path)
        self._m_prefix_hits.inc()
        if table is not None:
            self._m_prefix_paged_seeds.inc()
        else:
            self._m_prefix_assembles.inc()
        if match.device_tokens:
            self._m_prefix_hit_tokens.observe(match.device_tokens, tier="device")
        if host_tokens:
            self._m_prefix_hit_tokens.observe(host_tokens, tier="host")
        self._m_prefix_match_tokens.inc(match.length, kind="used")
        if match.state is not None:
            self._m_prefix_match_tokens.inc(match.matched - match.length, kind="beyond_snapshot")
            self._m_state_snapshots.inc(event="restored")
        self._sync_prefix_metrics()
        return match.length, row

    def _ensure_kv_pool(self):
        """The engine's page pool, created on first use (leaf dtypes/shapes
        are only known once a segment exists — the pool sizes itself from the
        first store). None when paging is disabled for this engine."""
        if not self.paged_prefix:
            return None
        if self._kv_pool is None:
            from prime_tpu.serve.kv_pool import PagedKVPool

            # the pool shares the device-tier byte budget: every page the
            # pool holds is a byte the radix accounting already charges
            # (PagedSegment.nbytes == the loose form's bytes), so the LRU
            # keeps bounding the SUM of pooled and loose segments. Where the
            # cache keeps snapshots of state too, the pool is allocated for
            # K/V's share of the budget at one snapshot a stride (what the
            # stride rule bounds them to): budget x stride K/V / (stride K/V +
            # snapshot), 53% for Olmo-Hybrid; the snapshots take the rest as
            # they come. The budget then bounds what the device really holds
            budget = int(self.prefix_cache_mb * 2**20)
            if self.state_stride:
                state, token = slot_bytes(self._cache)
                budget = budget * self.state_stride * token // (self.state_stride * token + state)
            self._kv_pool = PagedKVPool(budget, page_tokens=MIN_BUCKET)
        return self._kv_pool

    def _paged_seed_table(self, match, row_cb: int):
        """The page-id table for a paged seed, or None when the match must
        take the copy path: a host-resident entry, a loose (pool-full
        fallback or imported) segment, a partial take that isn't
        page-aligned, or a run longer than the row. Reads the pin-time
        snapshots, like the assemble path."""
        pool = self._kv_pool
        if pool is None or match.host_tokens:
            return None
        page_tokens = pool.page_tokens
        if row_cb % page_tokens:
            return None
        pages: list[int] = []
        for seg, take in zip(match.segments_snapshot, match.takes()):
            seg_pages = getattr(seg, "pages", None)
            if seg_pages is None or take % page_tokens:
                return None
            pages.extend(seg_pages[: take // page_tokens])
        if not pages or len(pages) > row_cb // page_tokens:
            return None
        table = np.full(row_cb // page_tokens, -1, dtype=np.int32)
        table[: len(pages)] = pages
        return table

    def _paged_seed_row(self, table, row_cb: int, state: dict[str, Any] | None = None):
        """Seed a staging row by gathering pool pages in place: one
        paged-gather dispatch per leaf, zeros past the table's sentinels —
        element-for-element the row assemble_row would build (the bit-identity
        tests/test_engine.py pins). Like assemble, lengths stay zeros:
        chunked prefill masks via prefill_offset and finalize sets slot
        lengths explicitly."""
        import jax.numpy as jnp

        from prime_tpu.models.llama import KVCache

        out = self._kv_pool.gather_row(table)
        return KVCache(
            k=out["k"], v=out["v"],
            lengths=jnp.zeros((1,), dtype=jnp.int32),
            k_scale=out.get("k_scale"), v_scale=out.get("v_scale"),
            **(state or {}),
        )

    def _row_slicer(self, row):
        """Segment extractor for _store_prefix: slots [start, stop) of every
        capacity-axis leaf of a finalized batch-1 staging row, as a plain
        dict (lengths is capacity-free and dropped — assemble rebuilds it).
        Each call is one lazy jnp slice per leaf, and the cache only invokes
        it for the genuinely new tail of the trie path. With paging enabled
        the slice is stored into the page pool and a PagedSegment enters the
        tree instead; a full (or disabled-by-budget) pool falls back to the
        loose slice — that segment's future hits just take the copy path."""
        src_cb = row.capacity

        def slicer(start: int, stop: int):
            out = {}
            for name, leaf in _capacity_leaves(row).items():
                assert leaf.shape[-1] == src_cb, f"{name} is not capacity-major"
                out[name] = leaf[..., start:stop]
            return out

        pool = self._ensure_kv_pool()
        if pool is None:
            return slicer

        from prime_tpu.serve.kv_pool import PagedSegment

        def paged_slicer(start: int, stop: int):
            seg = slicer(start, stop)
            pages = pool.store(seg)
            if pages is None:
                return seg
            return PagedSegment(pool, pages, stop - start)

        return paged_slicer

    def _store_prefix(self, ids: list[int], row, snapshots: dict[int, Any] | None = None) -> None:
        """Split the finalized staging row into block segments and insert
        them along the radix path: blocks already cached are deduplicated
        (shared bytes stored once), only the divergent tail allocates, and
        the byte-budget LRU evicts cold leaves afterwards. Only full blocks
        of REAL tokens are stored — the padded row tail never enters the
        cache."""
        cache = self.prefix_cache
        if cache is None:
            return
        aligned = (len(ids) // MIN_BUCKET) * MIN_BUCKET
        if aligned < self.min_prefix:
            return
        spills_before = cache.spills
        spilled_bytes_before = cache.spilled_bytes
        spill_s_before = cache.spill_seconds
        with self._clock.enter("prefix_store"):
            cache.insert(list(ids[:aligned]), self._row_slicer(row), snapshots)
        if cache.spills > spills_before:
            # spills force a device sync (device_get) on the store path —
            # leave trace evidence so the profiler's tier table can price
            # them. Duration is the time inside to_host only (the cache
            # accumulates it around the converter), not the whole insert.
            TRACER.emit(
                "serve.spill", cache.spill_seconds - spill_s_before,
                segments=cache.spills - spills_before,
                bytes=cache.spilled_bytes - spilled_bytes_before,
            )
        self._sync_prefix_metrics()

    def _sync_prefix_metrics(self) -> None:
        """Publish the cache's monotonic counters (spills, re-uploads,
        deletions) into the registry as deltas since the last sync, and
        refresh the per-tier footprint gauges. ONE owner of the cache->
        registry translation, called from the seed/store paths and the
        stats refresh."""
        cache = self.prefix_cache
        if cache is None:
            return
        for counter, attr, labels in (
            (self._m_prefix_spills, "spills", {}),
            (self._m_prefix_spilled_bytes, "spilled_bytes", {}),
            (self._m_prefix_reuploads, "reuploads", {}),
            (self._m_prefix_reupload_bytes, "reupload_bytes", {}),
            (self._m_prefix_evictions, "evictions", {}),
            (self._m_state_snapshots, "snapshots_stored", {"event": "stored"}),
            (self._m_state_snapshots, "snapshots_evicted", {"event": "evicted"}),
        ):
            current = getattr(cache, attr)
            delta = current - self._prefix_seen[attr]
            if delta > 0:
                counter.inc(delta, **labels)
                self._prefix_seen[attr] = current
        self._m_prefix_state_bytes.set(cache.state_bytes)
        self._m_prefix_bytes.set(cache.bytes)
        self._m_prefix_host_bytes.set(cache.host_bytes)
        self._m_prefix_nodes.set(cache.nodes)
        self._m_prefix_host_nodes.set(cache.host_nodes)

    def prefix_digest(self, max_entries: int = 256) -> list[int]:
        """Exact hot-prefix advertisement from the radix tree: the rolling
        block-hash chain (serve/digest.py) of every cached path, root-first
        so truncation keeps the hottest shared preambles. The server merges
        this into /healthz's ``prefix_digest`` field; the fleet balancer
        uses it to route saturation fallbacks to the replica holding the
        longest cached prefix. Reads engine-thread-owned structure — the
        server calls it through the loop-ticked stats snapshot, never live."""
        if self.prefix_cache is None:
            return []
        from prime_tpu.serve.digest import prefix_hashes

        out: list[int] = []
        seen: set[int] = set()
        for path in self.prefix_cache.iter_prefixes(limit=max_entries):
            for h in prefix_hashes(path):
                if h not in seen:
                    seen.add(h)
                    out.append(h)
            if len(out) >= max_entries:
                break
        return out[:max_entries]

    # ---- prefix-KV wire export/import (disaggregated serving) ----

    def export_kv(self, ids: list[int], timeout: float = 30.0) -> bytes | None:
        """Serialize the longest cached prefix of ``ids`` into the versioned
        wire payload — what a prefill replica's GET /admin/kv serves.
        Thread-safe, and the expensive half runs OFF the engine loop: only
        the radix-tree walk that PINS the match path (and the final
        release) marshal onto the loop as O(path-length) jobs; the
        serialization itself — the per-leaf device_get + memcpy of a
        potentially multi-MB payload — runs on the CALLING thread against
        the match's pin-time snapshots (prefix_cache.serialize_match). The
        pins survive concurrent store-path inserts (``_split`` transfers
        them, the PR 12 enabler), so an ``any``-role exporter no longer
        stalls its co-resident decode pipeline for the export's duration —
        the loop pays two queue hops instead of the whole device_get.
        Synchronous owners (tests, bench, the loop itself) keep the direct
        one-shot path. Returns None when nothing usable is cached."""
        if self.prefix_cache is None or len(ids) < self.min_prefix:
            return None
        self.prefix_cache.refuse_wire("export")  # a cache that keeps state, by name
        if self._thread is None or self._thread is threading.current_thread():
            return self._kv_execute("export", list(ids))
        match = self._kv_call("pin", list(ids), timeout)
        if match is None:
            return None
        try:
            payload = self.prefix_cache.serialize_match(match)
        finally:
            # the release mutates tree refcounts -> engine-thread-owned,
            # marshalled like the pin (a leaked pin would exempt the path
            # from the byte-budget LRU forever)
            self._kv_call("release", match, timeout)
        # counters on the calling thread: the registry is thread-safe, and
        # the direct path's _kv_execute owns its own increments
        self._m_kv_exports.inc()
        self._m_kv_export_bytes.inc(len(payload))
        return payload

    def import_kv(self, payload: bytes, timeout: float = 30.0) -> int:
        """Apply a wire payload to this engine's prefix cache — what a
        decode replica's PUT /admin/kv lands. The next admission whose
        prompt shares the imported path seeds its staging row from the
        planted segments (one assemble_row dispatch, zero prefix recompute).

        The payload decode/validation (including the one big host-side
        memcpy rebuilding the leaves) runs on the CALLING thread (an HTTP
        handler); the loop pays only the radix insert, whose slicer uploads
        JUST the genuinely new tail — a repeat migration of an
        already-cached path (the shared-preamble case the balancer's
        affinity concentrates) walks, dedups, and uploads nothing. Raises
        ValueError on a version/shape mismatch (validated before the tree
        is touched). Returns the KV bytes planted after dedup."""
        if self.prefix_cache is None:
            raise ValueError("prefix cache disabled; nothing to import into")
        self.prefix_cache.refuse_wire("import")
        from prime_tpu.serve.prefix_cache import decode_wire_payload

        tokens, leaves = decode_wire_payload(payload, self.prefix_cache.block)
        return self._kv_call("import", (tokens, leaves), timeout)

    def _kv_call(self, kind: str, arg: Any, timeout: float):
        if self._thread is None or self._thread is threading.current_thread():
            return self._kv_execute(kind, arg)
        reply: queue.Queue = queue.Queue()
        self._kv_jobs.put((kind, arg, reply))
        self._wake.set()
        try:
            ok, value = reply.get(timeout=timeout)
        except queue.Empty:
            raise TimeoutError(
                f"KV {kind} not serviced within {timeout}s (engine loop busy "
                "or wedged)"
            ) from None
        if not ok:
            raise value
        return value

    def _service_kv_jobs(self) -> bool:
        """Drain pending /admin/kv jobs on the engine thread (start of every
        tick — also reachable through the idle loop's wake). Failures travel
        back to the waiting caller, never kill the loop."""
        did = False
        while True:
            try:
                kind, arg, reply = self._kv_jobs.get_nowait()
            except queue.Empty:
                return did
            did = True
            try:
                reply.put((True, self._kv_execute(kind, arg)))
            except Exception as e:  # noqa: BLE001 — the caller gets the error
                reply.put((False, e))

    def _kv_execute(self, kind: str, arg: Any):
        if kind == "export":
            if self.prefix_cache is None or len(arg) < self.min_prefix:
                return None
            payload = self.prefix_cache.export_segments(arg)
            if payload is not None:
                self._m_kv_exports.inc()
                self._m_kv_export_bytes.inc(len(payload))
            return payload
        if kind == "pin":
            # off-loop export, step 1: pin the match path on the loop (the
            # walk touches LRU stamps and refcounts — tree-owner state);
            # serialization then happens on the caller's thread
            match = self.prefix_cache.match(arg, limit=len(arg))
            if match is not None:
                # paged snapshots read the shared page pool, and the pool's
                # donated store may retire its buffers under a concurrent
                # off-loop reader — materialize them HERE, on the loop, so
                # the caller-thread serialize only touches private arrays
                for i, seg in enumerate(match.segments_snapshot):
                    if hasattr(seg, "materialize"):
                        match.segments_snapshot[i] = seg.materialize()
            return match
        if kind == "release":
            self.prefix_cache.release(arg)
            return None
        # import: arg is the pre-decoded host (tokens, leaves) pair from
        # import_kv — the insert's slicer uploads only the new tail
        tokens, leaves = arg
        added = self.prefix_cache.insert_segments(tokens, leaves)
        self._m_kv_imports.inc()
        self._m_kv_import_bytes.inc(added)
        self._sync_prefix_metrics()
        return added

    def _emit(self, req: EngineRequest, token_ids: list[int]) -> None:
        """Feed decoded ids to the request, honoring EOS and max_new_tokens;
        retire the slot when the request completes."""
        out: list[int] = []
        for t in token_ids:
            if req.emitted >= req.max_new_tokens:
                break
            if t == self.eos_id:
                req.done = True
                break
            out.append(t)
            req.emitted += 1
        if out:
            req.events.put(out)
            self._m_tokens.inc(len(out))
            if self.adapter_bank is not None:
                self._m_adapter_tokens.inc(len(out), adapter=req.adapter or "base")
            if not req.first_token_at:
                req.first_token_at = time.monotonic()
                if req.submitted_at:
                    self._m_ttft.observe(req.first_token_at - req.submitted_at)
                    if self.adapter_bank is not None:
                        self._m_adapter_ttft.observe(
                            req.first_token_at - req.submitted_at,
                            adapter=req.adapter or "base",
                        )
                    self.flight.event(
                        req.id, "first_token",
                        ttft_ms=round(
                            (req.first_token_at - req.submitted_at) * 1e3, 3
                        ),
                    )
        if req.done or req.emitted >= req.max_new_tokens:
            req.done = True
            self._m_completed.inc()
            if req.first_token_at and req.emitted > 1:
                self._m_tpot.observe(
                    (time.monotonic() - req.first_token_at) / (req.emitted - 1)
                )
            self._retire_flight(req, "completed")
            if req.slot >= 0:
                self._active[req.slot] = False
                self._requests.pop(req.slot, None)
            req.events.put(None)

    def stats(self) -> dict:
        """Legacy JSON counters for the server's /metrics route — same keys
        and shape as the pre-registry bare ints, plus the pipeline and
        prefix-cache fields (additive). While the engine loop is running,
        this returns the loop's end-of-tick snapshot (taken under a small
        lock), NOT a live read: every field in one response reflects the
        same loop state, closing the ADVICE engine.py:1008 note about
        queue/slot reads racing mid-tick. Callers driving the engine
        synchronously (tests, bench) get a fresh computation — they own the
        state, so there is nothing to race."""
        if self._thread is None or self._thread is threading.current_thread():
            return self._refresh_stats()
        with self._stats_lock:
            snapshot = self._stats_snapshot
        if snapshot is None:  # loop started but no tick completed yet
            return self._refresh_stats()
        return dict(snapshot)

    def prefix_digest_snapshot(self) -> list[int]:
        """Thread-safe read of the hot-prefix digest for /healthz: the
        loop-ticked snapshot when the engine thread owns the tree, a fresh
        walk when the caller does (synchronous tests/bench)."""
        if self._thread is None or self._thread is threading.current_thread():
            return self.prefix_digest()
        with self._stats_lock:
            return list(self._digest_snapshot)

    def _refresh_stats(self) -> dict:
        """Compute the full stats dict from live state and publish it as the
        snapshot stats() serves to other threads. Called at the end of every
        tick() by the engine loop (and directly by synchronous owners)."""
        with self._clock.enter("stats"):
            return self._publish_stats()

    def _publish_stats(self) -> dict:
        # the loop's seconds since the last tick, by phase, and the starved ones
        self._clock.flush()
        self._m_active_slots.set(int(self._active.sum()))
        self._m_queue_depth.set(self.queue_depth())
        # HBM/live-buffer gauges: rate-limited inside, no-op when the
        # profiler is inactive, so steady state with profiling off stays
        # untouched.
        self.profiler.poll_memory()
        if self.prefix_cache is not None:
            self._sync_prefix_metrics()
            now = time.monotonic()
            if now - self._digest_at >= self.digest_refresh_s:
                digest = self.prefix_digest()
                with self._stats_lock:
                    self._digest_snapshot = digest
                self._digest_at = now
        values = self.registry.values()
        spec_ratio = (
            self._spec_accepted / self._spec_proposed if self._spec_proposed else 0.0
        )
        self._m_spec_ratio.set(spec_ratio)
        snapshot = {
            "requests_admitted": int(values["serve_requests_admitted_total"]),
            "requests_completed": int(values["serve_requests_completed_total"]),
            "requests_cancelled": int(values["serve_requests_cancelled_total"]),
            "requests_failed": int(values["serve_requests_failed_total"]),
            "tokens_emitted": int(values["serve_tokens_emitted_total"]),
            "prefix_hits": int(values["serve_prefix_hits_total"]),
            "batched_admission_waves": int(values["serve_batched_admission_waves_total"]),
            "active_slots": int(values["serve_active_slots"]),
            "queue_depth": int(values["serve_queue_depth"]),
            "max_slots": int(self.max_slots),
            "max_queue": int(self.max_queue),
            "mesh_devices": int(self.mesh_devices),
            "mesh_axes": dict(self.mesh_axes),
            "adapters_loaded": int(values["serve_adapters_loaded"]),
            "adapters": list(
                self.adapter_bank.adapter_names if self.adapter_bank else ()
            ),
            "adapter_weights": dict(self.adapter_weights),
            "state": "draining" if self._draining else "running",
            "overlap": bool(self.overlap),
            "speculative": bool(self.speculative),
            "draft_len": int(self.draft_len) if self.speculative else 0,
            "spec_accept_ratio": round(spec_ratio, 4),
            "inflight_depth": int(values["serve_inflight_depth"]),
            **self._clock.totals(),  # loop_s, loop_starved_s by phase; loop_starved_unseen_s
            "wasted_decode_tokens": int(values["serve_wasted_decode_tokens_total"]),
            "warmup_programs": int(values["serve_warmup_programs"]),
            "prefix_cache_bytes": int(values["serve_prefix_cache_bytes"]),
            "prefix_cache_host_bytes": int(values["serve_prefix_cache_host_bytes"]),
            "prefix_host_tier_disabled": int(values["serve_prefix_host_tier_disabled"]),
            "prefix_cache_nodes": int(values["serve_prefix_cache_nodes"]),
            "prefix_evictions": int(values["serve_prefix_evictions_total"]),
            "prefix_spills": int(values["serve_prefix_spills_total"]),
            "prefix_reuploads": int(values["serve_prefix_reuploads_total"]),
            "prefix_assembles": int(values["serve_prefix_assembles_total"]),
            "prefix_paged_seeds": int(values["serve_prefix_paged_seeds_total"]),
            "kv_exports": int(values["serve_kv_exports_total"]),
            "kv_imports": int(values["serve_kv_imports_total"]),
            "program_builds": self._clock.builds(),
            "uptime_s": round(time.monotonic() - self._t0, 3),
        }
        with self._stats_lock:
            self._stats_snapshot = snapshot
        return dict(snapshot)


class EngineBackend:
    """Joins a ContinuousBatchingEngine with a tokenizer — the backend
    `prime serve --continuous` hands to InferenceServer. Exposes both the
    blocking generate() protocol (non-streaming requests, eval runner
    compatibility) and submit/stream for true per-token SSE."""

    concurrent = True  # the server must NOT serialize requests behind a lock

    def __init__(self, engine: ContinuousBatchingEngine, tokenizer: Any) -> None:
        self.engine = engine
        self.tokenizer = tokenizer

    def stats(self) -> dict:
        """Forward the engine's observability counters (server /metrics)."""
        return self.engine.stats()

    def prefix_digest(self) -> list[int]:
        """The engine's hot-prefix advertisement (server /healthz)."""
        return self.engine.prefix_digest_snapshot()

    @property
    def prefix_cache_enabled(self) -> bool:
        """Whether /healthz should advertise a prefix digest at all: a
        cacheless replica advertising prompts it cannot assemble would
        steal cache-aware reroutes it then serves with a full recompute."""
        return self.engine.prefix_cache is not None

    @property
    def adapter_names(self) -> tuple[str, ...]:
        """Loaded multi-LoRA adapter names (base excluded): the server's
        model registry resolves the OpenAI ``model`` field against these,
        /v1/models lists them, and /healthz advertises them so the fleet
        balancer can route adapter traffic to a replica that holds the
        adapter (docs/architecture.md "Multi-LoRA serving")."""
        bank = self.engine.adapter_bank
        return bank.adapter_names if bank is not None else ()

    def export_kv_text(self, prompt: str) -> bytes | None:
        """GET /admin/kv?prompt=…: tokenize exactly like submit_text's
        untemplated path (the router exports the same rendered prompt text
        it forwards) and serialize the cached prefix over the wire format."""
        ids = self.tokenizer.encode(prompt, add_special_tokens=True)
        return self.engine.export_kv(ids)

    def export_kv_messages(self, messages, max_new_tokens: int = 1) -> bytes | None:
        """GET /admin/kv with a chat-request body: tokenize the messages
        EXACTLY like a chat admission would — the tokenizer's own chat
        template when it has one (the templated path adds no special
        tokens), the generic role-tagged render otherwise, tail-kept like
        submit_text — so the exported ids always name the radix path the
        admission actually stored, whatever tokenizer the backend serves.
        The text-query export above cannot promise that for templated
        backends (the router's rendering differs from the template), which
        is why the router's migration path exports through this."""
        from prime_tpu.serve.server import render_chat_prompt

        tokenizer = self.tokenizer
        templated = hasattr(tokenizer, "render_chat")
        prompt = (
            tokenizer.render_chat(messages)
            if templated
            else render_chat_prompt(messages)
        )
        ids = tokenizer.encode(prompt, add_special_tokens=not templated)
        keep = self.engine.capacity - max_new_tokens - self.engine.spec_overhead
        if keep <= 0:
            return None
        return self.engine.export_kv(ids[-keep:])

    def export_kv_ids(self, ids) -> bytes | None:
        """GET /admin/kv?ids=…: exact id-space export for callers that share
        the replica's tokenization."""
        return self.engine.export_kv(list(ids))

    def import_kv(self, payload: bytes) -> int:
        """PUT /admin/kv: plant a wire payload in this replica's cache."""
        return self.engine.import_kv(payload)

    @property
    def registry(self):
        """The engine's metrics Registry — InferenceServer renders it into
        the Prometheus exposition at /metrics?format=prometheus."""
        return self.engine.registry

    @property
    def flight(self):
        """The engine's flight recorder — InferenceServer serves it at
        GET /debug/requests[/{id}]."""
        return self.engine.flight

    @property
    def profiler(self):
        """The engine's device-time profiler — InferenceServer drives it
        from the /admin/profile start/stop capture endpoint."""
        return self.engine.profiler

    def submit_text(
        self,
        prompt: str,
        max_new_tokens: int,
        temperature: float,
        top_p: float = 1.0,
        templated: bool = False,
        trace: TraceContext | None = None,
        adapter: str | None = None,
    ) -> EngineRequest:
        ids = self.tokenizer.encode(prompt, add_special_tokens=not templated)
        # keep the tail if the prompt exceeds what the slot can hold
        # (speculation reserves spec_overhead extra verify slots per row)
        keep = self.engine.capacity - max_new_tokens - self.engine.spec_overhead
        if keep <= 0:
            raise ValueError(
                f"max_new_tokens ({max_new_tokens}) leaves no room for a "
                f"prompt in a slot of capacity {self.engine.capacity}"
            )
        return self.engine.submit(
            ids[-keep:], max_new_tokens=max_new_tokens,
            temperature=temperature, top_p=top_p, trace=trace, adapter=adapter,
        )

    def stream_text(self, req: EngineRequest, timeout: float | None = 120.0):
        """Yield text deltas as the request decodes. Detokenization is
        incremental: decode the accumulated ids each flush and emit the new
        suffix, withholding trailing replacement chars (a partial multi-byte
        sequence mid-token would otherwise flicker)."""
        ids: list[int] = []
        sent = ""
        for batch in req.tokens(timeout=timeout):
            ids.extend(batch)
            full = self.tokenizer.decode(ids)
            if full.startswith(sent):
                delta = full[len(sent):]
                if delta.endswith("�"):
                    continue  # partial multi-byte sequence; wait for more ids
                if delta:
                    sent = full
                    yield delta
        full = self.tokenizer.decode(ids)
        if full.startswith(sent) and len(full) > len(sent):
            yield full[len(sent):]

    def generate(
        self,
        prompts: list[str],
        max_new_tokens: int,
        temperature: float,
        top_p: float = 1.0,
        templated: bool = False,
        trace: TraceContext | None = None,
        adapter: str | None = None,
    ) -> list[str]:
        reqs = [
            self.submit_text(
                p, max_new_tokens, temperature, top_p, templated, trace, adapter
            )
            for p in prompts
        ]
        return [self.tokenizer.decode(r.all_tokens()) for r in reqs]

    def drain(self) -> None:
        """Forward the server's drain hook: stop admitting, finish in-flight
        (docs/architecture.md "Serve fleet", drain protocol)."""
        self.engine.drain()

    @property
    def drained(self) -> bool:
        return self.engine.drained

    def shutdown(self) -> None:
        self.engine.shutdown()


def _sample_batch(logits, temps, top_ps, rng):
    """Per-row sampling over (S, V) logits with traced (S,) temperature and
    top_p. Greedy rows (temp == 0), plain-temperature rows, and nucleus rows
    share one program; the vocab sort (models.sampler.top_p_filter, the one
    owner of the nucleus math) only executes when some row wants it
    (lax.cond picks the branch at runtime)."""
    import jax
    import jax.numpy as jnp

    from prime_tpu.models.sampler import top_p_filter

    greedy = jnp.argmax(logits, axis=-1)
    scaled = logits / jnp.maximum(temps, 1e-6)[:, None]
    wants_nucleus = jnp.any((top_ps < 1.0) & (temps > 0.0))
    filtered = jax.lax.cond(
        wants_nucleus, lambda x: top_p_filter(x, top_ps), lambda x: x, scaled
    )
    sampled = jax.random.categorical(rng, filtered, axis=-1)
    return jnp.where(temps == 0.0, greedy, sampled).astype(jnp.int32)


def _upload(values, dtype):
    """A host list as a device array of exactly ``dtype``: the conversion is
    numpy's, so the upload is a transfer and never a program.
    ``jnp.asarray(list, dtype=...)`` builds a ``convert_element_type`` program
    per shape on first use, on the loop thread, and an admission wave's
    (members, chunk size) is a new shape whenever the traffic's timing makes
    it one."""
    import jax.numpy as jnp

    return jnp.asarray(np.asarray(values, dtype=dtype))
