"""Block-granular radix-tree prefix KV cache with a host-RAM spill tier.

The engine's prompt-prefix reuse layer (serve/engine.py `_prefix_seed` /
`_store_prefix`) used to be a flat newest-last list of at most N whole-prompt
staging rows: matching compared the full prompt against each stored prompt,
every hit paid a per-leaf copy/pad dispatch chain, two prompts sharing a
system preamble stored that preamble's KV twice, and nothing bounded the
cached bytes. This module replaces the storage side with a radix tree over
``block``-aligned token runs:

- **Nodes own segments.** Each edge of the (path-compressed) trie is a run of
  tokens whose length is a multiple of ``block``; the node owning the edge
  holds the KV *segment* for exactly those cache slots — a dict of
  capacity-axis slices of the staging-row pytree (k/v plus int8 scales when
  quantized). A prompt's prefix KV is the concatenation of the segments along
  its trie path, which is what the engine's single jitted ``assemble_row``
  dispatch rebuilds into a fresh donation-safe row.
- **Shared blocks are stored once.** Inserting a prompt walks the existing
  path first; only the divergent tail allocates a new node (one slice per
  leaf). A mid-edge divergence splits the edge at the block boundary — both
  halves keep their slot counts, so total bytes are conserved — and the new
  tail hangs off the split point. Two prompts sharing only a system preamble
  therefore share the preamble's segment.
- **Matching is leaf-level and partial.** ``match`` walks full blocks and may
  stop mid-edge: a cached 96-token prompt serves a 48-token prefix hit by
  taking the first 48 slots of its segment (sliced inside the assemble
  program, not on the host). Correctness leans on the radix invariant: a
  segment is only reachable along the exact token path from the root, so the
  KV it holds was computed under precisely the context the new prompt shares.
- **Two tiers under two byte budgets.** Every node's segment lives on one of
  two tiers: ``device`` (HBM — directly assemblable) or ``host`` (RAM — the
  spill tier). When device bytes exceed ``budget_bytes`` and a host budget is
  configured, the LRU *demotes* segments to host buffers (``to_host``, e.g.
  ``jax.device_get``) instead of freeing them; a later hit on a host-resident
  node *promotes* (re-uploads, ``to_device``) its segments and feeds them
  through the same one-dispatch assemble path. Only when host bytes exceed
  ``host_budget_bytes`` are LRU host **leaves** actually deleted (interior
  nodes are load-bearing for their descendants' paths). With no host budget
  the device LRU deletes leaves directly — the original single-tier behavior.
- **Refcount pins span tiers.** ``match`` pins its path so a hit mid-assembly
  can never have a segment evicted, demoted, or promoted-then-demoted out
  from under it; callers release the pin once the assemble dispatch is
  enqueued. ``promote`` on a pinned match flips its host entries to device in
  place — the radix/refcount/split invariants are tier-agnostic.

- **Snapshots of state ride the path.** A model whose slots hold recurrent
  state beside K/V (a matrix state and a conv tail a linear-attention layer)
  cannot start a prompt from stored K/V alone: the state at the segment's end
  is part of what was computed. A cache built with ``state_stride > 0`` keeps,
  on a node, *snapshots*: every state leaf of one slot as it stood after a
  given token of the node's edge (``_Node.states``, keyed by the offset inside
  the edge, so a snapshot belongs to exactly the token path that ends at it,
  and a split hands each half the snapshots that lie on it). ``insert`` takes
  them by absolute position; ``match`` cuts its walk back to the deepest
  snapshot at or under the matched length and hands it over (``PrefixMatch.
  state``): K/V matched beyond it is not used, and a path with K/V and no
  snapshot is a miss. Snapshots are counted in the same byte budget
  (``bytes`` holds them, ``state_bytes`` says how much of it they are) and go
  with their node when it is evicted. They have no host tier and no wire
  format: such a cache is built without a host budget and refuses export and
  import by name. With ``state_stride`` 0 none of this runs: a K/V-only
  model's tree and counters are what they were.

The tree is engine-thread-owned (like all engine device state): pin/release
make the eviction invariant explicit, not the structure thread-safe. The
module is deliberately jax-light — segments are opaque pytrees; only byte
accounting walks their leaves, and the tier converters are injected — so it
unit-tests with plain numpy arrays and identity converters.
"""

from __future__ import annotations

import heapq
import itertools
import json
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Callable, Iterator

__all__ = [
    "BlockPrefixCache",
    "KV_WIRE_VERSION",
    "PrefixMatch",
    "decode_wire_payload",
    "segment_nbytes",
]

TIER_DEVICE = "device"
TIER_HOST = "host"

# Versioned KV wire format (docs/architecture.md "Disaggregated serving"):
# the host-tier segment layout promoted to an explicit cross-process
# contract. A payload is one JSON header line (version, block size, token
# path, per-segment leaf manifests) followed by the raw leaf bytes in
# manifest order. import_segments REJECTS any version it does not speak —
# a fleet mid-rollout must fail a migration cleanly (the router falls back
# to colocated serving) rather than deserialize garbage KV.
KV_WIRE_VERSION = 1


def segment_nbytes(segment: Any) -> int:
    """Bytes of a segment pytree (sum over leaves of size*itemsize — the same
    accounting for bf16/fp32 KV, int8 KV, and fp32 scales, and for device
    arrays and their host copies, whose shapes/dtypes are identical). A
    segment exposing an integer ``nbytes`` of its own (kv_pool.PagedSegment,
    or a bare array) is taken at its word — the paged accounting counts the
    same bytes the loose form would."""
    nbytes = getattr(segment, "nbytes", None)
    if isinstance(nbytes, int) and not isinstance(nbytes, bool):
        return nbytes
    import jax

    return int(
        sum(x.size * x.dtype.itemsize for x in jax.tree_util.tree_leaves(segment))
    )


def decode_wire_payload(payload: bytes, block: int) -> tuple[list[int], dict]:
    """Validate a KV wire payload and rebuild its leaves host-side:
    ``(token path, {leaf name: full-length array})`` with every leaf's last
    axis concatenated across segments. Raises ValueError — before any cache
    is touched — on a version/block/shape/byte-count mismatch. Pure
    function of the payload: safe on any thread (the engine runs it on the
    HTTP handler thread so only the radix insert reaches its loop)."""
    import numpy as np

    header_raw, sep, raw = payload.partition(b"\n")
    if not sep:
        raise ValueError("KV wire payload has no header line")
    try:
        header = json.loads(header_raw)
    except (ValueError, UnicodeDecodeError) as e:
        raise ValueError(f"KV wire header is not JSON: {e}") from None
    if not isinstance(header, dict):
        raise ValueError("KV wire header must be an object")
    version = header.get("version")
    if version != KV_WIRE_VERSION:
        raise ValueError(
            f"KV wire version {version!r} not supported (speak {KV_WIRE_VERSION})"
        )
    if header.get("block") != block:
        raise ValueError(
            f"KV wire block {header.get('block')!r} != cache block {block}"
        )
    tokens = header.get("tokens")
    if (
        not isinstance(tokens, list)
        or not tokens
        or not all(isinstance(t, int) and not isinstance(t, bool) for t in tokens)
    ):
        raise ValueError("KV wire tokens must be a non-empty int list")
    if len(tokens) % block:
        raise ValueError(
            f"KV wire token path ({len(tokens)}) not aligned to block {block}"
        )
    manifests = header.get("segments")
    if not isinstance(manifests, list) or not manifests:
        raise ValueError("KV wire payload has no segment manifests")
    # rebuild the per-segment leaf arrays from the raw byte stream
    names: list[str] | None = None
    parts: dict[str, list] = {}
    takes: list[int] = []
    offset = 0
    for manifest in manifests:
        if not isinstance(manifest, dict):
            raise ValueError("KV wire segment manifest must be an object")
        take = manifest.get("take")
        leaves = manifest.get("leaves")
        if not isinstance(take, int) or take <= 0 or not isinstance(leaves, list):
            raise ValueError("KV wire segment manifest missing take/leaves")
        takes.append(take)
        seg_names = []
        for leaf in leaves:
            try:
                name = leaf["name"]
                dtype = np.dtype(leaf["dtype"])
                shape = tuple(int(d) for d in leaf["shape"])
                nbytes = int(leaf["nbytes"])
            except (KeyError, TypeError, ValueError) as e:
                raise ValueError(f"KV wire leaf manifest malformed: {e}") from None
            if not shape or shape[-1] != take:
                raise ValueError(
                    f"KV wire leaf {name!r} shape {shape} does not end in "
                    f"the segment take {take}"
                )
            count = 1
            for d in shape:
                count *= d
            if count * dtype.itemsize != nbytes or offset + nbytes > len(raw):
                raise ValueError("KV wire payload truncated or miscounted")
            arr = np.frombuffer(raw, dtype=dtype, count=count, offset=offset)
            offset += nbytes
            parts.setdefault(name, []).append(arr.reshape(shape))
            seg_names.append(name)
        if names is None:
            names = seg_names
        elif names != seg_names:
            raise ValueError("KV wire segments disagree on leaf names")
    if offset != len(raw):
        raise ValueError("KV wire payload has trailing bytes")
    if sum(takes) != len(tokens):
        raise ValueError(
            f"KV wire takes sum to {sum(takes)} but the token path has "
            f"{len(tokens)}"
        )
    full = {
        name: np.concatenate(arrays, axis=-1) if len(arrays) > 1 else arrays[0]
        for name, arrays in parts.items()
    }
    return list(tokens), full


def _common_len(a, b) -> int:
    n = min(len(a), len(b))
    for i in range(n):
        if a[i] != b[i]:
            return i
    return n


class _Node:
    """One radix-tree edge+node: ``tokens`` is the edge label (length a
    multiple of the cache block), ``segment`` the KV slices for those slots,
    ``tier`` where the segment currently lives. Children are keyed by the
    first block of their edge — siblings can never share a first block (they
    would have been one edge split later)."""

    __slots__ = (
        "tokens", "segment", "children", "parent", "refs", "last_used",
        "nbytes", "tier", "states", "state_nbytes",
    )

    def __init__(self, tokens: tuple[int, ...], segment: Any, parent: "_Node | None") -> None:
        self.tokens = tokens
        self.segment = segment
        self.children: dict[tuple[int, ...], _Node] = {}
        self.parent = parent
        self.refs = 0
        self.last_used = 0
        self.nbytes = segment_nbytes(segment) if segment is not None else 0
        self.tier = TIER_DEVICE
        # snapshots of a slot's state leaves after token ``offset`` of this edge
        # (1 .. len(tokens), a multiple of the block); their bytes are in nbytes
        self.states: dict[int, Any] = {}
        self.state_nbytes = 0


@dataclass
class PrefixMatch:
    """A pinned walk result: ``entries`` are (node, take) pairs root-to-deep;
    ``take`` is how many of the node's slots the match uses (a multiple of
    the block; full except possibly the last entry). ``length`` is their sum
    and ``host_tokens`` the portion resident on the host spill tier at match
    time (``promote`` must run before ``segments()`` when it is non-zero).
    Callers MUST ``release()`` the match once its segments have been read.

    ``segments()`` reads the SNAPSHOT captured at pin time (refreshed by
    ``promote``), not the live nodes: a concurrent store-path insert may
    split a pinned node (``_split`` transfers the pin to both halves), and
    the snapshot keeps the match's view of every segment and token run
    intact across the reshape — the invariant that lets KV export move off
    the engine loop. ``extra_pins`` are the lower split halves this match's
    ``release()`` must also unpin."""

    length: int
    entries: list[tuple[_Node, int]] = field(default_factory=list)
    host_tokens: int = 0
    # a cache that keeps snapshots of state: the snapshot at ``length`` (the
    # walk was cut back to it) and the K/V tokens the walk matched before the cut
    state: Any = None
    matched: int = 0
    segments_snapshot: list = field(default_factory=list)
    tokens_snapshot: list = field(default_factory=list)
    extra_pins: list = field(default_factory=list)

    @property
    def device_tokens(self) -> int:
        return self.length - self.host_tokens

    def segments(self) -> tuple[Any, ...]:
        if self.segments_snapshot:
            return tuple(self.segments_snapshot)
        return tuple(node.segment for node, _ in self.entries)

    def tokens(self) -> list[int]:
        """The matched token path (snapshot — immune to later splits)."""
        if self.tokens_snapshot:
            return [
                int(t)
                for run, (_, take) in zip(self.tokens_snapshot, self.entries)
                for t in run[:take]
            ]
        return [
            int(t) for node, take in self.entries for t in node.tokens[:take]
        ]

    def takes(self) -> tuple[int, ...]:
        return tuple(take for _, take in self.entries)


class BlockPrefixCache:
    """Radix tree of block-aligned KV segments under per-tier byte budgets.

    ``block`` must match the engine's MIN_BUCKET (chunk_plan's alignment
    contract: a prefix hit becomes the ``start`` of a chunk plan, which must
    be block-aligned). ``budget_bytes <= 0`` means unbounded (the engine
    disables the cache entirely rather than passing 0 here).
    ``host_budget_bytes <= 0`` disables the spill tier (device eviction
    deletes, the original behavior); when positive, ``to_host`` /
    ``to_device`` convert segments across tiers (default: identity, which
    keeps the unit tests jax-free — the engine injects ``jax.device_get``
    and a ``jnp.asarray`` tree-map).
    ``state_stride > 0`` makes it a cache for a model whose slots hold
    recurrent state: the stride in tokens at which its engine takes snapshots
    (module docstring); a match then needs one.
    """

    def __init__(
        self,
        budget_bytes: int,
        block: int = 16,
        *,
        host_budget_bytes: int = 0,
        to_host: Callable[[Any], Any] | None = None,
        to_device: Callable[[Any], Any] | None = None,
        state_stride: int = 0,
    ) -> None:
        if block <= 0:
            raise ValueError(f"block must be positive, got {block}")
        if state_stride % block:
            raise ValueError(f"state_stride {state_stride} is not a multiple of block {block}")
        if state_stride and host_budget_bytes > 0:
            raise ValueError(
                "a prefix cache that keeps snapshots of state has no host tier: "
                "host_budget_bytes must be 0 (ROADMAP.md R7)"
            )
        self.block = block
        self.state_stride = int(state_stride)
        self.state_bytes = 0  # the snapshots' part of ``bytes``
        self.snapshots = 0  # snapshots held
        self.snapshots_stored = 0  # snapshots taken in (monotonic)
        self.snapshots_evicted = 0  # snapshots that went with an evicted node (monotonic)
        self.budget_bytes = int(budget_bytes)
        self.host_budget_bytes = int(host_budget_bytes)
        self._to_host = to_host if to_host is not None else (lambda seg: seg)
        self._to_device = to_device if to_device is not None else (lambda seg: seg)
        self._root = _Node((), None, None)
        self._clock = itertools.count(1)
        self.bytes = 0  # device-tier segment bytes
        self.host_bytes = 0  # host-tier segment bytes
        self.nodes = 0  # segment-owning nodes, both tiers (root excluded)
        self.host_nodes = 0  # host-tier subset of ``nodes``
        self.evictions = 0  # nodes DELETED from the tree (monotonic)
        self.evicted_bytes = 0
        self.spills = 0  # device->host demotions (monotonic)
        self.spilled_bytes = 0
        self.spill_seconds = 0.0  # wall time inside to_host (a device sync)
        self.reuploads = 0  # host->device promotions (monotonic)
        self.reupload_bytes = 0
        self.dedup_tokens = 0  # insert tokens already present (stored once)
        self.stored_tokens = 0  # insert tokens that allocated new segments
        # live (pinned, unreleased) matches: _split consults this to transfer
        # pins onto the lower half when it splits a pinned node — the list is
        # a handful of entries at most (one per concurrent match/export)
        self._active_matches: list[PrefixMatch] = []

    # ---- lookup ----

    def _walk(self, ids, limit: int) -> list[tuple[_Node, int]]:
        """Longest block-aligned cached prefix of ``ids[:limit]`` as
        (node, take) entries. Pure read — no pins, no LRU touches."""
        block = self.block
        cap = (min(limit, len(ids)) // block) * block
        entries: list[tuple[_Node, int]] = []
        node, pos = self._root, 0
        while pos + block <= cap:
            child = node.children.get(tuple(ids[pos : pos + block]))
            if child is None:
                break
            edge = child.tokens
            n = min(len(edge), cap - pos)
            m = (_common_len(edge[:n], tuple(ids[pos : pos + n])) // block) * block
            if m == 0:
                break
            entries.append((child, m))
            pos += m
            if m < len(edge):
                break  # diverged (or hit the cap) mid-edge: partial take
            node = child
        return entries

    @staticmethod
    def _cut_to_state(entries: list[tuple[_Node, int]]):
        """``entries`` cut back to the deepest snapshot on them, and that
        snapshot: ([], None) where the walk passed none."""
        for i in range(len(entries) - 1, -1, -1):
            node, take = entries[i]
            at = max((offset for offset in node.states if offset <= take), default=0)
            if at:
                return entries[:i] + [(node, at)], node.states[at]
        return [], None

    def match_lens(self, ids, limit: int | None = None) -> tuple[int, int]:
        """(longest usable cached prefix length, tokens of K/V the walk matched),
        block-aligned, in one walk and without pinning. Where the cache keeps
        state the first is the deepest snapshot's position at or under the
        second; in a K/V-only cache they are equal."""
        limit = len(ids) - 1 if limit is None else limit
        entries = self._walk(ids, limit)
        matched = sum(take for _, take in entries)
        if self.state_stride:
            entries, _ = self._cut_to_state(entries)
            return sum(take for _, take in entries), matched
        return matched, matched

    def match_len(self, ids, limit: int | None = None) -> int:
        """Longest usable cached prefix length: the engine's admission router
        calls this to decide which requests take the seeded path."""
        return self.match_lens(ids, limit)[0]

    def match(self, ids, limit: int | None = None) -> PrefixMatch | None:
        """Longest cached prefix of ``ids`` capped at ``limit`` tokens
        (default len-1: the engine must always prefill at least one real
        token for the finalize logits). Pins every node on the path and
        refreshes its LRU stamp; returns None on no usable blocks."""
        limit = len(ids) - 1 if limit is None else limit
        entries = self._walk(ids, limit)
        matched, state = sum(take for _, take in entries), None
        if self.state_stride:
            entries, state = self._cut_to_state(entries)
        if not entries:
            return None
        stamp = next(self._clock)
        host_tokens = 0
        for node, take in entries:
            node.refs += 1
            node.last_used = stamp
            if node.tier == TIER_HOST:
                host_tokens += take
        match = PrefixMatch(
            length=sum(t for _, t in entries), entries=entries,
            host_tokens=host_tokens, state=state, matched=matched,
            # pin-time snapshot: segments() and tokens() read these, so a
            # concurrent insert's _split of a pinned node cannot change what
            # this match assembles/serializes
            segments_snapshot=[node.segment for node, _ in entries],
            tokens_snapshot=[node.tokens for node, _ in entries],
        )
        self._active_matches.append(match)
        return match

    def release(self, match: PrefixMatch) -> None:
        for node, _ in match.entries:
            node.refs -= 1
        for node in match.extra_pins:
            node.refs -= 1
        match.extra_pins = []
        try:
            self._active_matches.remove(match)
        except ValueError:
            pass  # hand-built match (tests) or double release

    def promote(self, match: PrefixMatch) -> tuple[int, int]:
        """Re-upload every host-resident segment on a PINNED match path back
        to the device tier (in place — the path, refcounts, and byte totals
        are preserved; only the tier accounting moves). Must run before
        ``match.segments()`` is consumed when ``match.host_tokens > 0``: the
        assemble dispatch needs device-tier leaves. Returns (segments
        promoted, bytes promoted). Headroom is made BEFORE each re-upload —
        colder unpinned device segments demote first — so a device tier
        tuned near free HBM never transiently overshoots its budget on the
        hot-prefix path (beyond what the pinned path itself requires); a
        final rebalance settles the host tier the demotions grew."""
        promoted = promoted_bytes = 0
        heap: list[tuple[int, int, int, _Node]] | None = None
        for i, (node, _) in enumerate(match.entries):
            if node.tier != TIER_HOST:
                continue
            if self.budget_bytes > 0:
                heap = self._demote_lru_until(self.budget_bytes - node.nbytes, heap)
            node.segment = self._to_device(node.segment)
            if i < len(match.segments_snapshot):
                # the match's pin-time snapshot must serve the PROMOTED
                # (device) leaves to the assemble dispatch
                match.segments_snapshot[i] = node.segment
            node.tier = TIER_DEVICE
            self.host_bytes -= node.nbytes
            self.host_nodes -= 1
            self.bytes += node.nbytes
            self.reuploads += 1
            self.reupload_bytes += node.nbytes
            promoted += 1
            promoted_bytes += node.nbytes
        if promoted:
            self.evict_to_budget()
        return promoted, promoted_bytes

    # ---- insert ----

    def insert(self, ids, slicer: Callable[[int, int], Any],
               states: dict[int, Any] | None = None) -> int:
        """Store the KV for ``ids`` (length MUST be a multiple of the block —
        the engine aligns down so no padded/garbage slot is ever cached)
        along the trie path. ``slicer(start, stop)`` returns the segment
        pytree for slots [start, stop) of the finalized staging row; it is
        only called for the genuinely new tail, so shared blocks cost
        nothing (a host-resident shared block stays on the host — the walk
        just refreshes its stamp). ``states`` maps a position (a multiple of
        the block, at most ``len(ids)``) to the snapshot of the state after that
        token; a position that already has one keeps it. Returns the bytes
        added."""
        block = self.block
        total = len(ids)
        if total == 0:
            return 0
        if total % block:
            raise ValueError(f"insert length {total} not aligned to block {block}")
        ids = tuple(ids)
        stamp = next(self._clock)
        node, pos = self._root, 0
        added = 0
        while pos < total:
            child = node.children.get(ids[pos : pos + block])
            if child is None:
                seg = slicer(pos, total)
                new = _Node(ids[pos:total], seg, node)
                new.last_used = stamp
                node.children[ids[pos : pos + block]] = new
                self.bytes += new.nbytes
                self.nodes += 1
                added += new.nbytes
                self.stored_tokens += total - pos
                break
            edge = child.tokens
            n = min(len(edge), total - pos)
            m = (_common_len(edge[:n], ids[pos : pos + n]) // block) * block
            # the first block matched via the child key and total-pos >= block,
            # so the aligned common run is at least one block
            assert m >= block, "child key matched but edge diverges inside block 0"
            if m < len(edge):
                self._split(child, m)
            self.dedup_tokens += m
            child.last_used = stamp
            pos += m
            node = child
        if states:
            added += self._place_states(ids, states)
        self.evict_to_budget()
        return added

    def _place_states(self, ids: tuple[int, ...], states: dict[int, Any]) -> int:
        """Hang each snapshot on the node whose edge holds its position, at its
        offset inside that edge. The path is there: ``insert`` has just walked
        it, and an edge that ran past ``ids`` was split at their end."""
        block, added = self.block, 0
        node, start = self._root, 0  # ``node``'s own edge ends at ``start``
        for position in sorted(states):
            if position % block or not 0 < position <= len(ids):
                raise ValueError(f"snapshot position {position} is not on the path's blocks")
            child = None
            while start < position:
                child = node.children.get(ids[start : start + block])
                if child is None:
                    return added  # evicted under the insert (a budget under one path)
                if position <= start + len(child.tokens):
                    break
                node, start = child, start + len(child.tokens)
            offset = position - start
            if child is None or offset in child.states:
                continue
            nbytes = segment_nbytes(states[position])
            child.states[offset] = states[position]
            child.state_nbytes += nbytes
            child.nbytes += nbytes
            self.bytes += nbytes
            self.state_bytes += nbytes
            self.snapshots += 1
            self.snapshots_stored += 1
            added += nbytes
        return added

    def _split(self, node: _Node, m: int) -> None:
        """Split ``node``'s edge at slot ``m`` (block-aligned): the node
        keeps the first m tokens/slots (its parent key stays valid — the
        first block is unchanged); a new lower node takes the rest plus the
        original children. Byte accounting is conserved on the node's OWN
        tier: slot counts are linear, so upper+lower bytes == the original,
        and both halves stay where the segment lives.

        PIN-AWARE: splitting a node on a live match path is legal. Matches
        read pin-time SNAPSHOTS (the original uncut segment/token arrays
        stay alive through the snapshot references), and the pins transfer —
        the upper half keeps the node's refcount (same object) and the lower
        half inherits one pin per live match entry referencing the node, so
        the byte-budget LRU keeps treating the WHOLE pinned run as
        unevictable until release(). This is what lets a store-path insert
        land concurrently with an off-loop KV export's pinned serialization
        (the PR 11 follow-up)."""
        # host-resident segments are host arrays (e.g. device_get numpy),
        # where a basic slice is a VIEW over the full base buffer: both
        # halves must materialize copies or evicting one half later frees
        # nothing (the survivor's view pins the whole buffer and the host
        # byte budget silently stops bounding RSS). Device arrays slice into
        # fresh buffers already; copying there would be pure waste.
        copy = node.tier == TIER_HOST
        splitter = getattr(node.segment, "split", None)
        if splitter is not None:
            # paged segment (kv_pool.PagedSegment): the cut is a zero-copy
            # page-list repartition — page size == block, so a block-aligned
            # m is always a page boundary. Live snapshots keep reading the
            # original object's pages; both halves stay pin-protected below.
            upper_seg, lower_seg = splitter(m)
        else:
            upper_seg = None  # cut after the lower node exists, as before
            lower_seg = self._cut(node.segment, m, len(node.tokens), copy=copy)
        lower = _Node(node.tokens[m:], lower_seg, node)
        lower.tier = node.tier
        # a snapshot stays on the token path that ends at it: the half its
        # offset lies in keeps it (an offset of exactly m is the upper half's end)
        lower.states = {offset - m: s for offset, s in node.states.items() if offset > m}
        lower.state_nbytes = sum(segment_nbytes(s) for s in lower.states.values())
        lower.nbytes += lower.state_nbytes
        if node.refs:
            # transfer pins: each live match pin on this node — whether it
            # pinned it directly (entries) or inherited it from an EARLIER
            # split (extra_pins: a second insert may re-split a lower half)
            # — also pins the new lower half (its snapshot spans both), and
            # records it so release() unpins exactly what was pinned
            for match in self._active_matches:
                count = sum(1 for n, _ in match.entries if n is node) + sum(
                    1 for n in match.extra_pins if n is node
                )
                if count:
                    lower.refs += count
                    match.extra_pins.extend([lower] * count)
        lower.children = node.children
        for c in lower.children.values():
            c.parent = lower
        lower.last_used = node.last_used
        if upper_seg is None:
            upper_seg = self._cut(node.segment, 0, m, copy=copy)
        upper_state_nbytes = node.state_nbytes - lower.state_nbytes
        upper_nbytes = segment_nbytes(upper_seg) + upper_state_nbytes
        delta = lower.nbytes + upper_nbytes - node.nbytes
        if node.tier == TIER_HOST:
            self.host_bytes += delta
            self.host_nodes += 1
        else:
            self.bytes += delta
        self.nodes += 1
        node.segment = upper_seg
        node.nbytes = upper_nbytes
        node.states = {offset: s for offset, s in node.states.items() if offset <= m}
        node.state_nbytes = upper_state_nbytes
        node.tokens = node.tokens[:m]
        node.children = {lower.tokens[: self.block]: lower}

    @staticmethod
    def _cut(segment: Any, start: int, stop: int, copy: bool = False) -> Any:
        """Re-slice an existing segment along the capacity axis (always the
        last axis of every segment leaf, by construction of the engine's
        slicer). ``copy`` materializes the slice (host arrays slice to
        views; see _split) — device arrays already slice to new buffers."""
        import jax

        if copy:
            return jax.tree_util.tree_map(lambda x: x[..., start:stop].copy(), segment)
        return jax.tree_util.tree_map(lambda x: x[..., start:stop], segment)

    # ---- digest export ----

    def iter_prefixes(self, limit: int) -> Iterator[tuple[int, ...]]:
        """Root-first (BFS) token paths of up to ``limit`` segment-owning
        nodes, both tiers — shallow shared prefixes come first, so a
        truncated walk keeps the hottest entries. The fleet's hot-prefix
        digest (serve/digest.py) hashes these for /healthz advertisement."""
        emitted = 0
        queue: deque[tuple[_Node, tuple[int, ...]]] = deque([(self._root, ())])
        while queue and emitted < limit:
            node, base = queue.popleft()
            for child in node.children.values():
                path = base + child.tokens
                yield path
                emitted += 1
                if emitted >= limit:
                    return
                queue.append((child, path))

    # ---- KV wire format (export/import) ----

    def export_segments(self, ids, limit: int | None = None) -> bytes | None:
        """Serialize the longest cached prefix of ``ids`` into the versioned
        wire payload (KV_WIRE_VERSION): one JSON header line — block size,
        the matched token path, a per-segment manifest of (name, dtype,
        shape, nbytes) — then the raw leaf bytes in manifest order.

        The match path is REFCOUNT-PINNED for the whole serialization, so a
        concurrent store's eviction/demotion can never free or split a
        segment mid-read; the pin is released before returning. Export is
        tier-aware: host-resident segments serialize straight from their RAM
        buffers (no device round-trip), device segments pay one device_get
        (``np.asarray``) — both produce identical bytes, since spill
        converters round-trip shapes/dtypes exactly. int8 KV scales are
        ordinary named leaves and ride along. Returns None when no full
        block of ``ids`` is cached. Segments must be dict-of-array pytrees
        (the engine's layout) or bare arrays.

        Callers on the tree-owning thread use this one-shot form; the
        engine's OFF-LOOP export marshals only ``match``/``release`` onto
        its loop and runs :meth:`serialize_match` on the calling thread."""
        limit = len(ids) if limit is None else limit
        match = self.match(ids, limit=limit)
        if match is None:
            return None
        try:
            return self.serialize_match(match)
        finally:
            self.release(match)

    def refuse_wire(self, what: str) -> None:
        """KV wire version 1 carries K/V segments. A cache whose paths are only
        usable with the snapshots on them refuses the format by name, in both
        directions: a K/V-only payload would plant paths no request can start
        from, or ship a prefix without the state at its end."""
        if self.state_stride:
            raise ValueError(
                f"KV wire version {KV_WIRE_VERSION} carries K/V segments and no state: a prefix "
                f"cache that keeps snapshots of state does not {what} it (ROADMAP.md R7)"
            )

    def serialize_match(self, match: PrefixMatch) -> bytes:
        """Serialize a PINNED match into the wire payload. Thread-free by
        construction: every read goes through the match's pin-time
        SNAPSHOTS (segments/token runs captured when the pin landed,
        refreshed only by promote), so this may run OFF the tree-owning
        thread while concurrent inserts ``_split`` the pinned path — the
        snapshot keeps the serialization consistent and the pin keeps the
        byte-budget LRU from freeing or demoting anything mid-read. The
        caller owns the pin lifecycle: ``match()`` before, ``release()``
        after (both on the tree-owning thread)."""
        import numpy as np

        self.refuse_wire("export")
        tokens: list[int] = []
        manifests: list[dict] = []
        blobs: list[bytes] = []
        # read the pin-time snapshots, not the live nodes: a concurrent
        # insert may split a pinned node mid-serialization (off-loop
        # export) — the snapshot keeps this read consistent
        runs = match.tokens_snapshot or [n.tokens for n, _ in match.entries]
        for (node, take), run, segment in zip(
            match.entries, runs, match.segments()
        ):
            tokens.extend(int(t) for t in run[:take])
            if hasattr(segment, "materialize"):
                # paged segment: gather its pages into a loose dict. NOTE
                # this reads the shared pool, so it is only safe on the
                # tree-owning (engine loop) thread — the engine materializes
                # paged snapshots on the loop BEFORE handing a match to the
                # off-loop exporter (engine._kv_execute's "pin" step).
                segment = segment.materialize()
            items = (
                sorted(segment.items())
                if isinstance(segment, dict)
                else [("", segment)]
            )
            leaves = []
            for name, leaf in items:
                arr = np.ascontiguousarray(np.asarray(leaf)[..., :take])
                leaves.append(
                    {
                        "name": name,
                        "dtype": str(arr.dtype),
                        "shape": list(arr.shape),
                        "nbytes": int(arr.nbytes),
                    }
                )
                blobs.append(arr.tobytes())
            manifests.append({"take": int(take), "leaves": leaves})
        header = {
            "version": KV_WIRE_VERSION,
            "block": self.block,
            "tokens": tokens,
            "segments": manifests,
        }
        return (
            json.dumps(header, separators=(",", ":")).encode()
            + b"\n"
            + b"".join(blobs)
        )

    def import_segments(self, payload: bytes) -> int:
        """Insert a wire payload (``export_segments`` output, possibly from
        another process/host) along the radix path. Validates version, block
        size, token path, and byte counts BEFORE touching the tree — a
        mismatched or truncated payload raises ValueError and leaves the
        cache untouched. Leaves are rebuilt host-side and fed through
        ``to_device`` only for the genuinely new tail (shared blocks dedup
        exactly like a local insert). Returns the bytes added.

        Engine note: the decode/validate half (``decode_wire_payload``) and
        the upload are thread-free — the engine calls them on the HTTP
        handler thread and marshals only ``insert_segments`` onto its loop,
        so a multi-MB migration never stalls the decode pipeline behind a
        payload parse."""
        tokens, leaves = decode_wire_payload(payload, self.block)
        return self.insert_segments(tokens, leaves)

    def insert_segments(self, tokens, leaves) -> int:
        """Insert pre-decoded wire leaves (name → full-length array, last
        axis = the token path) along the radix path. Each new-tail slice
        passes through ``to_device`` — a no-op for already-device arrays, an
        upload for host arrays — so only genuinely new bytes ever move."""
        self.refuse_wire("import")

        def slicer(start: int, stop: int):
            seg = {name: leaf[..., start:stop] for name, leaf in leaves.items()}
            if "" in seg and len(seg) == 1:
                return self._to_device(seg[""])
            return self._to_device(seg)

        return self.insert(list(tokens), slicer)

    # ---- eviction / demotion ----

    def _collect_lru(self, want: Callable[[_Node], bool]) -> list[tuple[int, int, int, _Node]]:
        """ONE tree walk collecting every node ``want`` accepts into a
        min-heap ordered (last_used, -depth, id): coldest first, and on
        stamp ties (one walk stamps its whole path with one clock tick) the
        DEEPEST node first, so children demote/evict before the parents
        that carry their paths."""
        heap: list[tuple[int, int, int, _Node]] = []
        stack: list[tuple[_Node, int]] = [(self._root, 0)]
        while stack:
            node, depth = stack.pop()
            for child in node.children.values():
                stack.append((child, depth + 1))
                if want(child):
                    heapq.heappush(heap, (child.last_used, -(depth + 1), id(child), child))
        return heap

    def _demote_lru_until(
        self,
        target_bytes: int,
        heap: list[tuple[int, int, int, _Node]] | None = None,
    ) -> list[tuple[int, int, int, _Node]]:
        """Demote LRU unpinned device segments to the host tier until device
        bytes fit ``target_bytes`` or candidates run out (pins can hold the
        tier over target, which is transient and safe). Returns the heap so
        repeated callers (promote's per-segment headroom) pay ONE walk."""
        if heap is None:
            heap = self._collect_lru(lambda n: n.tier == TIER_DEVICE)
        while self.bytes > target_bytes and heap:
            _, _, _, victim = heapq.heappop(heap)
            if victim.refs > 0 or victim.tier != TIER_DEVICE:
                continue  # pinned (incl. a match path mid-promote) or moved
            self._spill(victim)
        return heap

    def _spill(self, node: _Node) -> None:
        """Demote one device-tier segment to the host spill tier in place:
        the tree shape, refcount, and LRU stamp are untouched — only the
        segment's residency (and the per-tier byte totals) move."""
        t0 = time.monotonic()
        node.segment = self._to_host(node.segment)
        self.spill_seconds += time.monotonic() - t0
        node.tier = TIER_HOST
        self.bytes -= node.nbytes
        self.host_bytes += node.nbytes
        self.host_nodes += 1
        self.spills += 1
        self.spilled_bytes += node.nbytes

    def evict_to_budget(self) -> int:
        """Rebalance both tiers. Device over budget: with a host tier, demote
        least-recently-used unpinned device segments (ANY node — demotion
        keeps the tree shape, so interior nodes are fair game and no cascade
        is needed; the hot shared preambles have fresh stamps and naturally
        stay resident); without one, drop LRU unpinned device leaves as
        before. Host over budget: drop LRU unpinned host LEAVES, cascading
        to a parent bared by its last child's eviction only when that parent
        is itself host-resident; if host bytes remain only on interior
        nodes (device tails planted under spilled parents), whole LRU
        host-rooted subtrees go. Pinned nodes are skipped; only pins can
        hold a tier over budget, which is transient and safe. Returns the
        number of nodes DELETED (demotions are counted in ``spills``, not
        here)."""
        evicted = 0
        if self.budget_bytes > 0 and self.bytes > self.budget_bytes:
            if self.host_budget_bytes > 0:
                self._demote_lru_until(self.budget_bytes)
            else:
                evicted += self._evict_leaves(TIER_DEVICE)
        if self.host_budget_bytes > 0 and self.host_bytes > self.host_budget_bytes:
            evicted += self._evict_leaves(TIER_HOST)
            if self.host_bytes > self.host_budget_bytes:
                # leaf eviction ran dry with host bytes left: insert() can
                # plant a fresh DEVICE tail under a spilled (host) parent,
                # leaving host bytes only on interior nodes no leaf pass can
                # delete — a RAM budget that HBM-resident children can pin
                # open is not a budget, so fall back to whole subtrees
                evicted += self._evict_host_subtrees()
        return evicted

    def _evict_leaves(self, tier: str) -> int:
        """Drop least-recently-used unpinned leaves of ``tier`` until that
        tier is within its budget: ONE tree walk collects the current leaves
        into a min-heap by LRU stamp, and a parent bared by its last child's
        eviction joins the heap if it shares the tier (the cascade stays
        local via parent pointers — no per-victim re-walk on the engine
        thread)."""
        over = (
            (lambda: self.bytes > self.budget_bytes)
            if tier == TIER_DEVICE
            else (lambda: self.host_bytes > self.host_budget_bytes)
        )
        heap = self._collect_lru(lambda n: not n.children and n.tier == tier)
        evicted = 0
        while over() and heap:
            _, _, _, victim = heapq.heappop(heap)
            if victim.refs > 0 or victim.children or victim.tier != tier:
                continue  # pinned, became interior, or changed tier
            parent = victim.parent
            assert parent is not None
            del parent.children[victim.tokens[: self.block]]
            self._forget(victim)
            evicted += 1
            if parent is not self._root and not parent.children and parent.tier == tier:
                depth, n = 0, parent
                while n.parent is not None:
                    depth, n = depth + 1, n.parent
                heapq.heappush(heap, (parent.last_used, -depth, id(parent), parent))
        return evicted

    def _forget(self, node: _Node) -> None:
        """Account one DETACHED node out of the cache (caller already
        unlinked it from its parent)."""
        closer = getattr(node.segment, "close", None)
        if closer is not None:
            closer()  # paged segment: return its pages to the pool
        if node.tier == TIER_HOST:
            self.host_bytes -= node.nbytes
            self.host_nodes -= 1
        else:
            self.bytes -= node.nbytes
        self.nodes -= 1
        self.evicted_bytes += node.nbytes
        self.evictions += 1
        self.state_bytes -= node.state_nbytes
        self.snapshots -= len(node.states)
        self.snapshots_evicted += len(node.states)

    def _evict_host_subtrees(self) -> int:
        """Last resort for host-budget pressure: delete whole LRU
        host-rooted subtrees, device-tier descendants included (hot tails
        under a cold spilled preamble die with it — the alternative is a
        host footprint no knob bounds). Subtrees containing a pinned node
        are skipped; popped nodes already removed via an ancestor are
        recognized by id."""
        heap = self._collect_lru(lambda n: n.tier == TIER_HOST)
        evicted = 0
        gone: set[int] = set()
        while self.host_bytes > self.host_budget_bytes and heap:
            _, _, nid, victim = heapq.heappop(heap)
            if nid in gone or victim.tier != TIER_HOST:
                continue
            stack, subtree, pinned = [victim], [], False
            while stack:
                n = stack.pop()
                if n.refs > 0:
                    pinned = True
                    break
                subtree.append(n)
                stack.extend(n.children.values())
            if pinned:
                continue
            parent = victim.parent
            assert parent is not None
            del parent.children[victim.tokens[: self.block]]
            for n in subtree:
                gone.add(id(n))
                self._forget(n)
                evicted += 1
        return evicted

    def clear(self) -> None:
        stack = list(self._root.children.values())
        while stack:
            node = stack.pop()
            closer = getattr(node.segment, "close", None)
            if closer is not None:
                closer()  # paged segments: pages back to the pool
            stack.extend(node.children.values())
        self._root = _Node((), None, None)
        self.bytes = 0
        self.host_bytes = 0
        self.nodes = 0
        self.host_nodes = 0
        self.state_bytes = 0
        self.snapshots = 0
