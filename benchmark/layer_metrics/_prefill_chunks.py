"""Shared by the readers of the engine's chunk-prefill counters
(``serve/engine.py _prefill`` -> ``serve_prefill_chunks_total`` and
``serve_prefill_chunk_tokens_total{kind}``, counted on the host where a chunk
program is dispatched): the window's deltas. A program without the counters (the
parent of the PR that brought them) gives None, and the readers return nothing."""

from __future__ import annotations


def chunk_counts(run: dict) -> dict | None:
    """Chunk programs, prompt tokens and pad positions they carried, between the
    window's two snapshots."""
    delta, pair = run["counter_delta"], run["counters"]
    if "serve_prefill_chunks_total" not in pair[1]:
        return None
    out = {kind: delta(pair, "serve_prefill_chunk_tokens_total", {"kind": kind})
           for kind in ("prompt", "pad")}
    out["chunks"] = delta(pair, "serve_prefill_chunks_total")
    return out if out["chunks"] > 0 else None
