"""Shared by the readers of the engine loop's phase clock: the program's
counter ``serve_loop_seconds_total{phase}``, every second of the loop thread
charged to exactly one phase (PERF.md section 3 lists the phases and their
code sites). A program without the clock (the parent of the PR that brought
it) has no such family, and every reader here then returns None."""

from __future__ import annotations

FAMILY = "serve_loop_seconds_total"
BLOCKED_ON_DEVICE = ("sync_wait", "prefill_sync")
# host code of an admission; its wait on the device is `prefill_sync`
ADMISSION_HOST = ("admit", "prefix_seed", "prefill", "prefix_store")


def phase_s(run: dict, pair, *phases: str) -> float | None:
    """Seconds the loop spent in ``phases`` between the two snapshots of
    ``pair``; in every phase when none is named, which is the loop thread's
    wall time between them."""
    if not pair or FAMILY not in pair[1]:
        return None
    if not phases:
        return run["counter_delta"](pair, FAMILY)
    return sum(run["counter_delta"](pair, FAMILY, {"phase": phase}) for phase in phases)


def with_work_s(run: dict, pair) -> tuple[float, float] | None:
    """(the loop's seconds between the snapshots, those of them with a request
    queued or live), or None where there is no clock or no time."""
    total = phase_s(run, pair)
    if not total or total <= 0:
        return None
    return total, total - phase_s(run, pair, "no_work")
