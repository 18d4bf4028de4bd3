"""Shared by the readers of the phase clock's starved seconds: the program's
counter ``serve_loop_starved_seconds_total{phase}``, the seconds of
``serve_loop_seconds_total{phase}`` in which the loop thread had seen that the
chip had no model program left to run (the newest enqueued program's output
answered ``is_ready()``), and ``serve_loop_starved_unseen_seconds_total``, the
seconds between a look that found the chip with work and the next, which found
none. The chip's idle time with work lies between *starved* and *starved +
unseen*: a bound from below with a stated width, read over the whole window
with tracing off; ``device_idle_share`` times the same thing from outside, over
the captured span. Every reader gives a share of the loop's seconds with a
request queued or live (all phases less ``no_work``, as ``loop_host_share``
has it). A program without the family (the parent of the PR that brought it)
gives None."""

from __future__ import annotations

from benchmark.layer_metrics._loop import ADMISSION_HOST, with_work_s

FAMILY = "serve_loop_starved_seconds_total"
UNSEEN = "serve_loop_starved_unseen_seconds_total"
# the four groups hold every phase of the running loop but `no_work`, each once
EMIT = ("emit",)
ADMISSION = (*ADMISSION_HOST, "prefill_sync")  # of prefill_sync, the tail after the read
UPKEEP = ("dispatch", "sync_wait", "stats", "kv_jobs", "other")
BUILD = ("program_build", "gc")


def _with_work_s(run: dict, family: str) -> float | None:
    """The loop's seconds with work over the window, or None where there are
    none or the program has no such ``family``."""
    pair = run["counters"]
    times = with_work_s(run, pair)
    if times is None or times[1] <= 0 or family not in pair[1]:
        return None
    return times[1]


def starved_share(run: dict, *phases: str) -> float | None:
    """Starved seconds of ``phases`` over the window, as a percentage of the
    loop's seconds with work; of every phase but ``no_work`` when none is named."""
    with_work = _with_work_s(run, FAMILY)
    if with_work is None:
        return None
    delta, pair = run["counter_delta"], run["counters"]
    if phases:
        seconds = sum(delta(pair, FAMILY, {"phase": phase}) for phase in phases)
    else:
        seconds = delta(pair, FAMILY) - delta(pair, FAMILY, {"phase": "no_work"})
    return 100.0 * seconds / with_work


def unseen_share(run: dict) -> float | None:
    with_work = _with_work_s(run, UNSEEN)
    if with_work is None:
        return None
    return 100.0 * run["counter_delta"](run["counters"], UNSEEN) / with_work
