"""Which phase of the engine loop each idle gap of the device fell in.

    python3 scripts/loop_gaps.py --workload qwen3-4b.chat --seed 7 --seconds 51 [--out DIR]

Brings one cell of the benchmark up with the harness's own functions
(``benchmark/systems/serve_http.py``: ``bring_up``, ``one_window`` with a
capture, ``tear_down``; no check against the reference and no result line)
and, while the capture is still on disk, lays the ``serve.*`` annotations of
the loop thread's line (plane ``/host:CPU``, the line that holds
``serve.dispatch``; prime_tpu/obs/phase.py opens them) over the device's
operation intervals. Both are on the profiler's clock. For each of the ten
longest idle gaps, and for all idle time together, it gives the seconds by the
innermost annotation open at the time (``(none)``: no annotation open, which
is the ``other`` and ``program_build`` phases and a program without the phase
clock), and ``device_idle_with_work_share``: of the span less the seconds
under ``serve.no_work``, the share in which the chip did nothing. It also
checks the phase clock against the wall: the sum of the phases between the
window's two snapshots beside the snapshots' own interval.

**The starved clock against the capture.** The phase clock claims, from inside
the program, to know when the chip has no model program left to run
(``serve_loop_starved_seconds_total``; docs/observability.md "The starved
clock"), and an annotation opened while it thinks so carries the stat
``starved=1`` (shown here as `` (starved)`` after the annotation's name). The
capture is the judge: the report's ``starved_clock`` gives the device's idle
seconds with work beside the clock's *starved* and *starved + unseen* between
two snapshots taken around the captured span itself (``one_window`` takes its
own before ``start_trace`` and after ``stop_trace``, which takes a quarter of a
minute), the seconds in which no model program ran (the ``XLA Modules`` line:
what the clock means, without the gaps between one program's operations), the
share of the idle time with work that lies under annotations carrying the bit
(recall) and the device's busy share under them (precision: near 0). One thing
the clock cannot know is how late a blocking read came back: it takes the chip
to have finished when ``np.asarray`` returns, and a loop thread that waits for
the interpreter lock behind the streams' threads returns later. The capture
shows it as idle time at the end of a ``serve.sync`` / ``serve.prefill_sync``
annotation (``late_return_s``), and the bounds are held against the idle
seconds less those as well.

The benchmark cannot report any of this yet: its readers run after the
capture's directory is gone and see only ``xplane.reduce``'s summary (PERF.md
section 7, first in line for the next ``benchmark`` issue). The arithmetic
below is what that would take over.
"""

from __future__ import annotations

import argparse
import bisect
import contextlib
import json
import re
import sys
import tempfile
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO_ROOT))

from benchmark.harness import xplane  # noqa: E402  the yardstick's own interval arithmetic

HOST_PLANE = "/host:CPU"
ANCHOR = "serve.dispatch"  # the loop thread's line is the one that holds it
FAMILY = "serve_loop_seconds_total"
STARVED_FAMILY = "serve_loop_starved_seconds_total"
UNSEEN_FAMILY = "serve_loop_starved_unseen_seconds_total"
NONE = "(none)"
NO_WORK = "serve.no_work"
STARVED = " (starved)"  # after the name of an annotation opened with the clock's bit set
MODEL_PROGRAMS = ("decode", "chunk_prefill", "finalize")  # roles of programs.json the engine announces
BLOCKING_READS = ("serve.sync", "serve.prefill_sync")  # the loop is inside np.asarray(a program's output)


def read_annotations(trace_dir) -> list[dict]:
    """The ``serve.*`` events of plane ``/host:CPU`` as ``xplane.read_events``
    gives them, with the stat ``starved`` as a key: that reader drops stats."""
    from jax.profiler import ProfileData

    rows = []
    for path in sorted(Path(trace_dir).rglob("*.xplane.pb")):
        for plane in ProfileData.from_file(str(path)).planes:
            if plane.name != HOST_PLANE:
                continue
            for line in plane.lines:
                for event in line.events:
                    if event.name.startswith("serve."):
                        rows.append({"plane": plane.name, "line": line.name, "name": event.name,
                                     "start_ns": float(event.start_ns),
                                     "dur_ns": float(event.duration_ns),
                                     "starved": dict(event.stats).get("starved")})
    return rows


def loop_annotations(events: list[dict]) -> list[tuple[float, float, str]]:
    """``(start_ns, end_ns, name)`` of the ``serve.*`` events on the loop
    thread's line, or [] where the capture holds no ``serve.dispatch``; an
    event with the stat ``starved`` has `` (starved)`` after its name."""
    lines = {e["line"] for e in events if e["plane"] == HOST_PLANE and e["name"] == ANCHOR}
    return sorted(
        (e["start_ns"], e["start_ns"] + e["dur_ns"], e["name"] + (STARVED if e.get("starved") else ""))
        for e in events
        if e["plane"] == HOST_PLANE and e["line"] in lines and e["name"].startswith("serve."))


def exclusive_segments(annotations: list[tuple[float, float, str]]) -> list[tuple[float, float, str]]:
    """Nested annotations (``serve.assemble`` inside ``serve.prefix_seed``)
    flattened: each instant belongs to the innermost one open at it."""
    out: list[tuple[float, float, str]] = []
    stack: list[tuple[float, str]] = []  # (end, name), outermost first
    cursor = None

    def emit(until: float) -> None:
        if stack and cursor is not None and until > cursor:
            out.append((cursor, until, stack[-1][1]))

    for start, end, name in sorted(annotations, key=lambda a: (a[0], -a[1])):
        while stack and stack[-1][0] <= start:
            closed = stack[-1][0]
            emit(closed)
            stack.pop()
            cursor = closed
        emit(start)
        stack.append((end, name))
        cursor = start
    while stack:
        closed = stack[-1][0]
        emit(closed)
        stack.pop()
        cursor = closed
    return out


def split(t0: float, t1: float, segments: list[tuple[float, float, str]]) -> dict[str, float]:
    """Seconds of ``[t0, t1)`` by the segment's name; the rest under ``(none)``."""
    out: dict[str, float] = {}
    covered = 0.0
    for start, end, name in segments:
        seconds = (min(end, t1) - max(start, t0)) / 1e9
        if seconds > 0:
            out[name] = out.get(name, 0.0) + seconds
            covered += seconds
    rest = (t1 - t0) / 1e9 - covered
    if rest > 1e-9:
        out[NONE] = rest
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


def attribute(events: list[dict], layout: dict, longest: int = 10) -> dict | None:
    """The idle time of the first device plane by loop annotation."""
    planes = sorted({e["plane"] for e in events if e["plane"].startswith(layout["plane_prefix"])})
    ops_re = re.compile(layout["ops_line"])
    busy = []
    for plane in planes:
        busy = [(e["start_ns"], e["start_ns"] + e["dur_ns"]) for e in events
                if e["plane"] == plane and ops_re.search(e["line"]) and e["dur_ns"] > 0]
        if busy:
            break
    if not busy:
        return None
    span0, span1 = min(s for s, _ in busy), max(e for _, e in busy)
    annotations = loop_annotations(events)
    segments = exclusive_segments(annotations)
    gaps = [(start, start + seconds * 1e9) for start, seconds in xplane.gaps(busy, limit=len(busy))]
    idle_by: dict[str, float] = {}
    for g0, g1 in gaps:
        for name, seconds in split(g0, g1, segments).items():
            idle_by[name] = idle_by.get(name, 0.0) + seconds
    span_by = split(span0, span1, segments)
    span_s = (span1 - span0) / 1e9
    idle_s = sum(g1 - g0 for g0, g1 in gaps) / 1e9
    no_work_idle = idle_by.get(NO_WORK, 0.0) + idle_by.get(NO_WORK + STARVED, 0.0)
    no_work_span = span_by.get(NO_WORK, 0.0) + span_by.get(NO_WORK + STARVED, 0.0)
    # under annotations that carry the clock's bit, nobody-to-serve left out
    marked = [name for name in span_by if name.endswith(STARVED) and name != NO_WORK + STARVED]
    marked_span = sum(span_by[name] for name in marked)
    marked_idle = sum(idle_by.get(name, 0.0) for name in marked)
    # seconds with work in which no model program ran, the gaps inside a program left out
    modules = []
    if "modules_line" in layout:
        line_re = re.compile(layout["modules_line"])
        model_re = re.compile("|".join(layout["programs"][role] for role in MODEL_PROGRAMS))
        modules = [(e["start_ns"], e["start_ns"] + e["dur_ns"]) for e in events
                   if e["plane"] == plane and line_re.search(e["line"]) and model_re.search(e["name"])]
    # the chip already idle when a blocking read came back: the gap open at the
    # annotation's end, as far back as the annotation goes
    by_start = sorted(gaps)
    starts = [g0 for g0, _ in by_start]
    late = 0.0
    for start, end, name in annotations:
        at = bisect.bisect_left(starts, end) - 1  # the last gap that began before the end
        if name.removesuffix(STARVED) in BLOCKING_READS and at >= 0 and by_start[at][1] >= end:
            late += (end - max(by_start[at][0], start)) / 1e9
    no_program_s = None
    if modules:
        clipped = [(max(a, span0), min(b, span1)) for a, b in modules if b > span0 and a < span1]
        no_program_s = sum(
            seconds for name, seconds in _uncovered_by(clipped, span0, span1, segments).items()
            if not name.startswith(NO_WORK))
    table = []
    for g0, g1 in sorted(gaps, key=lambda g: g[0] - g[1])[:longest]:
        by = split(g0, g1, segments)
        first = next(iter(by))
        table.append({"at_s": (g0 - span0) / 1e9, "ms": (g1 - g0) / 1e6, "mostly": first,
                      "share": by[first] / ((g1 - g0) / 1e9),
                      "by_ms": {k: round(v * 1e3, 3) for k, v in by.items()}})
    return {
        "span_s": span_s, "idle_s": idle_s, "annotations": len(annotations),
        "idle_by_annotation_s": dict(sorted(idle_by.items(), key=lambda kv: -kv[1])),
        "span_by_annotation_s": span_by,
        "device_idle_share": 100.0 * idle_s / span_s,
        "device_idle_with_work_share": (
            100.0 * (idle_s - no_work_idle) / (span_s - no_work_span)
            if span_s - no_work_span > 0 else None),
        "idle_with_work_s": idle_s - no_work_idle,
        "late_return_s": late,
        "no_model_program_with_work_s": no_program_s,
        "under_starved_annotations": {
            "span_s": marked_span, "idle_s": marked_idle,
            "recall": marked_idle / (idle_s - no_work_idle) if idle_s > no_work_idle else None,
            "busy_share": (marked_span - marked_idle) / marked_span if marked_span > 0 else None,
        },
        "longest_gaps": table,
    }


def _uncovered_by(busy, t0: float, t1: float, segments) -> dict[str, float]:
    """Seconds of ``[t0, t1)`` outside the union of ``busy``, by segment name."""
    out: dict[str, float] = {}
    cursor = t0
    for start, end in sorted(busy) + [(t1, t1)]:
        if start > cursor:
            for name, seconds in split(cursor, start, segments).items():
                out[name] = out.get(name, 0.0) + seconds
        cursor = max(cursor, end)
    return out


def clock_over_span(pair, capture: dict | None) -> dict | None:
    """The starved clock between two snapshots taken around the captured span,
    beside what the capture saw of the device over it."""
    if not pair or STARVED_FAMILY not in pair[1]:
        return None

    def by_phase(family: str) -> dict[str, float]:
        before, after = ({s["labels"].get("phase", ""): s["value"]
                          for s in snapshot.get(family, {}).get("series", [])} for snapshot in pair)
        return {p: after[p] - before.get(p, 0.0) for p in after if after[p] - before.get(p, 0.0) > 0}

    starved_by = by_phase(STARVED_FAMILY)
    starved = sum(s for phase, s in starved_by.items() if phase != "no_work")
    unseen = sum(by_phase(UNSEEN_FAMILY).values())
    out = {"snapshots_s": (pair[1]["captured_at"]["series"][0]["value"]
                           - pair[0]["captured_at"]["series"][0]["value"]),
           "starved_s": starved, "unseen_s": unseen,
           "starved_by_phase_s": dict(sorted(starved_by.items(), key=lambda kv: -kv[1]))}
    if capture:
        late = out["late_return_s"] = capture["late_return_s"]
        for key in ("idle_with_work", "no_model_program_with_work"):
            seen = out[f"{key}_s"] = capture[f"{key}_s"]
            # between the clock's two bounds, give or take a tenth; then with the
            # blocking reads' late returns, which no look can see, left out
            for tag, less in (("within_bounds", 0.0), ("less_late_within_bounds", late)):
                out[f"{key}_{tag}"] = (None if seen is None else
                                       0.9 * starved <= seen - less <= 1.1 * (starved + unseen))
        out.update(recall=capture["under_starved_annotations"]["recall"],
                   busy_share_under_bit=capture["under_starved_annotations"]["busy_share"])
    return out


@contextlib.contextmanager
def snapshots_around_the_capture(registry):
    """The registry's snapshot right after ``jax.profiler.start_trace`` returns
    and right before ``stop_trace`` is called, while ``one_window`` runs."""
    import jax

    taken: list[dict] = []
    start, stop = jax.profiler.start_trace, jax.profiler.stop_trace

    def start_then_snapshot(*args, **kwargs):
        start(*args, **kwargs)
        taken.append(registry.snapshot())

    def snapshot_then_stop(*args, **kwargs):
        taken.append(registry.snapshot())
        stop(*args, **kwargs)

    jax.profiler.start_trace, jax.profiler.stop_trace = start_then_snapshot, snapshot_then_stop
    try:
        yield taken
    finally:
        jax.profiler.start_trace, jax.profiler.stop_trace = start, stop


def clock_against_wall(pair) -> dict | None:
    """Sum of the phases between two snapshots beside their own interval."""
    if not pair or FAMILY not in pair[1]:
        return None

    def phases(snapshot: dict) -> dict[str, float]:
        return {s["labels"]["phase"]: s["value"]
                for s in snapshot.get(FAMILY, {}).get("series", [])}

    before, after = phases(pair[0]), phases(pair[1])
    by = {p: after[p] - before.get(p, 0.0) for p in after if after[p] - before.get(p, 0.0) > 0}
    wall = (pair[1]["captured_at"]["series"][0]["value"]
            - pair[0]["captured_at"]["series"][0]["value"])
    total = sum(by.values())
    return {"phases_s": total, "snapshots_s": wall, "off_by": (total - wall) / wall if wall else None,
            "by_phase_s": dict(sorted(by.items(), key=lambda kv: -kv[1]))}


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--out", default=str(REPO_ROOT / "chiprun_out" / "loop_gaps"))
    ours, rest = parser.parse_known_args(argv)

    from benchmark import run as bench_run
    from benchmark.harness.manifest import BENCH_DIR, load_manifest, resolve_cell
    from benchmark.systems import serve_http

    args = bench_run.parse(rest)  # the harness's own options: --workload --seed --seconds --rehearse
    resolved = resolve_cell(load_manifest(), args.workload)
    if args.rehearse:
        import os

        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ.setdefault("PRIME_TPU_PALLAS_INTERPRET", "1")
    import prime_tpu  # noqa: F401  places the compile cache, as benchmark/run.py does
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    device, _ = bench_run.device_gate(resolved["entry"]["chips"], args.rehearse)
    layout = json.loads((BENCH_DIR / "layer_metrics" / "programs.json").read_text())[device["platform"]]
    counter = serve_http.CompileCounter()
    server, backend, serve_args, model = serve_http.bring_up(resolved, args.rehearse, None)
    try:
        with tempfile.TemporaryDirectory(prefix="loop-gaps-") as tmp, \
                snapshots_around_the_capture(backend.engine.registry) as around:
            run = serve_http.one_window(resolved, backend, server, serve_args, model, args.seed,
                                        args.seconds, 1, Path(tmp), counter, bench_run.T_START,
                                        args.rehearse, drain_s=args.drain)
            # the device's rows from the harness's reader, the loop's with their bit from ours
            events = [e for e in xplane.read_events(run["trace_dir"])
                      if not (e["plane"] == HOST_PLANE and e["name"].startswith("serve."))]
            events += read_annotations(run["trace_dir"])
    finally:
        serve_http.tear_down(server, backend)
    gaps = attribute(events, layout)
    # the benchmark's own readers of the phase clock over this window, as a traced run prints them
    import importlib

    context = {**run, "counter_delta": serve_http.counter_delta}
    readers = {name: importlib.import_module(f"benchmark.layer_metrics.{name}").read(context)
               for name in ("loop_host_share", "chip_starved_share", "chip_starved_emit_share",
                            "chip_starved_admission_share", "chip_starved_upkeep_share",
                            "chip_starved_build_share", "chip_starved_unseen_share")}
    report = {"workload": args.workload, "seed": args.seed,
              "capture_s": run["trace_span"][1] - run["trace_span"][0],
              # a program built inside the window stalls the loop: read the table with that in mind
              "built_in_window": run["compiles"],
              "window": clock_against_wall(run["counters"]),
              "window_starved": clock_over_span(run["counters"], None),
              "window_readers": readers,
              "starved_clock": clock_over_span(tuple(around) if len(around) == 2 else None, gaps),
              "gaps": gaps}
    out = Path(ours.out)
    out.mkdir(parents=True, exist_ok=True)
    (out / f"{args.workload}-{args.seed}.json").write_text(json.dumps(report, indent=1))
    print(json.dumps(report), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
