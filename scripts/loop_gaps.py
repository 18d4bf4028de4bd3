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

The benchmark cannot report any of this yet: its readers run after the
capture's directory is gone and see only ``xplane.reduce``'s summary (PERF.md
section 7, first in line for the next ``benchmark`` issue). The arithmetic
below is what that would take over.
"""

from __future__ import annotations

import argparse
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
NONE = "(none)"


def loop_annotations(events: list[dict]) -> list[tuple[float, float, str]]:
    """``(start_ns, end_ns, name)`` of the ``serve.*`` events on the loop
    thread's line, or [] where the capture holds no ``serve.dispatch``."""
    lines = {e["line"] for e in events if e["plane"] == HOST_PLANE and e["name"] == ANCHOR}
    return sorted(
        (e["start_ns"], e["start_ns"] + e["dur_ns"], e["name"]) for e in events
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
    no_work_idle = idle_by.get("serve.no_work", 0.0)
    no_work_span = span_by.get("serve.no_work", 0.0)
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
        "longest_gaps": table,
    }


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
        with tempfile.TemporaryDirectory(prefix="loop-gaps-") as tmp:
            run = serve_http.one_window(resolved, backend, server, serve_args, model, args.seed,
                                        args.seconds, 1, Path(tmp), counter, bench_run.T_START,
                                        args.rehearse, drain_s=args.drain)
            events = xplane.read_events(run["trace_dir"])
    finally:
        serve_http.tear_down(server, backend)
    report = {"workload": args.workload, "seed": args.seed,
              "capture_s": run["trace_span"][1] - run["trace_span"][0],
              # a program built inside the window stalls the loop: read the table with that in mind
              "built_in_window": run["compiles"],
              "window": clock_against_wall(run["counters"]),
              "gaps": attribute(events, layout)}
    out = Path(ours.out)
    out.mkdir(parents=True, exist_ok=True)
    (out / f"{args.workload}-{args.seed}.json").write_text(json.dumps(report, indent=1))
    print(json.dumps(report), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
