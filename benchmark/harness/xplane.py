"""From a ``jax.profiler`` trace to numbers. The reduction is the yardstick's.

``read_events`` flattens the ``.xplane.pb`` files under a trace directory into
``(plane, line, name, start_ns, dur_ns)`` rows; everything after that is plain
arithmetic on those rows, so the tests run it on a small trace kept as JSON.
Which planes are devices, which line holds device operations and which holds
whole programs, and which program is "decode" or "chunk_prefill", is data:
``layer_metrics/programs.json``, written after looking at one trace by hand.
"""

from __future__ import annotations

import re
from pathlib import Path


def read_events(trace_dir) -> list[dict]:
    from jax.profiler import ProfileData

    rows = []
    for path in sorted(Path(trace_dir).rglob("*.xplane.pb")):
        for plane in ProfileData.from_file(str(path)).planes:
            for line in plane.lines:
                for event in line.events:
                    rows.append({"plane": plane.name, "line": line.name, "name": event.name,
                                 "start_ns": float(event.start_ns),
                                 "dur_ns": float(event.duration_ns)})
    return rows


def union_s(intervals) -> float:
    """Seconds covered by the union of ``(start_ns, end_ns)`` intervals."""
    total, cur_start, cur_end = 0.0, None, None
    for start, end in sorted(intervals):
        if cur_end is None or start > cur_end:
            if cur_end is not None:
                total += cur_end - cur_start
            cur_start, cur_end = start, end
        else:
            cur_end = max(cur_end, end)
    if cur_end is not None:
        total += cur_end - cur_start
    return total / 1e9


def gaps(intervals, limit: int = 10) -> list[tuple[float, float]]:
    """The longest idle gaps between merged intervals: ``(start_ns, seconds)``."""
    out, cur_end = [], None
    for start, end in sorted(intervals):
        if cur_end is not None and start > cur_end:
            out.append((cur_end, (start - cur_end) / 1e9))
        cur_end = end if cur_end is None else max(cur_end, end)
    return sorted(out, key=lambda g: -g[1])[:limit]


def short_name(name: str, limit: int = 80) -> str:
    """``%copy.85 = bf16[36,8]{...} copy(...)`` -> ``%copy.85 bf16[36,8]``."""
    lhs, sep, rhs = name.partition(" = ")
    if not sep:
        return name[:limit]
    return f"{lhs} {rhs.split('{')[0].strip()}"[:limit]


def _rows(events, plane: str, line_pattern: str):
    line_re = re.compile(line_pattern)
    return [e for e in events if e["plane"] == plane and line_re.search(e["line"])]


def reduce(events: list[dict], layout: dict) -> dict | None:
    """Per device: busy seconds (union of device-operation intervals) and the
    span they cover; per program role: summed duration and event count of the
    matching whole-program events (``layout["programs"]``) on the first device; the operations that took
    most time; the longest idle gaps. None where no device plane has events."""
    planes = sorted({e["plane"] for e in events if e["plane"].startswith(layout["plane_prefix"])})
    per_device = []
    for plane in planes:
        ops = [e for e in _rows(events, plane, layout["ops_line"]) if e["dur_ns"] > 0]
        if not ops:
            continue
        intervals = [(e["start_ns"], e["start_ns"] + e["dur_ns"]) for e in ops]
        span_ns = max(end for _, end in intervals) - min(start for start, _ in intervals)
        per_device.append({"plane": plane, "busy_s": union_s(intervals), "window_s": span_ns / 1e9,
                           "ops": ops, "intervals": intervals})
    if not per_device:
        return None
    first = per_device[0]
    modules = _rows(events, first["plane"], layout["modules_line"])
    by_role = {}
    for role, pattern in layout["programs"].items():
        role_re = re.compile(pattern)
        hits = [e for e in modules if role_re.search(e["name"])]
        by_role[role] = {"seconds": sum(e["dur_ns"] for e in hits) / 1e9, "events": len(hits)}
    # containers (a while loop, a call) hold the operations that do the work, so
    # the ranking leaves them out; busy time above is a union and counts them once
    container_re = re.compile(layout.get("containers", r"$^"))
    op_seconds: dict[str, float] = {}
    for e in first["ops"]:
        name = short_name(e["name"])
        if not container_re.search(name):
            op_seconds[name] = op_seconds.get(name, 0.0) + e["dur_ns"] / 1e9
    module_names: dict[str, float] = {}
    for e in modules:
        module_names[e["name"]] = module_names.get(e["name"], 0.0) + e["dur_ns"] / 1e9
    collective_re = re.compile(layout.get("collectives", r"$^"))
    collective = [(e["start_ns"], e["start_ns"] + e["dur_ns"]) for e in first["ops"]
                  if collective_re.search(e["name"])]
    return {
        "devices": len(per_device),
        "busy_s": sum(d["busy_s"] for d in per_device) / len(per_device),
        "window_s": sum(d["window_s"] for d in per_device) / len(per_device),
        "first_device": {"busy_s": first["busy_s"], "window_s": first["window_s"],
                         "collective_s": union_s(collective)},
        "programs": by_role,
        "module_seconds": dict(sorted(module_names.items(), key=lambda kv: -kv[1])[:20]),
        "device_ops": [[name, s] for name, s in sorted(op_seconds.items(), key=lambda kv: -kv[1])[:10]],
        "idle_gaps": [["unattributed", s] for _, s in gaps(first["intervals"])],
    }


def inventory(events: list[dict], top: int = 25) -> dict:
    """Planes, their lines and the names that took most time on each: what to
    look at by hand before trusting ``programs.json``."""
    out: dict = {}
    for e in events:
        line = out.setdefault(e["plane"], {}).setdefault(e["line"], {"events": 0, "seconds": 0.0, "names": {}})
        line["events"] += 1
        line["seconds"] += e["dur_ns"] / 1e9
        entry = line["names"].setdefault(e["name"], [0, 0.0])
        entry[0] += 1
        entry[1] += e["dur_ns"] / 1e9
    for lines in out.values():
        for line in lines.values():
            line["names"] = dict(sorted(line["names"].items(), key=lambda kv: -kv[1][1])[:top])
    return out
