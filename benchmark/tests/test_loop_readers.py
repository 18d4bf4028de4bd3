"""The five readers of the engine loop's phase clock, each on a hand-made run
(two registry snapshots), the zero denominators included."""
import importlib

import pytest

from benchmark.systems.serve_http import counter_delta

FAMILY = "serve_loop_seconds_total"


def snapshot(at: float, phases: dict | None, **counters: float) -> dict:
    out = {"captured_at": {"type": "gauge", "series": [{"labels": {}, "value": at}]}}
    if phases is not None:
        out[FAMILY] = {"type": "counter", "series": [
            {"labels": {"phase": phase}, "value": seconds} for phase, seconds in phases.items()]}
    for name, value in counters.items():
        out[name] = {"type": "counter", "series": [{"labels": {}, "value": value}]}
    return out


def read(metric: str, **run):
    reader = importlib.import_module(f"benchmark.layer_metrics.{metric}")
    return reader.read({**run, "counter_delta": counter_delta})


BEFORE = snapshot(100.0, {"no_work": 5.0, "sync_wait": 20.0, "admit": 1.0, "warmup": 700.0},
                  serve_requests_admitted_total=3, serve_prefix_hits_total=2)
# ten seconds later: 4 of them idle, 4.5 blocked on the device, 1.5 of host work
AFTER = snapshot(110.0, {"no_work": 9.0, "sync_wait": 24.0, "prefill_sync": 0.5, "admit": 1.1,
                         "prefix_seed": 0.2, "prefill": 0.3, "prefix_store": 0.1,
                         "program_build": 0.25, "emit": 0.3, "other": 0.25, "warmup": 700.0},
                 serve_requests_admitted_total=8, serve_prefix_hits_total=6)
WINDOW = (BEFORE, AFTER)
NO_CLOCK = (snapshot(100.0, None, serve_requests_admitted_total=3, serve_prefix_hits_total=2),
            snapshot(110.0, None, serve_requests_admitted_total=8, serve_prefix_hits_total=6))
STOOD_STILL = (AFTER, AFTER)


def test_loop_host_share():
    # with work: 10 - 4 = 6 s, of which 4.5 blocked on the device
    assert read("loop_host_share", counters=WINDOW) == pytest.approx(100 * (1 - 4.5 / 6.0))
    assert read("loop_host_share", counters=NO_CLOCK) is None
    assert read("loop_host_share", counters=STOOD_STILL) is None
    only_idle = (snapshot(0.0, {"no_work": 1.0}), snapshot(2.0, {"no_work": 3.0}))
    assert read("loop_host_share", counters=only_idle) is None


def test_admission_stall_ms_is_the_host_part():
    # admit 0.1 + prefix_seed 0.2 + prefill 0.3 + prefix_store 0.1 over 5; not prefill_sync
    assert read("admission_stall_ms", counters=WINDOW) == pytest.approx(700.0 / 5)
    assert read("admission_stall_ms", counters=NO_CLOCK) is None
    assert read("admission_stall_ms", counters=STOOD_STILL) is None  # nobody admitted


def test_admission_sync_ms_is_the_wait_on_the_device():
    assert read("admission_sync_ms", counters=WINDOW) == pytest.approx(500.0 / 5)
    assert read("admission_sync_ms", counters=NO_CLOCK) is None
    assert read("admission_sync_ms", counters=STOOD_STILL) is None
    # admitted, and never blocked (no such series yet): 0, not nothing
    never = (snapshot(0.0, {"admit": 1.0}, serve_requests_admitted_total=0),
             snapshot(1.0, {"admit": 2.0}, serve_requests_admitted_total=2))
    assert read("admission_sync_ms", counters=never) == 0.0


def test_program_build_stall_ms():
    assert read("program_build_stall_ms", counters=WINDOW) == pytest.approx(250.0)
    assert read("program_build_stall_ms", counters=STOOD_STILL) == 0.0  # a clock, and no build
    assert read("program_build_stall_ms", counters=NO_CLOCK) is None


def test_prefix_path_ms_per_hit():
    assert read("prefix_path_ms_per_hit", counters=WINDOW) == pytest.approx(300.0 / 4)
    assert read("prefix_path_ms_per_hit", counters=NO_CLOCK) is None
    assert read("prefix_path_ms_per_hit", counters=STOOD_STILL) is None  # no hit


def test_manifest_lists_the_readers():
    from benchmark.harness.manifest import load_manifest, metrics_for

    manifest = load_manifest()
    names = [m["name"] for m in manifest["per_layer"]]
    new = ["loop_host_share", "admission_stall_ms", "admission_sync_ms",
           "program_build_stall_ms", "prefix_path_ms_per_hit"]
    assert names[-5:] == new
    chat = {m["name"] for m in metrics_for(manifest, "per_layer", "qwen3-4b.chat")}
    assert set(new[:4]) <= chat and "prefix_path_ms_per_hit" not in chat
    sessions = {m["name"] for m in metrics_for(manifest, "per_layer", "qwen3-4b.prefix-sessions")}
    assert set(new) <= sessions
