"""The six readers of the phase clock's starved seconds, each on a hand-made
run (two registry snapshots): the four groups sum to the whole, the
denominator is the loop's seconds with work, and a program without the family
(the parent of the PR that brought it) reads nothing."""
import importlib

import pytest

from benchmark.systems.serve_http import counter_delta

LOOP = "serve_loop_seconds_total"
STARVED = "serve_loop_starved_seconds_total"
UNSEEN = "serve_loop_starved_unseen_seconds_total"
READERS = ("chip_starved_share", "chip_starved_emit_share", "chip_starved_admission_share",
           "chip_starved_upkeep_share", "chip_starved_build_share", "chip_starved_unseen_share")


def snapshot(at: float, loop: dict, starved: dict | None, unseen: float | None) -> dict:
    def family(phases):
        return {"type": "counter", "series": [
            {"labels": {"phase": phase}, "value": seconds} for phase, seconds in phases.items()]}

    out = {"captured_at": {"type": "gauge", "series": [{"labels": {}, "value": at}]},
           LOOP: family(loop)}
    if starved is not None:
        out[STARVED] = family(starved)
    if unseen is not None:
        out[UNSEEN] = {"type": "counter", "series": [{"labels": {}, "value": unseen}]}
    return out


def read(metric: str, pair):
    reader = importlib.import_module(f"benchmark.layer_metrics.{metric}")
    return reader.read({"counters": pair, "counter_delta": counter_delta})


# twenty seconds of the loop, ten of them with nobody to serve
BEFORE = snapshot(100.0, {"no_work": 5.0, "sync_wait": 50.0, "emit": 2.0, "warmup": 700.0},
                  {"no_work": 5.0, "emit": 1.0, "warmup": 3.0}, 0.5)
AFTER = snapshot(
    120.0,
    {"no_work": 15.0, "sync_wait": 55.0, "emit": 3.0, "admit": 0.5, "prefix_seed": 0.25,
     "prefill": 0.5, "prefix_store": 0.25, "prefill_sync": 1.0, "dispatch": 0.5, "stats": 0.25,
     "kv_jobs": 0.125, "other": 0.125, "program_build": 0.25, "gc": 0.25, "warmup": 700.0},
    {"no_work": 15.0, "emit": 1.5, "admit": 0.25, "prefix_seed": 0.125, "prefill": 0.0625,
     "prefix_store": 0.0, "prefill_sync": 0.0625, "dispatch": 0.125, "sync_wait": 0.0625,
     "stats": 0.03125, "kv_jobs": 0.015625, "other": 0.015625, "program_build": 0.125,
     "gc": 0.125, "warmup": 3.0},
    0.75)
WINDOW = (BEFORE, AFTER)
WITH_WORK = 10.0
EXPECTED = {
    "chip_starved_emit_share": 0.5,
    "chip_starved_admission_share": 0.25 + 0.125 + 0.0625 + 0.0 + 0.0625,
    "chip_starved_upkeep_share": 0.125 + 0.0625 + 0.03125 + 0.015625 + 0.015625,
    "chip_starved_build_share": 0.25,
    "chip_starved_unseen_share": 0.25,
}


@pytest.mark.parametrize("metric", sorted(EXPECTED))
def test_a_group_is_its_phases_starved_seconds_over_the_loops_seconds_with_work(metric):
    assert read(metric, WINDOW) == pytest.approx(100.0 * EXPECTED[metric] / WITH_WORK)


def test_the_four_groups_sum_to_the_whole_and_no_work_is_left_out():
    groups = [read(m, WINDOW) for m in READERS[1:5]]
    assert read("chip_starved_share", WINDOW) == pytest.approx(sum(groups))
    assert sum(groups) == pytest.approx(100.0 * 1.5 / WITH_WORK)


@pytest.mark.parametrize("metric", READERS)
def test_a_program_without_the_family_or_without_time_reads_nothing(metric):
    parent = tuple(snapshot(s["captured_at"]["series"][0]["value"],
                            {x["labels"]["phase"]: x["value"] for x in s[LOOP]["series"]}, None, None)
                   for s in WINDOW)
    assert read(metric, parent) is None
    assert read(metric, (AFTER, AFTER)) is None  # the loop stood still
    only_idle = (snapshot(0.0, {"no_work": 1.0}, {"no_work": 1.0}, 0.0),
                 snapshot(2.0, {"no_work": 3.0}, {"no_work": 3.0}, 0.0))
    assert read(metric, only_idle) is None
    assert read(metric, None) is None


@pytest.mark.parametrize("metric", READERS)
def test_a_clock_that_saw_no_starved_second_reads_zero_not_nothing(metric):
    # the families are registered with the engine: there from the first snapshot,
    # with no series until a phase is charged
    busy = (snapshot(0.0, {"sync_wait": 1.0}, {}, 0.0), snapshot(2.0, {"sync_wait": 3.0}, {}, 0.0))
    assert read(metric, busy) == 0.0


def test_manifest_lists_the_six_at_its_end_for_every_cell():
    from benchmark.harness.manifest import load_manifest, metrics_for

    manifest = load_manifest()
    assert [m["name"] for m in manifest["per_layer"]][-6:] == list(READERS)
    for entry in manifest["per_layer"][-6:]:
        assert entry == {"name": entry["name"], "unit": "%", "better": "lower",
                         "source": "program_counter", "layer": "engine serve/engine.py",
                         "moves": "tpot_p50_ms"}
    for cell in manifest["workloads"]:
        listed = {m["name"] for m in metrics_for(manifest, "per_layer", cell["name"])}
        assert set(READERS) <= listed, cell["name"]
