"""scripts/loop_gaps.py: laying the loop thread's annotations over the device's
idle gaps is plain arithmetic on trace rows, so it runs on a hand-made trace."""
import importlib.util
from pathlib import Path

import pytest

SPEC = importlib.util.spec_from_file_location(
    "loop_gaps", Path(__file__).resolve().parents[1] / "scripts" / "loop_gaps.py")
loop_gaps = importlib.util.module_from_spec(SPEC)
SPEC.loader.exec_module(loop_gaps)

LAYOUT = {"plane_prefix": "/device:TPU:", "ops_line": "^XLA Ops$"}
MS = 1_000_000


def op(start_ms, dur_ms):
    return {"plane": "/device:TPU:0", "line": "XLA Ops", "name": "fusion", "start_ns": start_ms * MS,
            "dur_ns": dur_ms * MS}


def note(name, start_ms, dur_ms, line="loop", starved=None):
    return {"plane": "/host:CPU", "line": line, "name": name, "start_ns": start_ms * MS,
            "dur_ns": dur_ms * MS, "starved": starved}


def program(name, start_ms, dur_ms):
    return {"plane": "/device:TPU:0", "line": "XLA Modules", "name": name,
            "start_ns": start_ms * MS, "dur_ns": dur_ms * MS}


def test_nested_annotations_flatten_to_the_innermost():
    segments = loop_gaps.exclusive_segments([
        (0, 100, "serve.prefix_seed"), (20, 50, "serve.assemble"), (120, 130, "serve.sync")])
    assert segments == [(0, 20, "serve.prefix_seed"), (20, 50, "serve.assemble"),
                        (50, 100, "serve.prefix_seed"), (120, 130, "serve.sync")]
    assert loop_gaps.split(40, 125, segments) == {
        "serve.prefix_seed": pytest.approx(50e-9), "serve.assemble": pytest.approx(10e-9),
        loop_gaps.NONE: pytest.approx(20e-9), "serve.sync": pytest.approx(5e-9)}


def test_gaps_are_put_down_to_the_phase_they_fell_in():
    events = [
        op(0, 10), op(10, 10),            # busy 0-20
        op(60, 10),                       # idle 20-60: admission on the host
        op(170, 10),                      # idle 70-170: nobody to serve
        note("serve.dispatch", 0, 1), note("serve.sync", 1, 19),
        note("serve.admit", 20, 5), note("serve.prefill", 25, 10), note("serve.prefill_sync", 35, 20),
        note("serve.dispatch", 56, 2), note("serve.sync", 58, 12),
        note("serve.no_work", 72, 90),
        note("serve.dispatch", 165, 3),
        note("serve.chat", 0, 200, line="http-thread"),  # another thread's line: not the loop's
    ]
    out = loop_gaps.attribute(events, LAYOUT)
    assert out["span_s"] == pytest.approx(0.180) and out["idle_s"] == pytest.approx(0.140)
    assert out["device_idle_share"] == pytest.approx(100 * 140 / 180)
    longest, second = out["longest_gaps"]
    assert longest["ms"] == pytest.approx(100) and longest["mostly"] == "serve.no_work"
    assert longest["share"] == pytest.approx(0.9)
    assert second["ms"] == pytest.approx(40) and second["mostly"] == "serve.prefill_sync"
    assert second["by_ms"] == {"serve.prefill_sync": 20.0, "serve.prefill": 10.0,
                               "serve.admit": 5.0, "serve.dispatch": 2.0, "serve.sync": 2.0,
                               loop_gaps.NONE: 1.0}
    assert "serve.chat" not in out["span_by_annotation_s"]
    # with the 90 ms of no request left out: 50 idle ms of the 90 with work
    assert out["device_idle_with_work_share"] == pytest.approx(100 * 50 / 90)


def test_a_capture_without_the_clock_or_without_a_device():
    events = [op(0, 10), op(30, 10)]  # the parent: no annotation at all
    out = loop_gaps.attribute(events, LAYOUT)
    assert out["annotations"] == 0 and out["longest_gaps"][0]["mostly"] == loop_gaps.NONE
    assert out["device_idle_with_work_share"] == pytest.approx(50.0)
    assert loop_gaps.attribute([note("serve.dispatch", 0, 1)], LAYOUT) is None


def test_clock_against_wall():
    def snap(at, **phases):
        return {"captured_at": {"series": [{"labels": {}, "value": at}]},
                loop_gaps.FAMILY: {"series": [{"labels": {"phase": p}, "value": v}
                                              for p, v in phases.items()]}}

    out = loop_gaps.clock_against_wall((snap(10.0, sync_wait=1.0, warmup=5.0),
                                        snap(20.0, sync_wait=8.0, no_work=2.9, warmup=5.0)))
    assert out["phases_s"] == pytest.approx(9.9) and out["snapshots_s"] == pytest.approx(10.0)
    assert out["off_by"] == pytest.approx(-0.01)
    assert list(out["by_phase_s"]) == ["sync_wait", "no_work"]
    assert loop_gaps.clock_against_wall(({"captured_at": {}}, {"captured_at": {}})) is None


def test_the_clocks_bit_is_held_against_the_device():
    """The same loop with the bit on the annotations the clock opened starved,
    the prefill's operations, and the programs' line: what the capture says of
    the clock's claim."""
    layout = {**LAYOUT, "modules_line": "^XLA Modules$",
              "programs": {"decode": "^jit_decode\\(", "chunk_prefill": "^jit_chunk_prefill\\(",
                           "finalize": "^jit_finalize_batch\\(", "store": "^jit__store_pages\\("}}
    events = [
        op(0, 10), op(10, 10), op(40, 12), op(56, 3), op(60, 10), op(170, 10),
        program("jit_decode(1)", 0, 20), program("jit_chunk_prefill(2)", 40, 12),
        program("jit__store_pages(3)", 56, 3),  # a page program: the chip is busy, with no model program
        program("jit_decode(1)", 60, 10), program("jit_decode(1)", 170, 10),
        note("serve.dispatch", 0, 1), note("serve.sync", 1, 19),
        note("serve.admit", 20, 5, starved=1), note("serve.prefill", 25, 10, starved=1),
        note("serve.prefill_sync", 35, 20),  # the prefill enqueued: opened with the chip about to be busy
        note("serve.dispatch", 56, 2, starved=1), note("serve.sync", 58, 12),
        note("serve.no_work", 72, 90, starved=1),
        note("serve.dispatch", 165, 3, starved=1),
    ]
    out = loop_gaps.attribute(events, layout)
    # the tags are on the names: the gap table tells a starved phase from a hidden one
    assert out["longest_gaps"][0]["mostly"] == "serve.no_work (starved)"
    assert out["longest_gaps"][1]["by_ms"] == {
        "serve.prefill (starved)": 10.0, "serve.admit (starved)": 5.0, "serve.prefill_sync": 5.0}
    # idle 20-40, 52-56, 59-60, 70-170; 90 ms of it with nobody to serve
    assert out["idle_with_work_s"] == pytest.approx(0.035)
    assert out["device_idle_with_work_share"] == pytest.approx(100 * 35 / 90)
    # admit 5, prefill 10, dispatch 2 (the chip storing pages meanwhile) + 3; not no_work
    under = out["under_starved_annotations"]
    assert under["span_s"] == pytest.approx(0.020) and under["idle_s"] == pytest.approx(0.018)
    assert under["recall"] == pytest.approx(18 / 35)
    assert under["busy_share"] == pytest.approx(2 / 20)
    # no model program over 20-40, 52-60, 70-170 less the 90 ms of nobody to serve
    assert out["no_model_program_with_work_s"] == pytest.approx(0.020 + 0.008 + 0.010)
    # the prefill's read came back 3 ms after the chip had finished (52-55); the sync on time
    assert out["late_return_s"] == pytest.approx(0.003)

    def snap(at, unseen, **starved):
        return {"captured_at": {"series": [{"labels": {}, "value": at}]},
                loop_gaps.STARVED_FAMILY: {"series": [{"labels": {"phase": p}, "value": v}
                                                      for p, v in starved.items()]},
                loop_gaps.UNSEEN_FAMILY: {"series": [{"labels": {}, "value": unseen}]}}

    pair = (snap(10.0, 1.0, no_work=3.0, admit=0.5), snap(10.18, 1.012, no_work=3.09, admit=0.505,
                                                          prefill=0.01, dispatch=0.005))
    held = loop_gaps.clock_over_span(pair, out)
    assert held["starved_s"] == pytest.approx(0.020) and held["unseen_s"] == pytest.approx(0.012)
    assert list(held["starved_by_phase_s"])[0] == "no_work"
    # 20 to 32 ms claimed, give or take a tenth: 18 to 35.2
    assert held["idle_with_work_within_bounds"] is True  # 35
    assert held["no_model_program_with_work_within_bounds"] is False  # 38
    assert held["no_model_program_with_work_less_late_within_bounds"] is True  # 35
    assert held["late_return_s"] == pytest.approx(0.003)
    assert held["recall"] == pytest.approx(18 / 35)
    assert held["busy_share_under_bit"] == pytest.approx(2 / 20)
    # a program without the starved clock, and a capture without the programs' line
    assert loop_gaps.clock_over_span(({"captured_at": {}}, {"captured_at": {}}), out) is None
    bare = loop_gaps.attribute([e for e in events if e["line"] != "XLA Modules"], LAYOUT)
    assert bare["no_model_program_with_work_s"] is None
    assert loop_gaps.clock_over_span(pair, bare)["no_model_program_with_work_within_bounds"] is None
