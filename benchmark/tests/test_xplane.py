"""The trace reducer on a small synthetic trace kept beside this file."""
import json
from pathlib import Path

import pytest

from benchmark.harness import xplane

EVENTS = json.loads((Path(__file__).parent / "small_trace.json").read_text())
LAYOUT = {"plane_prefix": "/device:TPU:", "ops_line": "^XLA Ops$", "modules_line": "^XLA Modules$",
          "collectives": "all-reduce", "programs": {"decode": "decode", "chunk_prefill": "chunk"}}


def test_union_merges_overlaps():
    assert xplane.union_s([(0, 10), (5, 20), (30, 40)]) == pytest.approx(30e-9)
    assert xplane.union_s([]) == 0.0


def test_reduce_busy_idle_and_programs():
    out = xplane.reduce(EVENTS, LAYOUT)
    assert out["devices"] == 2
    first = out["first_device"]
    assert first["busy_s"] == pytest.approx(3.0e-3)      # 1.5 + 0.5 + 1.0 ms
    assert first["window_s"] == pytest.approx(10.0e-3)
    assert first["collective_s"] == pytest.approx(0.5e-3)
    assert out["busy_s"] == pytest.approx((3.0e-3 + 6.0e-3) / 2)
    assert out["programs"]["decode"] == {"seconds": pytest.approx(2.5e-3), "events": 2}
    assert out["programs"]["chunk_prefill"]["events"] == 1
    assert out["device_ops"][0][0] == "fusion.1"
    assert out["idle_gaps"][0] == ["unattributed", pytest.approx(5.5e-3)]


def test_no_device_plane_is_none():
    host_only = [e for e in EVENTS if e["plane"].startswith("/host")]
    assert xplane.reduce(host_only, LAYOUT) is None
