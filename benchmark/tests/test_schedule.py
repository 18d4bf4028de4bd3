"""Same seed -> byte-identical schedule and digest; another seed -> other
prompts on the same schedule."""
import json

import pytest

from benchmark.harness import schedule as sched
from benchmark.harness.manifest import load_json

MIXES = ["chat", "prefix-sessions"]


@pytest.mark.parametrize("mix", MIXES)
def test_same_seed_same_bytes(mix):
    traffic = load_json("traffic", mix)
    a = sched.materialise(traffic, 2**31 + 5, 20.0, 151936)
    b = sched.materialise(traffic, 2**31 + 5, 20.0, 151936)
    assert json.dumps(a, sort_keys=True) == json.dumps(b, sort_keys=True)
    assert sched.digest(a) == sched.digest(b)
    assert sched.digest(a) != sched.digest(sched.materialise(traffic, 7, 20.0, 151936))


def _shape(schedule):
    return [(s["start_s"], [len(m["ids"]) for t in s["turns"] for m in t["new_messages"]],
             [(t["max_tokens"], t["think_s"]) for t in s["turns"]]) for s in schedule["sessions"]]


@pytest.mark.parametrize("mix", MIXES)
def test_every_seed_sends_other_prompts_on_the_same_schedule(mix):
    traffic = load_json("traffic", mix)
    a = sched.materialise(traffic, 1, 20.0, 151936)
    b = sched.materialise(traffic, 2, 20.0, 151936)
    assert _shape(a) == _shape(b)
    last = a["sessions"][-1]["turns"][-1]["new_messages"][-1]["ids"]
    assert last != b["sessions"][-1]["turns"][-1]["new_messages"][-1]["ids"]
    assert max(s["start_s"] for s in a["sessions"]) < traffic["ramp_s"] + 20.0


def test_chat_lengths_fit_a_slot():
    traffic = load_json("traffic", "chat")
    for s in sched.materialise(traffic, 3, 51.0, 151936)["sessions"]:
        turn = s["turns"][0]
        n = len(turn["new_messages"][0]["ids"])
        assert traffic["prompt"]["min"] <= n <= traffic["prompt"]["max"]
        assert n + turn["max_tokens"] <= traffic["max_total"]
        assert min(turn["new_messages"][0]["ids"]) >= 1000


def test_sessions_share_their_tenants_system_prompt():
    traffic = load_json("traffic", "prefix-sessions")
    s = sched.materialise(traffic, 3, 30.0, 151936)
    systems = {tuple(p["turns"][0]["new_messages"][0]["ids"]) for p in s["primer"]}
    assert len(systems) == traffic["tenants"]
    for session in s["sessions"]:
        assert tuple(session["turns"][0]["new_messages"][0]["ids"]) in systems
        assert len(session["turns"]) == traffic["turns"]
        total = sum(len(m["ids"]) + 1 for t in session["turns"] for m in t["new_messages"])
        total += sum(t["max_tokens"] + 1 for t in session["turns"])
        assert total < 2048
