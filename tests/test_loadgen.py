"""Loadgen determinism, SLO report sourcing, replay, and perf-delta tests.

The load-bearing property (ISSUE 9 / ROADMAP Open item 5): the same seed
must produce a byte-identical request schedule — prompts, tenants, arrival
offsets, cancel points — and the SLO report must derive every number from
registry snapshots / flight-recorder data, never from client stopwatches.
"""

import json
import os

import pytest

from prime_tpu.loadgen import (
    SCENARIOS,
    EngineTarget,
    Phase,
    Scenario,
    build_report,
    build_schedule,
    run_schedule,
    scenario_row,
    schedule_digest,
    schedule_from_flight,
    schedule_from_prompts,
    schedule_from_trace,
)
from prime_tpu.loadgen.perf_delta import delta_table, load_rounds
from prime_tpu.obs.metrics import Registry

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# ---- schedule determinism ----------------------------------------------------


@pytest.mark.parametrize("name", sorted(SCENARIOS))
def test_same_seed_byte_identical_schedule(name):
    a = build_schedule(SCENARIOS[name](seed=42))
    b = build_schedule(SCENARIOS[name](seed=42))
    assert a == b  # full dataclass equality: prompts, tenants, arrivals, cancels
    assert schedule_digest(a) == schedule_digest(b)


@pytest.mark.parametrize("name", sorted(SCENARIOS))
def test_different_seed_different_schedule(name):
    a = build_schedule(SCENARIOS[name](seed=1))
    b = build_schedule(SCENARIOS[name](seed=2))
    assert schedule_digest(a) != schedule_digest(b)


def test_schedule_sorted_and_indexed():
    schedule = build_schedule(SCENARIOS["smoke"](seed=0))
    arrivals = [r.arrival_s for r in schedule]
    assert arrivals == sorted(arrivals)
    assert sorted(r.index for r in schedule) == list(range(len(schedule)))


def test_shared_prefix_shared_within_tenant_only():
    scenario = Scenario(
        "t", 7,
        (Phase(kind="chat_burst", n=6, tenants=2, shared_prefix=16,
               prompt_tokens=24, max_new_tokens=4),),
    )
    schedule = build_schedule(scenario)
    by_tenant = {}
    for r in schedule:
        by_tenant.setdefault(r.tenant, []).append(r.prompt_ids[:16])
    assert len(by_tenant) == 2
    for prefixes in by_tenant.values():
        assert len({p for p in prefixes}) == 1  # identical within a tenant
    (p1,), (p2,) = ({p for p in v} for v in by_tenant.values())
    assert p1 != p2  # distinct across tenants


def test_spec_friendly_prompts_tile_a_cycle():
    """The spec_friendly scenario's tails tile one short token cycle per
    request (the repetitive shape n-gram drafts accept), deterministically
    from the seed; cycle_tokens=0 keeps the historical i.i.d. draw for
    every other kind."""
    schedule = build_schedule(SCENARIOS["spec_friendly"](seed=3))
    assert schedule, "spec_friendly produced no requests"
    phase = SCENARIOS["spec_friendly"](seed=3).phases[0]
    assert phase.cycle_tokens > 0
    for r in schedule:
        tail = r.prompt_ids[1:]  # [0] is the BOS stand-in
        cycle = tail[: phase.cycle_tokens]
        for i, tok in enumerate(tail):
            assert tok == cycle[i % len(cycle)]
    with pytest.raises(ValueError, match="cycle_tokens"):
        Phase(kind="spec_friendly", n=1, prompt_tokens=8, cycle_tokens=8)


def test_cancel_storm_pins_cancel_points():
    schedule = build_schedule(SCENARIOS["cancel_storm"](seed=5))
    cancels = [r for r in schedule if r.cancel_after_s is not None]
    assert cancels, "cancel storm produced no cancel points"
    for r in cancels:
        assert r.cancel_after_s > r.arrival_s


def test_mixed_tenants_pin_adapters():
    schedule = build_schedule(SCENARIOS["mixed_tenants"](seed=3))
    assert {r.adapter for r in schedule} == {"base", "adapter-a", "adapter-b"}


def test_vocab_is_part_of_the_determinism_key():
    scenario = SCENARIOS["chat_burst"](seed=9)
    assert schedule_digest(build_schedule(scenario, vocab=500)) != schedule_digest(
        build_schedule(scenario, vocab=600)
    )


def test_phase_validation():
    with pytest.raises(ValueError):
        Phase(kind="nope", n=1)
    with pytest.raises(ValueError):
        Phase(kind="chat_burst", n=0)
    with pytest.raises(ValueError):
        Phase(kind="chat_burst", n=1, shared_prefix=8, prompt_tokens=8)


def test_schedule_from_prompts_preserves_order_and_ids():
    prompts = [[1, 5, 9], [1, 7, 7, 7]]
    schedule = schedule_from_prompts("bench", prompts, 8)
    assert [list(r.prompt_ids) for r in schedule] == prompts
    assert all(r.arrival_s == 0.0 for r in schedule)
    assert [r.max_new_tokens for r in schedule] == [8, 8]


# ---- captured_at + report sourcing ------------------------------------------


def test_registry_snapshot_embeds_monotonic_captured_at():
    r = Registry()
    first = r.snapshot()
    second = r.snapshot()
    t1 = first["captured_at"]["series"][0]["value"]
    t2 = second["captured_at"]["series"][0]["value"]
    assert t2 >= t1
    # family-shaped: JSON-round-trips and walks like any other family
    snap = json.loads(json.dumps(first))
    assert "series" in snap["captured_at"]


def test_captured_at_name_is_reserved():
    r = Registry()
    with pytest.raises(ValueError):
        r.counter("captured_at")
    with pytest.raises(ValueError):
        r.gauge("captured_at")


def _snap(captured_at, tokens, admitted=4, hits=1, ttft_counts=None,
          loop=None, starved=None, spec_accepted=0.0, spec_windows=0,
          spec_drafts=0.0):
    """Hand-built registry snapshot: the report consumes plain dicts, so the
    arithmetic is testable without clocks."""
    ttft_counts = ttft_counts or [0, 0, 0]
    snap = {
        "serve_spec_accepted_tokens": {"type": "histogram", "help": "", "series": [{
            "labels": {}, "buckets": [1.0, 4.0], "counts": [0, 0, 0],
            "sum": float(spec_accepted), "count": int(spec_windows)}]},
        "serve_spec_draft_tokens_total": {"type": "counter", "help": "", "series": [
            {"labels": {}, "value": float(spec_drafts)}]},
        "captured_at": {"type": "gauge", "help": "", "series": [
            {"labels": {}, "value": captured_at}]},
        "serve_tokens_emitted_total": {"type": "counter", "help": "", "series": [
            {"labels": {}, "value": float(tokens)}]},
        "serve_requests_admitted_total": {"type": "counter", "help": "", "series": [
            {"labels": {}, "value": float(admitted)}]},
        "serve_requests_completed_total": {"type": "counter", "help": "", "series": [
            {"labels": {}, "value": float(admitted)}]},
        "serve_requests_cancelled_total": {"type": "counter", "help": "", "series": []},
        "serve_requests_failed_total": {"type": "counter", "help": "", "series": []},
        "serve_prefix_hits_total": {"type": "counter", "help": "", "series": [
            {"labels": {}, "value": float(hits)}]},
        # the phase clock's two families (a round from before the starved
        # clock has only the first)
        **{family: {"type": "counter", "help": "", "series": [
            {"labels": {"phase": phase}, "value": seconds} for phase, seconds in phases.items()]}
           for family, phases in (("serve_loop_seconds_total", loop),
                                  ("serve_loop_starved_seconds_total", starved))
           if phases is not None},
        "serve_ttft_seconds": {"type": "histogram", "help": "", "series": [{
            "labels": {}, "buckets": [0.1, 1.0], "counts": list(ttft_counts),
            "sum": 1.0, "count": sum(ttft_counts)}]},
    }
    return snap


class _FakeResult:
    """Duck-typed RunResult for pure-arithmetic report tests."""

    def __init__(self, before, after):
        from collections import Counter

        self.scenario = "fake"
        self.seed = 0
        self.digest = "d" * 64
        self.requests = 4
        self.outcomes = Counter({"completed": 4})
        self.client_tokens = 0
        self.before = before
        self.after = after
        self.flight = {}
        self.time_scale = 1.0


def test_report_numbers_come_from_snapshot_deltas_not_client_timers():
    before = {"engine": _snap(100.0, tokens=40, admitted=0, hits=0,
                              loop={"no_work": 9.0, "emit": 1.0}, starved={"no_work": 9.0})}
    after = {"engine": _snap(102.0, tokens=140, admitted=4, hits=2, ttft_counts=[3, 1, 0],
                             loop={"no_work": 9.4, "emit": 1.6, "sync_wait": 1.0},
                             starved={"no_work": 9.4, "emit": 0.4})}
    row = scenario_row(_FakeResult(before, after))
    assert row["duration_s"] == pytest.approx(2.0)
    assert row["tok_s"] == pytest.approx(50.0)  # (140-40) / (102-100)
    assert row["admitted"] == 4
    assert row["prefix_hit_ratio"] == pytest.approx(0.5)
    # of the loop's 1.6 s with work (2.0 less 0.4 of no_work), 0.4 starved in emit
    assert row["chip_starved_share"] == pytest.approx(25.0)
    # an engine from before the starved clock: absent, not zero
    old = {"engine": _snap(102.0, tokens=140, loop={"emit": 1.6})}
    assert scenario_row(_FakeResult(before, old))["chip_starved_share"] is None
    # p50 of [3 <= 0.1s, 1 <= 1.0s]: rank 2 of 4 inside the first bucket
    assert row["ttft_s"]["p50"] == pytest.approx(0.1 * 2 / 3, rel=1e-4)
    assert row["ttft_s"]["p95"] > row["ttft_s"]["p50"]


def test_report_merges_multiple_engine_components():
    before = {
        "replica0.engine": _snap(10.0, tokens=0),
        "replica1.engine": _snap(20.0, tokens=10),
    }
    after = {
        "replica0.engine": _snap(12.0, tokens=60),
        "replica1.engine": _snap(22.0, tokens=50),
    }
    row = scenario_row(_FakeResult(before, after))
    # 60 + 40 tokens over the (equal) 2 s windows
    assert row["tokens"] == 100
    assert row["tok_s"] == pytest.approx(50.0)


def test_report_spec_fields_are_registry_windowed():
    """spec_accepted_tokens / spec_accept_ratio come from the accepted-
    tokens histogram's sum delta over the proposed-draft counter delta —
    windowed like every other field, None when no verify window ran."""
    before = {"engine": _snap(10.0, tokens=0, spec_accepted=12.0,
                              spec_windows=6, spec_drafts=40.0)}
    after = {"engine": _snap(12.0, tokens=80, spec_accepted=42.0,
                             spec_windows=16, spec_drafts=80.0)}
    row = scenario_row(_FakeResult(before, after))
    assert row["spec_accepted_tokens"] == 30  # 42 - 12
    assert row["spec_accept_ratio"] == pytest.approx(30.0 / 40.0)
    # spec off: the counter never moves -> ratio is None, not 0.0
    quiet = scenario_row(_FakeResult(
        {"engine": _snap(1.0, tokens=0)}, {"engine": _snap(2.0, tokens=8)}
    ))
    assert quiet["spec_accept_ratio"] is None
    assert quiet["spec_accepted_tokens"] == 0


def test_report_field_set_is_stable():
    before = {"engine": _snap(1.0, tokens=0)}
    after = {"engine": _snap(2.0, tokens=8)}
    row_a = scenario_row(_FakeResult(before, after))
    row_b = scenario_row(_FakeResult(before, after))
    assert row_a == row_b
    expected = {
        "scenario", "seed", "schedule_digest", "requests", "outcomes",
        "client_tokens", "duration_s", "tokens", "tok_s", "admitted",
        "completed", "cancelled", "failed", "chip_starved_share",
        "prefix_hit_ratio", "prefix_hit_tokens", "prefix_spills",
        "prefix_reuploads", "wasted_decode_tokens", "ttft_s", "tpot_s",
        "queue_wait_s", "rejected_429",
    }
    assert expected <= set(row_a)
    report = build_report([_FakeResult(before, after)])
    assert report["slo_schema"] == 1
    assert report["headline"]["tok_s"] == pytest.approx(8.0)


# ---- end-to-end against a tiny in-process engine ----------------------------


@pytest.fixture(scope="module")
def tiny_engine_factory():
    import jax
    import jax.numpy as jnp

    from prime_tpu.models import get_config
    from prime_tpu.models.llama import init_params
    from prime_tpu.serve.engine import ContinuousBatchingEngine

    config = get_config("tiny-test")
    params = init_params(jax.random.PRNGKey(0), config, dtype=jnp.float32)

    def make(**kw):
        kw.setdefault("max_slots", 4)
        kw.setdefault("capacity", 128)
        kw.setdefault("chunk", 4)
        kw.setdefault("prefix_cache_mb", 1)
        return config, ContinuousBatchingEngine(params, config, pad_id=0, **kw)

    return make


def test_engine_run_produces_registry_sourced_report(tiny_engine_factory):
    config, engine = tiny_engine_factory()
    scenario = SCENARIOS["smoke"](seed=11)
    schedule = build_schedule(scenario, vocab=config.vocab_size)
    result = run_schedule(
        schedule, EngineTarget(engine), scenario="smoke", seed=11, time_scale=0.2,
    )
    assert result.digest == schedule_digest(schedule)
    report = build_report([result])
    row = report["scenarios"][0]
    assert report["headline"]["tok_s"] > 0
    assert row["tokens"] > 0
    assert row["admitted"] > 0
    assert row["duration_s"] and row["duration_s"] > 0
    assert sum(result.outcomes.values()) == len(schedule)
    # flight scrape captured the run (replay seed)
    recent = result.flight["recent"]
    assert len(recent) >= row["admitted"]


def test_engine_runs_same_seed_matching_field_sets(tiny_engine_factory):
    rows = []
    for _ in range(2):
        config, engine = tiny_engine_factory()
        schedule = build_schedule(SCENARIOS["chat_burst"](seed=21), vocab=config.vocab_size)
        result = run_schedule(
            schedule, EngineTarget(engine), scenario="chat_burst", seed=21,
            time_scale=0.0,
        )
        rows.append(scenario_row(result))
    assert set(rows[0]) == set(rows[1])
    assert rows[0]["schedule_digest"] == rows[1]["schedule_digest"]
    assert rows[0]["requests"] == rows[1]["requests"]


def test_queue_full_counts_as_rejected(tiny_engine_factory):
    config, engine = tiny_engine_factory(max_queue=1, max_slots=2)
    schedule = build_schedule(
        Scenario("storm", 1, (Phase(kind="rate_storm", n=12, prompt_tokens=16,
                                    max_new_tokens=4),)),
        vocab=config.vocab_size,
    )
    result = run_schedule(schedule, EngineTarget(engine), scenario="storm",
                          time_scale=0.0)
    # every request is accounted for exactly once; the oversubscribed wave
    # must trip the bounded queue at least once
    assert sum(result.outcomes.values()) == len(schedule)
    assert result.outcomes["rejected_429"] > 0
    assert scenario_row(result)["rejected_429"] == result.outcomes["rejected_429"]


# ---- replay ------------------------------------------------------------------


def test_replay_from_flight_fixture_reproduces_count_and_order():
    payload = {
        "inflight": [],
        "recent": [
            {"id": "3", "trace_id": None, "state": "done", "outcome": "completed",
             "start_unix_s": 1000.5, "duration_s": 0.4, "events": 3,
             "last_event": "completed", "prompt_tokens": 24, "max_new_tokens": 8},
            {"id": "1", "trace_id": "a" * 32, "state": "done", "outcome": "cancelled",
             "start_unix_s": 1000.0, "duration_s": 0.2, "events": 2,
             "last_event": "cancelled", "prompt_tokens": 16, "max_new_tokens": 32},
            {"id": "2", "trace_id": None, "state": "done", "outcome": "completed",
             "start_unix_s": 1000.25, "duration_s": 0.3, "events": 3,
             "last_event": "completed", "prompt_tokens": 48, "max_new_tokens": 8},
        ],
    }
    schedule = schedule_from_flight(payload, seed=0, vocab=500)
    assert len(schedule) == 3
    # ordering and offsets follow recorded submit times, not list order
    assert [r.arrival_s for r in schedule] == [0.0, 0.25, 0.5]
    assert [len(r.prompt_ids) for r in schedule] == [16, 48, 24]
    assert [r.max_new_tokens for r in schedule] == [32, 8, 8]
    # the cancelled timeline cancels at its recorded duration
    assert schedule[0].cancel_after_s == pytest.approx(0.2)
    assert schedule[1].cancel_after_s is None
    # replay is itself deterministic
    assert schedule_digest(schedule) == schedule_digest(
        schedule_from_flight(payload, seed=0, vocab=500)
    )
    assert schedule_digest(schedule) != schedule_digest(
        schedule_from_flight(payload, seed=1, vocab=500)
    )


def test_replay_from_engine_flight_roundtrip(tiny_engine_factory):
    config, engine = tiny_engine_factory()
    schedule = build_schedule(SCENARIOS["chat_burst"](seed=31), vocab=config.vocab_size)
    result = run_schedule(schedule, EngineTarget(engine), scenario="chat_burst",
                          time_scale=0.0)
    replayed = schedule_from_flight(result.flight, vocab=config.vocab_size)
    served = result.outcomes["completed"] + result.outcomes["cancelled"]
    assert len(replayed) == served
    # prompt sizes survive the roundtrip (admission meta), arrival order is
    # the engine's recorded submit order
    assert sorted(len(r.prompt_ids) for r in replayed) == sorted(
        len(r.prompt_ids) for r in schedule
    )[: len(replayed)]
    arrivals = [r.arrival_s for r in replayed]
    assert arrivals == sorted(arrivals)
    # a replayed schedule drives the engine again, end to end
    config, engine2 = tiny_engine_factory()
    result2 = run_schedule(replayed, EngineTarget(engine2), scenario="replay",
                           time_scale=0.0)
    assert sum(result2.outcomes.values()) == len(replayed)


def test_replay_from_trace_jsonl(tmp_path):
    path = tmp_path / "trace.jsonl"
    spans = [
        {"name": "serve.request", "trace_id": "t1", "span_id": "s1",
         "parent_id": None, "start_unix_s": 50.0, "start_s": 1.0,
         "duration_s": 0.5, "attrs": {"request": 1, "outcome": "completed",
                                      "tokens": 6}},
        {"name": "serve.prefill", "trace_id": "t1", "span_id": "s2",
         "parent_id": None, "start_unix_s": 50.01, "start_s": 1.01,
         "duration_s": 0.1, "attrs": {"request": 1, "prompt_len": 20}},
        {"name": "serve.request", "trace_id": "t2", "span_id": "s3",
         "parent_id": None, "start_unix_s": 50.2, "start_s": 1.2,
         "duration_s": 0.3, "attrs": {"request": 2, "outcome": "cancelled",
                                      "tokens": 2}},
        {"name": "unrelated.span", "trace_id": "t3", "span_id": "s4",
         "parent_id": None, "start_unix_s": 49.0, "start_s": 0.5,
         "duration_s": 0.1, "attrs": {}},
    ]
    path.write_text("\n".join(json.dumps(s) for s in spans) + "\n")
    schedule = schedule_from_trace(str(path), vocab=500)
    assert len(schedule) == 2
    assert [r.arrival_s for r in schedule] == [0.0, pytest.approx(0.2)]
    assert len(schedule[0].prompt_ids) == 20
    assert schedule[0].max_new_tokens == 6
    assert schedule[1].cancel_after_s == pytest.approx(0.5)


# ---- perf delta --------------------------------------------------------------


def test_perf_delta_parses_all_committed_rounds():
    # the committed rounds are the CPU loadgen records (r06 on); the
    # schema-1 labeling of legacy records is pinned on synthetic files below
    rounds = load_rounds(REPO_ROOT)
    assert len(rounds) >= 2
    table = delta_table(rounds)
    assert "r06" in table and "loadgen tok/s" in table


def test_perf_delta_unwraps_driver_records_and_labels_schemas(tmp_path):
    (tmp_path / "BENCH_r01.json").write_text(json.dumps({
        "n": 1, "cmd": "python bench.py", "rc": 1, "tail": "...",
        "parsed": {"metric": "decode_tokens_per_sec", "value": 0.0,
                   "unit": "tokens/s", "error": "backend unresponsive"},
    }))
    (tmp_path / "BENCH_r02.json").write_text(json.dumps({
        "n": 2, "cmd": "python bench.py", "rc": 124, "tail": "...",
        "parsed": None,
    }))
    (tmp_path / "BENCH_r03.json").write_text(json.dumps({
        "schema": 2, "metric": "decode_tokens_per_sec (x)", "value": 123.4,
        "unit": "tokens/s",
        "loadgen": {"slo_schema": 1,
                    "headline": {"tok_s": 99.0},
                    "scenarios": [{"scenario": "serve", "tok_s": 99.0,
                                   "ttft_s": {"p50": 0.01, "p95": 0.02}}]},
    }))
    rounds = load_rounds(str(tmp_path))
    assert [r.schema for r in rounds] == [1, 1, 2]
    assert rounds[1].error and "rc=124" in rounds[1].error
    table = delta_table(rounds)
    assert "123" in table
    assert "slo:serve ttft p50 ms" in table
    assert "(∅→live)" in table  # 0.0 → measured renders as revival, not +inf%


def test_perf_delta_min_rounds_message(tmp_path):
    assert "need at least 2" in delta_table(load_rounds(str(tmp_path)))


def test_perf_delta_unnumbered_files_sort_last(tmp_path):
    # a BENCH_*.json without an r<N> must never become r01's delta baseline
    (tmp_path / "BENCH_baseline.json").write_text(json.dumps(
        {"value": 99.0, "metric": "decode_tokens_per_sec"}))
    (tmp_path / "BENCH_r01.json").write_text(json.dumps(
        {"value": 0.0, "metric": "decode_tokens_per_sec"}))
    assert [r.label for r in load_rounds(str(tmp_path))] == [
        "r01", "BENCH_baseline"
    ]


def test_router_only_scrape_and_truncation_warn_instead_of_zero():
    result = _FakeResult({"target.router": _snap(1.0, tokens=0)},
                         {"target.router": _snap(2.0, tokens=0)})
    result.timed_out = True
    row = scenario_row(result)
    assert "no engine registries" in row["warning"]
    assert "truncated" in row["warning"]
    assert row["tok_s"] == 0.0 and row["duration_s"] is None
