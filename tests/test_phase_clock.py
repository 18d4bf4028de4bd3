"""The engine loop's phase clock (prime_tpu/obs/phase.py), on the CPU with the
tiny model: the phases are exclusive and exhaustive, program builds are
accounted through jax.monitoring by one listener set a process, the same
sites land in a jax.profiler capture as annotations on the loop thread's
line, and the names the benchmark finds programs by are the engine's own.
"""

import json
import re
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from prime_tpu.models import get_config
from prime_tpu.models.llama import init_params
from prime_tpu.obs import phase as phase_mod
from prime_tpu.obs.metrics import snapshot_captured_at
from prime_tpu.serve.engine import ContinuousBatchingEngine

CONFIG = get_config("tiny-test")
PARAMS = init_params(jax.random.PRNGKey(0), CONFIG, dtype=jnp.float32)
REPO = Path(__file__).resolve().parents[1]
PROMPT = [5, 9, 301, 42] * 5


@pytest.fixture(autouse=True)
def _default_env(monkeypatch):
    for knob in (
        "PRIME_SERVE_OVERLAP", "PRIME_SERVE_WARMUP", "PRIME_SERVE_MESH",
        "PRIME_SERVE_SPEC", "PRIME_SERVE_PROFILE", "PRIME_TRACE",
    ):
        monkeypatch.delenv(knob, raising=False)


def make_engine(**kw) -> ContinuousBatchingEngine:
    kw.setdefault("max_slots", 2)
    kw.setdefault("capacity", 64)
    kw.setdefault("chunk", 4)
    kw.setdefault("prefix_cache_mb", 8)
    return ContinuousBatchingEngine(PARAMS, CONFIG, **kw)


STARVED = "serve_loop_starved_seconds_total"
UNSEEN = "serve_loop_starved_unseen_seconds_total"


def loop_seconds(snapshot: dict, family: str = "serve_loop_seconds_total") -> dict[str, float]:
    return {s["labels"]["phase"]: s["value"] for s in snapshot.get(family, {}).get("series", [])}


def unseen_seconds(snapshot: dict) -> float:
    return sum(s["value"] for s in snapshot[UNSEEN]["series"])


def snapshot_after_flush(engine, timeout: float = 5.0) -> dict:
    """A registry snapshot taken right after the loop published its seconds
    (once a tick; every 0.2 s when idle), so the counter lags by microseconds
    and not by a tick."""
    total = engine.registry.get("serve_loop_seconds_total").total

    before, deadline = total(), time.monotonic() + timeout
    while total() == before and time.monotonic() < deadline:
        time.sleep(0.0005)
    return engine.registry.snapshot()


def settle(engine, timeout: float = 30.0) -> None:
    """Wait until the loop has nothing live or in flight, then a moment more.
    A sleep alone is not enough on a loaded machine: the loop thread can be
    kept from its trailing sync for longer than any fixed pause."""
    deadline = time.monotonic() + timeout
    while (engine._inflight or any(engine._active)) and time.monotonic() < deadline:
        time.sleep(0.01)
    time.sleep(0.3)


def delta(before: dict, after: dict, family: str = "serve_loop_seconds_total") -> dict[str, float]:
    a, b = loop_seconds(before, family), loop_seconds(after, family)
    return {phase: b[phase] - a.get(phase, 0.0) for phase in b}


def test_phases_are_exclusive_and_exhaustive_over_a_burst_and_an_idle_stretch():
    engine = make_engine()
    engine.start()
    try:
        s0 = snapshot_after_flush(engine)
        # a burst that keeps the queue full for a while: 2 slots, 10 requests
        reqs = [engine.submit(PROMPT, max_new_tokens=40) for _ in range(10)]
        next(iter(reqs[0].tokens(timeout=120)))  # the first request is live
        s1 = snapshot_after_flush(engine)
        still_queued_at_s2 = False
        s2 = s1
        while engine.queue_depth() > 0:
            s2 = snapshot_after_flush(engine)
            still_queued_at_s2 = engine.queue_depth() > 0
            if still_queued_at_s2:
                break
        for req in reqs:
            req.all_tokens(timeout=120)
        s3 = snapshot_after_flush(engine)
        time.sleep(0.7)  # the idle stretch
        s4 = snapshot_after_flush(engine)
    finally:
        engine.shutdown()

    snaps = [s0, s1, s2, s3, s4]
    # no phase ever goes down
    for before, after in zip(snaps, snaps[1:]):
        assert all(v >= 0.0 for v in delta(before, after).values())
    # exhaustive: the phases sum to the wall time between the snapshots
    for before, after in ((s0, s3), (s3, s4), (s0, s4)):
        wall = snapshot_captured_at(after) - snapshot_captured_at(before)
        assert sum(delta(before, after).values()) == pytest.approx(wall, rel=0.02)
    # no_work grows only while nothing is queued or live
    if still_queued_at_s2:
        assert delta(s1, s2).get("no_work", 0.0) == 0.0
    idle = delta(s3, s4)
    idle_wall = snapshot_captured_at(s4) - snapshot_captured_at(s3)
    assert idle["no_work"] == pytest.approx(idle_wall, rel=0.05)
    # with work the loop blocks on the device in both of its sync phases
    busy = delta(s0, s3)
    assert busy["sync_wait"] > 0 and busy["prefill_sync"] > 0
    assert busy["dispatch"] > 0 and busy["prefill"] > 0 and busy["emit"] > 0
    assert set(busy) <= set(phase_mod.PHASES)
    # the starved clock charges a second time, so never more than the phase
    # holds; with nobody to serve the chip has nothing to run all the while
    for before, after in zip(snaps, snaps[1:]):
        held = delta(before, after)
        for phase, seconds in delta(before, after, STARVED).items():
            assert -1e-9 <= seconds <= held[phase] + 1e-9, phase
    assert delta(s3, s4, STARVED)["no_work"] == pytest.approx(idle["no_work"], abs=1e-5)
    assert set(loop_seconds(s4, STARVED)) <= set(phase_mod.PHASES)


def test_first_use_of_a_shape_charges_program_build_once_listened():
    """A program built on the loop thread moves its seconds into
    program_build and counts under the phase that triggered it; two engines
    in one process share one set of jax.monitoring listeners."""
    from jax._src import monitoring  # the registry of listeners, to count ours

    def ours() -> int:
        return sum(cb is phase_mod._on_duration
                   for cb in monitoring.get_event_duration_listeners())

    first, second = make_engine(), make_engine()
    assert ours() == 1
    assert sum(cb is phase_mod._on_event for cb in monitoring.get_event_listeners()) == 1

    req = first.submit(PROMPT, max_new_tokens=6)
    for _ in range(200):
        first.tick()
        if req.done:
            break
    assert req.done
    compiles = first.registry.get("serve_compiles_total")
    assert compiles.value(phase="prefill") >= 2  # chunk prefill, finalize
    assert compiles.value(phase="dispatch") >= 1  # decode
    built = loop_seconds(first.registry.snapshot())["program_build"]
    totals = first._clock.build_totals()
    assert totals["total"] >= 3 and totals["loads"] == 0
    # trace and lowering seconds ride along, so the phase holds more than
    # the backend compiles alone
    assert built >= totals["seconds"] > 0
    names = [b["name"] for b in first.stats()["program_builds"]]
    assert any("decode" in n for n in names) and any("chunk_prefill" in n for n in names)
    assert all(b["kind"] == "compile" for b in first.stats()["program_builds"])
    # the other engine's thread of accounting saw none of it
    assert second._clock.build_totals()["total"] == 0

    # a new prompt length is a new chunk-prefill shape: built again, charged again
    before = compiles.value(phase="prefill")
    req = first.submit(PROMPT + PROMPT + [7], max_new_tokens=2)
    for _ in range(200):
        first.tick()
        if req.done:
            break
    assert compiles.value(phase="prefill") > before
    assert loop_seconds(first.registry.snapshot())["program_build"] > built


def test_two_engines_ticked_in_turn_on_one_thread_keep_their_builds_apart():
    """Synchronous owners tick several engines on one thread (disaggregated
    prefill and decode): a build is charged to the engine whose tick
    triggered it, whichever attached last, and to nobody twice."""
    first, second = make_engine(), make_engine(chunk=2)  # another decode program
    reqs = [first.submit(PROMPT, max_new_tokens=6), second.submit(PROMPT, max_new_tokens=6)]
    t0 = time.monotonic()
    for _ in range(200):
        first.tick()
        second.tick()
        if all(r.done for r in reqs):
            break
    wall = time.monotonic() - t0
    assert all(r.done for r in reqs)
    for engine in (first, second):
        totals = engine._clock.build_totals()
        assert totals["total"] >= 3, totals  # chunk prefill, finalize, decode: its own
        names = {b["name"] for b in engine.stats()["program_builds"]}
        assert any("decode" in n for n in names)
        # an engine's phases never hold more than the thread's wall time
        assert sum(loop_seconds(engine.registry.snapshot()).values()) <= wall * 1.001
    assert second._clock.build_totals()["total"] + first._clock.build_totals()["total"] == sum(
        e.registry.get("serve_compiles_total").total() for e in (first, second))


def test_a_build_nested_in_an_outer_trace_is_charged_once():
    """An eager op inside a function being traced compiles while the outer
    trace is timed: the outer duration holds it, so only that is carved."""
    engine = make_engine()
    clock = engine._clock
    clock.attach()
    clock.flush()

    @jax.jit
    def outer(x):
        with jax.ensure_compile_time_eval():  # eager: built inside the outer trace
            table = jnp.cumsum(jnp.arange(7919) % 13)
        return x + table[:3]

    t0 = time.monotonic()
    with clock.enter("dispatch"):
        outer(jnp.ones(3)).block_until_ready()
    wall = time.monotonic() - t0
    flushed = clock.flush()
    assert flushed["program_build"] > 0
    assert flushed["program_build"] <= wall
    assert sum(flushed.values()) == pytest.approx(wall, rel=0.05, abs=2e-3)


def test_capture_holds_the_phases_on_the_loop_threads_line(tmp_path):
    """Under jax.profiler the sites' annotations land on ONE line of plane
    /host:CPU, readable by the benchmark's own reader, with their attributes
    as stats, and their durations agree with the phase counters."""
    from jax.profiler import ProfileData

    from benchmark.harness import xplane

    engine = make_engine()  # with the prefix cache: a repeated prompt hits
    engine.start()
    try:
        # build every program first: a build's seconds leave the phase's
        # counter for program_build but stay inside the annotation
        for _ in range(3):
            for req in [engine.submit(PROMPT, max_new_tokens=12) for _ in range(3)]:
                req.all_tokens(timeout=120)
        # a request is whole before the loop has synced the chunk it left in
        # flight: let that trailing sync land before the counter is read, and
        # again before the capture stops, or it falls between `before` and the
        # capture and the counter holds a sync that no annotation shows
        settle(engine)
        before = snapshot_after_flush(engine)
        jax.profiler.start_trace(str(tmp_path))
        try:
            reqs = [engine.submit(PROMPT, max_new_tokens=12) for _ in range(3)]
            for req in reqs:
                req.all_tokens(timeout=120)
            settle(engine)
        finally:
            jax.profiler.stop_trace()
        after = snapshot_after_flush(engine)
    finally:
        engine.shutdown()

    events = xplane.read_events(tmp_path)
    ours = [e for e in events if e["name"].startswith("serve.")]
    assert {e["plane"] for e in ours} == {"/host:CPU"}
    names = {e["name"] for e in ours}
    assert {"serve.dispatch", "serve.sync", "serve.prefill", "serve.assemble",
            "serve.no_work", "serve.emit", "serve.admit", "serve.prefill_sync",
            "serve.prefix_seed", "serve.prefix_store"} <= names

    # one line holds them all (the loop thread's), and seq is a stat
    lines = set()
    seqs = []
    idle_bits, sync_bits = [], []
    for path in tmp_path.rglob("*.xplane.pb"):
        for plane in ProfileData.from_file(str(path)).planes:
            for index, line in enumerate(plane.lines):
                for event in line.events:
                    if event.name.startswith("serve."):
                        lines.add((plane.name, index))
                    if event.name == "serve.dispatch":
                        seqs.append(dict(event.stats)["seq"])
                    if event.name == "serve.no_work":
                        idle_bits.append(dict(event.stats).get("starved"))
                    if event.name == "serve.sync":
                        sync_bits.append(dict(event.stats).get("starved"))
    assert len(lines) == 1
    assert len(seqs) >= 3 and sorted(seqs) == list(range(min(seqs), max(seqs) + 1))
    # the starved bit rides the annotation: the loop idles only once its last
    # chunk is drained, and a sync in the tick of its dispatch is opened with
    # that chunk just enqueued
    assert idle_bits and all(bit == 1 for bit in idle_bits)
    assert None in sync_bits

    moved = delta(before, after)
    assert moved.get("program_build", 0.0) == 0.0
    for phase, annotations in (("dispatch", ("serve.dispatch",)), ("sync_wait", ("serve.sync",)),
                               ("prefill", ("serve.prefill",)),
                               ("prefix_seed", ("serve.prefix_seed",))):
        seconds = sum(e["dur_ns"] for e in ours if e["name"] in annotations) / 1e9
        assert seconds == pytest.approx(moved[phase], rel=0.10), phase


@pytest.mark.parametrize("overlap", [True, False], ids=["overlap", "sync"])
def test_a_hit_stores_its_pages_before_it_waits_for_the_first_token(overlap):
    """A prefix hit is admitted as a wave of one: its new pages are stored
    while the chip is still busy with the prefill, and only then does the
    loop block for the first token. (Until PR 28 a hit had an admission path
    of its own that blocked first and stored with the chip idle.)"""
    engine = make_engine(overlap=overlap)
    shared = PROMPT + [7] * 12  # two cached blocks
    cold = engine.submit(shared, max_new_tokens=2)
    while not cold.done:
        engine.tick()
    phases: list[str] = []
    switch = engine._clock.switch

    def recording(phase: str) -> None:
        phases.append(phase)
        switch(phase)

    engine._clock.switch = recording
    hit = engine.submit(shared + [11] * 17, max_new_tokens=2)  # one block more to store
    while not hit.done:
        engine.tick()
    assert engine.prefix_hits == 1
    assert phases.count("prefix_store") == 1 and phases.count("prefill_sync") == 1
    assert (phases.index("prefix_seed") < phases.index("prefill")
            < phases.index("prefix_store") < phases.index("prefill_sync"))


class NeverDoneBeforeTheLoopWaits:
    """A model program's output on a chip slower than the host: not ready
    until the loop has blocked on the device once since it was enqueued (the
    tiny model on a loaded CPU finishes whenever it likes)."""

    def __init__(self, output, clock) -> None:
        self.output, self.clock, self.waited_for = output, clock, False

    def is_ready(self) -> bool:
        self.waited_for = self.waited_for or self.clock.phase in phase_mod.BLOCKED_ON_DEVICE
        return self.waited_for and self.output.is_ready()


def run_bursts(overlap: bool) -> tuple[dict, dict]:
    """Two bursts on a synchronously ticked engine; the snapshots around the
    second, whose programs the first one built."""
    engine = make_engine(overlap=overlap)
    clock, enqueued = engine._clock, engine._clock.enqueued
    clock.enqueued = lambda output: enqueued(NeverDoneBeforeTheLoopWaits(output, clock))
    snaps = []
    for token in (7, 11):
        reqs = [engine.submit([token] + PROMPT[1:], max_new_tokens=24) for _ in range(4)]
        for _ in range(400):
            engine.tick()
            if all(r.done for r in reqs) and not engine._inflight:
                break
        assert all(r.done for r in reqs)
        snaps.append(engine.registry.snapshot())
    return snaps[0], snaps[1]


def test_serial_loop_starves_the_chip_through_its_host_phases():
    """PRIME_SERVE_OVERLAP=0: the loop drains the queue before it does anything
    else, so the chip has no model program all through emit, stats and kv_jobs
    (which the two hand counters said as stall == window), and through admit
    but for the lines between a prefill's enqueue and the wait for it. The
    store is entered with the prefill just enqueued, and not starved at all."""
    before, after = run_bursts(overlap=False)
    held, starved = delta(before, after), delta(before, after, STARVED)
    assert delta(before, after).get("program_build", 0.0) == 0.0
    for phase in ("emit", "stats", "kv_jobs"):
        assert held[phase] > 0
        assert starved[phase] == pytest.approx(held[phase], abs=1e-5), phase
    assert 0.5 * held["admit"] < starved["admit"] < held["admit"]
    assert held["prefix_store"] > 0 and starved.get("prefix_store", 0.0) == 0.0
    # blocked on the array it looks at: the reads' exits leave nothing unseen
    assert held["sync_wait"] > 0 and starved.get("sync_wait", 0.0) == 0.0
    assert unseen_seconds(after) - unseen_seconds(before) < 0.05 * sum(held.values())


def test_pipelined_loop_hides_its_emit_behind_the_chunk_in_flight():
    """The same bursts under the one-chunk-deep pipeline: a chunk's tokens are
    emitted while the next chunk computes, so emit is starved strictly less
    than whole (and a smaller share of it than in the serial loop, 1.0)."""
    before, after = run_bursts(overlap=True)
    held, starved = delta(before, after), delta(before, after, STARVED)
    assert held["emit"] > 0
    assert starved.get("emit", 0.0) < held["emit"]
    for phase, seconds in starved.items():
        assert seconds <= held[phase] + 1e-9, phase


class Output:
    """Stands in for a model program's output: the test says when it is there."""

    def __init__(self, ready: bool = False) -> None:
        self.ready = ready

    def is_ready(self) -> bool:
        return self.ready


class Ticker:
    """The clock's time.monotonic(), moved by hand."""

    def __init__(self) -> None:
        self.now = 1000.0

    def monotonic(self) -> float:
        return self.now


@pytest.fixture
def hand_clock(monkeypatch):
    """A phase clock on a registry of its own, on a hand-moved time: `spend`
    enters a phase, lets seconds pass, and leaves it for `other`."""
    from prime_tpu.obs.metrics import Registry

    ticker = Ticker()
    monkeypatch.setattr(phase_mod, "time", ticker)
    registry = Registry()
    clock = phase_mod.PhaseClock(registry)
    clock.attach()

    def spend(phase: str, seconds: float) -> None:
        clock.switch(phase)
        ticker.now += seconds
        clock.switch("other")

    def read() -> tuple[dict, dict, float]:
        clock.flush()
        snapshot = registry.snapshot()
        return loop_seconds(snapshot), loop_seconds(snapshot, STARVED), unseen_seconds(snapshot)

    return clock, ticker, spend, read


def test_starved_seconds_stay_within_the_phase_and_wait_for_a_ready_output(hand_clock):
    clock, ticker, spend, read = hand_clock
    spend("emit", 1.0)  # nothing announced yet: nothing to be starved of
    chunk = Output()
    clock.switch("dispatch")
    clock.enqueued(chunk)
    spend("emit", 2.0)  # the chunk is running
    held, starved, unseen = read()
    assert held["emit"] == pytest.approx(3.0) and starved == {} and unseen == 0.0
    clock.switch("sync_wait")
    ticker.now += 0.5
    chunk.ready = True  # the read comes back when the chip finishes
    clock.switch("other")
    spend("emit", 4.0)
    spend("admit", 1.5)
    held, starved, unseen = read()
    assert starved == {"emit": pytest.approx(4.0), "admit": pytest.approx(1.5)}
    assert all(starved[phase] <= held[phase] for phase in starved) and unseen == 0.0


def test_enqueued_ends_a_starved_stretch_inside_the_phase(hand_clock):
    clock, ticker, spend, read = hand_clock
    clock.enqueued(Output(ready=True))
    spend("admit", 1.0)  # the look at its entry finds the output there
    clock.switch("prefill")
    ticker.now += 0.25  # planning and uploads, the chip still idle
    clock.enqueued(Output())
    assert clock.starved is False
    ticker.now += 0.75  # the rest of the phase, with the prefill running
    clock.switch("other")
    held, starved, _ = read()
    assert held["prefill"] == pytest.approx(1.0)
    assert starved == {"admit": pytest.approx(1.0), "prefill": pytest.approx(0.25)}


def test_a_ready_after_not_ready_look_is_unseen_but_at_a_blocking_reads_exit(hand_clock):
    clock, ticker, spend, read = hand_clock
    chunk = Output()
    clock.enqueued(chunk)
    clock.switch("emit")  # a look: still running
    ticker.now += 3.0
    chunk.ready = True  # finished somewhere inside those three seconds
    clock.switch("admit")
    ticker.now += 1.0
    clock.switch("other")
    held, starved, unseen = read()
    assert unseen == pytest.approx(3.0)
    assert starved == {"admit": pytest.approx(1.0)} and held["emit"] == pytest.approx(3.0)
    for blocked in phase_mod.BLOCKED_ON_DEVICE:
        chunk = Output()
        clock.enqueued(chunk)
        clock.switch(blocked)
        ticker.now += 2.0
        chunk.ready = True
        clock.switch("emit")
        ticker.now += 0.5
        clock.switch("other")
    _, starved, unseen = read()
    assert unseen == pytest.approx(3.0)  # nothing added
    assert starved["emit"] == pytest.approx(1.0)
    # an enqueue looks at its predecessor too: it ran out before its successor came
    chunk = Output()
    clock.enqueued(chunk)
    ticker.now += 0.5
    chunk.ready = True
    clock.enqueued(Output())
    assert read()[2] == pytest.approx(3.5)


def test_a_build_in_a_starved_phase_moves_its_starved_seconds_too(hand_clock):
    clock, ticker, spend, read = hand_clock
    clock.enqueued(Output(ready=True))
    spend("other", 0.0)  # the look
    clock.switch("prefix_store")
    ticker.now += 5.0
    clock._carve(2.0)  # a program built on first use, two of the five seconds
    clock._carve(0.5, "gc")
    clock.switch("other")
    held, starved, _ = read()
    assert held["prefix_store"] == pytest.approx(2.5) == starved["prefix_store"]
    assert held["program_build"] == pytest.approx(2.0) == starved["program_build"]
    assert held["gc"] == pytest.approx(0.5) == starved["gc"]
    # with the chip busy the build is carved from the phase alone
    clock.switch("prefill")
    clock.enqueued(Output())
    ticker.now += 3.0
    clock._carve(1.0)
    clock.switch("other")
    held, starved, _ = read()
    assert held["program_build"] == pytest.approx(3.0)
    assert starved["program_build"] == pytest.approx(2.0) and "prefill" not in starved
    # a build is long beside a chunk, so its end is a look: the chip ran out
    # somewhere inside this one, and the phase's rest is starved
    chunk = Output()
    clock.switch("prefix_store")
    clock.enqueued(chunk)
    ticker.now += 2.0
    chunk.ready = True
    clock._carve(2.0)
    assert clock.starved
    ticker.now += 0.5
    clock.switch("other")
    held, starved, unseen = read()
    assert starved["prefix_store"] == pytest.approx(3.0) and unseen == pytest.approx(2.0)
    assert starved["program_build"] == pytest.approx(2.0)


def test_one_flush_publishes_both_families_under_one_hold_of_the_lock(hand_clock):
    import threading

    clock, ticker, spend, _ = hand_clock
    registry = clock._registry
    clock.enqueued(Output(ready=True))
    spend("other", 0.0)
    spend("emit", 1.0)
    assert loop_seconds(registry.snapshot()) == {} == loop_seconds(registry.snapshot(), STARVED)
    seen: list[dict] = []
    reader = threading.Thread(target=lambda: seen.append(registry.snapshot()))
    inc = clock._m_loop_s.inc

    def inc_while_a_reader_waits(*args, **kwargs):
        if not reader.ident:
            reader.start()
            reader.join(timeout=0.2)
            assert reader.is_alive() and not seen  # held out until the flush is whole
        inc(*args, **kwargs)

    clock._m_loop_s.inc = inc_while_a_reader_waits
    clock.flush()
    reader.join(timeout=5.0)
    assert not reader.is_alive()
    assert loop_seconds(seen[0])["emit"] == pytest.approx(1.0)
    assert loop_seconds(seen[0], STARVED)["emit"] == pytest.approx(1.0)
    assert clock.totals() == {"loop_s": {"emit": 1.0}, "loop_starved_s": {"emit": 1.0},
                              "loop_starved_unseen_s": 0.0}


def test_a_collection_on_the_loops_thread_is_the_gc_phase():
    """The gc.callbacks hook is registered once a process and carves a
    collection's seconds out of the phase it struck."""
    import gc

    first, second = make_engine(), make_engine()
    assert sum(cb is phase_mod._on_gc for cb in gc.callbacks) == 1
    clock = first._clock
    clock.attach()
    junk = [[i] for i in range(200_000)]  # something for a full collection to walk
    clock.flush()
    t0 = time.monotonic()
    with clock.enter("emit"):
        gc.collect()
    wall = time.monotonic() - t0
    flushed = clock.flush()
    assert 0 < flushed["gc"] <= wall and junk
    assert flushed["gc"] + flushed["emit"] == pytest.approx(wall, rel=0.05, abs=2e-3)
    assert "gc" not in loop_seconds(second.registry.snapshot())


def test_programs_json_names_are_the_engines_own():
    """benchmark/layer_metrics/programs.json finds programs in a trace by the
    names jit gives them: the __name__ of each function the engine jits. A
    rename has to fail here, not on the chip."""
    layout = json.loads((REPO / "benchmark/layer_metrics/programs.json").read_text())
    from prime_tpu.serve import kv_pool

    engine = make_engine()
    built = {
        "decode": [engine._make_decode()],
        "chunk_prefill": [engine._make_chunk_prefill()],
        "finalize": [engine._make_finalize_batch()],
        "assemble": [engine._make_assemble_row(), kv_pool.gather_pages],
        "store": [kv_pool._store_pages],
    }
    trace_name = {"tpu": "jit_{}(", "cpu": "PjitFunction({})"}
    for platform, template in trace_name.items():
        programs = layout[platform]["programs"]
        assert set(programs) == set(built)
        for role, fns in built.items():
            for fn in fns:
                name = template.format(fn.__wrapped__.__name__)
                assert re.search(programs[role], name), (platform, role, name)
                others = [r for r in programs if r != role and re.search(programs[r], name)]
                assert not others, (platform, name, others)


def test_programs_json_finds_the_programs_of_an_engine_whose_slots_hold_state():
    """The sambay block's engine (ring, row and recurrent state in a slot) jits
    the same three functions under the same names, so the trace readers find
    its decode, chunk prefill and finalize without an entry of their own."""
    import jax
    import jax.numpy as jnp

    from prime_tpu.models import get_config
    from prime_tpu.models.llama import init_params

    layout = json.loads((REPO / "benchmark/layer_metrics/programs.json").read_text())
    config = get_config("tiny-phi4flash")
    engine = ContinuousBatchingEngine(
        init_params(jax.random.PRNGKey(0), config, dtype=jnp.float32), config,
        max_slots=2, capacity=64, chunk=4,
    )
    assert engine.prefix_cache is None and engine._assemble_fn is None
    built = {
        "decode": engine._make_decode(),
        "chunk_prefill": engine._make_chunk_prefill(),
        "finalize": engine._make_finalize_batch(),
    }
    for platform, template in {"tpu": "jit_{}(", "cpu": "PjitFunction({})"}.items():
        programs = layout[platform]["programs"]
        for role, fn in built.items():
            name = template.format(fn.__wrapped__.__name__)
            assert [r for r in programs if re.search(programs[r], name)] == [role], (platform, name)
