"""Continuous-batching engine tests (CPU, tiny model).

The load-bearing property: a request decoded by the slot-based engine —
whatever else is in flight, whenever it was admitted — produces exactly the
tokens the one-shot sampler produces for the same prompt under greedy
decoding. Everything else (slot reuse, mid-flight admission, streaming
order) is scaffolding on top of that invariant.
"""

import json
import time

import jax
import jax.numpy as jnp
import pytest

from prime_tpu.models import get_config
from prime_tpu.models.llama import init_params
from prime_tpu.models.sampler import generate
from prime_tpu.serve.engine import ContinuousBatchingEngine, EngineRequest, bucket_for


CONFIG = get_config("tiny-test")
PARAMS = init_params(jax.random.PRNGKey(0), CONFIG, dtype=jnp.float32)


@pytest.fixture(autouse=True)
def _default_pipeline_env(monkeypatch):
    """Pin the engine's env-driven defaults: an ambient PRIME_SERVE_OVERLAP=0
    (someone debugging with the escape hatch) or PRIME_SERVE_WARMUP=1 must
    not silently flip every engine test onto the other code path."""
    monkeypatch.delenv("PRIME_SERVE_OVERLAP", raising=False)
    monkeypatch.delenv("PRIME_SERVE_WARMUP", raising=False)
    monkeypatch.delenv("PRIME_SERVE_MESH", raising=False)
    monkeypatch.delenv("PRIME_SERVE_SPEC", raising=False)
    monkeypatch.delenv("PRIME_SERVE_DRAFT_LEN", raising=False)
    monkeypatch.delenv("PRIME_SERVE_PREFIX_CACHE_MB", raising=False)
    monkeypatch.delenv("PRIME_SERVE_PREFIX_CACHE_HOST_MB", raising=False)


def reference_tokens(prompt_ids: list[int], n: int) -> list[int]:
    """One-shot greedy generation for a single prompt via the sampler."""
    prompts = jnp.asarray([prompt_ids], dtype=jnp.int32)
    lengths = jnp.asarray([len(prompt_ids)], dtype=jnp.int32)
    result = generate(
        PARAMS, prompts, lengths, CONFIG, jax.random.PRNGKey(7),
        max_new_tokens=n, temperature=0.0,
    )
    return result.tokens[0].tolist()


def make_engine(**kw) -> ContinuousBatchingEngine:
    kw.setdefault("max_slots", 4)
    kw.setdefault("capacity", 128)
    kw.setdefault("chunk", 4)
    kw.setdefault("prefix_cache_mb", 0)  # prefix tests opt in explicitly
    return ContinuousBatchingEngine(PARAMS, CONFIG, **kw)


def drain(engine, *requests, max_ticks=200):
    for _ in range(max_ticks):
        engine.tick()
        if all(r.done for r in requests):
            return
    raise AssertionError("requests did not finish")


def test_bucket_for():
    assert bucket_for(1, 2048) == 16
    assert bucket_for(16, 2048) == 16
    assert bucket_for(17, 2048) == 32
    assert bucket_for(100, 2048) == 128
    assert bucket_for(100, 100) == 100
    with pytest.raises(ValueError):
        bucket_for(300, 128)


def test_single_request_matches_one_shot_sampler():
    prompt = [5, 9, 301, 42, 77]
    engine = make_engine()
    req = engine.submit(prompt, max_new_tokens=12)
    drain(engine, req)
    assert req.all_tokens(timeout=1) == reference_tokens(prompt, 12)


def test_concurrent_requests_each_match_reference():
    prompts = [[3, 1, 4, 1, 5], [2, 7, 18], [161, 80, 33, 98, 226, 50], [101]]
    engine = make_engine()
    reqs = [engine.submit(p, max_new_tokens=10) for p in prompts]
    drain(engine, *reqs)
    for p, r in zip(prompts, reqs):
        assert r.all_tokens(timeout=1) == reference_tokens(p, 10)


def test_batched_admission_mixed_plans_match_reference():
    """A burst whose prompts span different row buckets: the same-plan
    groups batch, the odd one goes alone, and every request still emits the
    one-shot sampler's exact greedy tokens."""
    short = [[3, 1, 4], [2, 7, 18, 9], [11, 12]]            # one bucket
    long = [list(range(2, 40))]                              # bigger bucket
    engine = make_engine()
    reqs = [engine.submit(p, max_new_tokens=8) for p in short + long]
    drain(engine, *reqs)
    for p, r in zip(short + long, reqs):
        assert r.all_tokens(timeout=1) == reference_tokens(p, 8)


def test_batched_admission_with_prefix_hit_in_burst():
    """A burst containing a prompt that prefix-hits the cache routes that
    request through the seeded single path while the rest batch; tokens
    still match the reference for all of them."""
    base = list(range(5, 37))  # 32 tokens: above min_prefix, bucket-aligned
    engine = make_engine(prefix_cache_mb=64)
    warm = engine.submit(base + [7], max_new_tokens=4)
    drain(engine, warm)
    # burst: one prefix-hitting prompt + two cold ones
    prompts = [base + [9, 3], [41, 42, 43], [91, 92, 93, 94]]
    reqs = [engine.submit(p, max_new_tokens=8) for p in prompts]
    drain(engine, *reqs)
    assert engine.prefix_hits >= 1
    for p, r in zip(prompts, reqs):
        assert r.all_tokens(timeout=1) == reference_tokens(p, 8)


def test_engine_stats_counters():
    """stats() tracks admissions (batched + single), completions, tokens,
    and the batched-wave count."""
    engine = make_engine()
    prompts = [[3, 1, 4], [2, 7, 18], [9, 9, 9], [5, 6]]
    reqs = [engine.submit(p, max_new_tokens=6) for p in prompts]
    drain(engine, *reqs)
    s = engine.stats()
    assert s["requests_admitted"] == 4
    assert s["requests_completed"] == 4
    assert s["tokens_emitted"] == sum(len(r.all_tokens(timeout=1)) for r in reqs)
    assert s["batched_admission_waves"] >= 1  # the 4-wide cold wave
    assert s["active_slots"] == 0
    assert s["queue_depth"] == 0
    assert s["uptime_s"] >= 0


def test_batched_admission_seeds_prefix_cache():
    """A batched wave stores its first member's staged row, so a recurring
    shared-prefix burst prefix-hits from the second wave on (and the hit
    path still emits reference tokens)."""
    base = list(range(5, 37))  # 32 tokens, bucket-aligned, above min_prefix
    engine = make_engine(prefix_cache_mb=64)
    wave1 = [engine.submit(base + [t], max_new_tokens=4) for t in (101, 102)]
    drain(engine, *wave1)
    assert engine.prefix_hits == 0
    wave2 = [engine.submit(base + [t], max_new_tokens=4) for t in (103, 104)]
    drain(engine, *wave2)
    assert engine.prefix_hits >= 1
    for t, r in zip((103, 104), wave2):
        assert r.all_tokens(timeout=1) == reference_tokens(base + [t], 4)


def test_mid_flight_admission():
    """A request admitted while another is mid-decode: both match reference."""
    engine = make_engine()
    first = engine.submit([11, 22, 33], max_new_tokens=16)
    engine.tick()  # admit + one chunk
    engine.tick()  # another chunk, mid-flight
    second = engine.submit([44, 55], max_new_tokens=8)
    drain(engine, first, second)
    assert first.all_tokens(timeout=1) == reference_tokens([11, 22, 33], 16)
    assert second.all_tokens(timeout=1) == reference_tokens([44, 55], 8)


def test_slot_reuse_oversubscription():
    """More requests than slots: later ones wait, slots are reused, and every
    request still matches the reference."""
    engine = make_engine(max_slots=2)
    prompts = [[i + 1, i + 2, i + 3] for i in range(5)]
    reqs = [engine.submit(p, max_new_tokens=6) for p in prompts]
    drain(engine, *reqs)
    for p, r in zip(prompts, reqs):
        assert r.all_tokens(timeout=1) == reference_tokens(p, 6)


def test_eos_stops_emission():
    prompt = [5, 9, 301, 42, 77]
    ref = reference_tokens(prompt, 12)
    eos = ref[3]  # pretend the 4th generated token is EOS
    engine = make_engine(eos_id=eos)
    req = engine.submit(prompt, max_new_tokens=12)
    drain(engine, req)
    assert req.all_tokens(timeout=1) == ref[:3]


def test_max_new_tokens_one():
    prompt = [7, 8, 9]
    engine = make_engine()
    req = engine.submit(prompt, max_new_tokens=1)
    drain(engine, req)
    assert req.all_tokens(timeout=1) == reference_tokens(prompt, 1)


def test_per_request_sampling_params_are_traced():
    """Mixed greedy + sampled requests share the compiled decode program and
    the sampled request actually varies with temperature."""
    engine = make_engine()
    greedy = engine.submit([3, 1, 4, 1, 5], max_new_tokens=8, temperature=0.0)
    hot = engine.submit([3, 1, 4, 1, 5], max_new_tokens=8, temperature=5.0, top_p=0.9)
    drain(engine, greedy, hot)
    assert greedy.all_tokens(timeout=1) == reference_tokens([3, 1, 4, 1, 5], 8)
    assert len(hot.all_tokens(timeout=1)) == 8  # sampled path emitted fully


def test_submit_validation():
    engine = make_engine(capacity=64)
    with pytest.raises(ValueError):
        engine.submit([], max_new_tokens=4)
    with pytest.raises(ValueError):
        engine.submit(list(range(60)), max_new_tokens=10)


def test_background_thread_lifecycle():
    """start()/shutdown() drive requests without manual ticking."""
    prompt = [10, 20, 30]
    with make_engine() as engine:
        req = engine.submit(prompt, max_new_tokens=6)
        assert req.all_tokens(timeout=60) == reference_tokens(prompt, 6)


def test_row_capacity_for_non_pow2_slot_capacity():
    """A non-power-of-two slot capacity must not let a chunk overflow the
    staging row (dynamic_update_slice would clamp the write while the
    attention mask assumed the true offset — silent KV corruption)."""
    from prime_tpu.serve.engine import chunk_plan, row_capacity_for

    row = row_capacity_for(2500, 512, 3000)
    assert row == 2560  # multiple of the chunk, not bucket_for's min(pow2, cap)
    for off, size in chunk_plan(0, 2500, 512, row):
        assert off + size <= row
    with pytest.raises(ValueError, match="staging row"):
        row_capacity_for(2800, 512, 3000)  # needs 3072 > capacity: clear error


def test_request_timeout_cancels():
    engine = make_engine()
    req = engine.submit([1, 2, 3], max_new_tokens=8)  # never ticked
    with pytest.raises(TimeoutError, match="cancelled"):
        req.all_tokens(timeout=0.05)
    assert req.cancelled
    engine.tick()  # the cancelled request must not be admitted
    assert not any(engine._active)


def test_chunk_plan_invariants():
    from prime_tpu.serve.engine import MIN_BUCKET, chunk_plan

    for start, length, pc, row_cb in [
        (0, 100, 512, 128), (0, 600, 512, 1024), (16, 116, 512, 128),
        (112, 128, 512, 128), (48, 1500, 256, 2048), (0, 16, 16, 16),
    ]:
        plan = chunk_plan(start, length, pc, row_cb)
        covered = start
        for off, size in plan:
            assert off == covered, "chunks must be contiguous"
            assert size & (size - 1) == 0 and size >= 1, "power-of-two sizes"
            assert off % size == 0 or off == 0, "aligned to own size"
            assert off + size <= row_cb, "never past the row (no DUS clamping)"
            assert size <= pc
            covered = off + size
        assert covered >= length, "plan must cover the prompt"
    with pytest.raises(ValueError):
        chunk_plan(MIN_BUCKET - 1, 100, 512, 128)


def test_long_prompt_chunked_admission_matches_reference():
    """A prompt longer than prefill_chunk admits in chunks and still decodes
    token-exactly like the one-shot sampler."""
    prompt = [(i * 7) % 500 + 1 for i in range(70)]
    engine = make_engine(capacity=128, prefill_chunk=32)
    req = engine.submit(prompt, max_new_tokens=8)
    drain(engine, req)
    assert req.all_tokens(timeout=1) == reference_tokens(prompt, 8)


def test_prefix_cache_hit_matches_cold_path():
    """Two prompts sharing a long prefix: the second admission seeds from the
    cached row (prefix_hits increments) and produces exactly the cold-path
    tokens."""
    shared = [(i * 11) % 500 + 1 for i in range(48)]
    a = shared + [7, 8, 9]
    b = shared + [100, 200]
    engine = make_engine(capacity=128, prefill_chunk=32, min_prefix=16,
                         prefix_cache_mb=64)
    ra = engine.submit(a, max_new_tokens=6)
    drain(engine, ra)
    assert engine.prefix_hits == 0
    rb = engine.submit(b, max_new_tokens=6)
    drain(engine, rb)
    assert engine.prefix_hits == 1
    assert ra.all_tokens(timeout=1) == reference_tokens(a, 6)
    assert rb.all_tokens(timeout=1) == reference_tokens(b, 6)


def test_prefix_cache_byte_budget_evicts_lru_and_identical_prompt():
    """Byte-budget LRU: with room for ~2 stored prefixes, storing a third
    evicts the LEAST RECENTLY USED one (p1 was touched by a hit, so p2
    goes); an identical-prompt re-admission still seeds from its own
    blocks."""
    engine = make_engine(capacity=64, prefill_chunk=32, min_prefix=16,
                         prefix_cache_mb=64)
    cache = engine.prefix_cache
    p1, p2 = [1] * 20, [2] * 20
    for p in (p1, p2):
        r = engine.submit(list(p), max_new_tokens=2)
        drain(engine, r)
    per_entry = cache.bytes // 2
    assert per_entry > 0 and cache.nodes == 2
    # touch p1 (a hit refreshes its LRU stamp), then shrink the budget so a
    # third entry forces exactly one eviction
    r = engine.submit(list(p1), max_new_tokens=2)
    drain(engine, r)
    assert engine.prefix_hits == 1
    cache.budget_bytes = int(per_entry * 2.5)
    r = engine.submit([3] * 20, max_new_tokens=2)
    drain(engine, r)
    assert cache.evictions == 1 and cache.bytes <= cache.budget_bytes
    assert engine._prefix_match_lens([1] * 20)[0] == 16  # p1 survived (recently used)
    assert engine._prefix_match_lens([2] * 20)[0] == 0   # p2 was the LRU victim
    assert engine.stats()["prefix_evictions"] == 1
    # identical prompt re-admission: seeded from its own cached blocks
    r = engine.submit([3] * 20, max_new_tokens=4)
    drain(engine, r)
    assert engine.prefix_hits == 2
    assert r.all_tokens(timeout=1) == reference_tokens([3] * 20, 4)


def test_prefix_cache_partial_hit_and_block_dedup():
    """The radix upgrade over the flat list: two prompts sharing only a
    32-token preamble store that preamble ONCE (bytes grow by the divergent
    tail only), and a third prompt sharing nothing but the preamble still
    hits at preamble length."""
    pre = [(i * 13) % 400 + 1 for i in range(32)]
    a = pre + [7, 8, 9, 10, 11, 12, 13, 14, 15, 16, 17, 18, 19, 20, 21, 22]
    b = pre + [107, 108, 109, 110, 111, 112, 113, 114, 115, 116, 117, 118,
               119, 120, 121, 122]
    engine = make_engine(capacity=128, prefill_chunk=32, min_prefix=16,
                         prefix_cache_mb=64)
    cache = engine.prefix_cache
    ra = engine.submit(list(a), max_new_tokens=4)
    drain(engine, ra)
    bytes_a = cache.bytes  # 48 stored slots
    rb = engine.submit(list(b), max_new_tokens=4)
    drain(engine, rb)
    # b hit the shared 32 tokens and stored only its 16-token tail: bytes are
    # 48 + 16 slots, NOT the 96 two full-row duplicates would cost
    assert engine.prefix_hits == 1
    assert cache.dedup_tokens >= 32
    assert cache.bytes == bytes_a * 64 // 48
    c = pre + [999, 998]
    assert engine._prefix_match_lens(c)[0] == 32  # preamble-only partial hit
    rc = engine.submit(list(c), max_new_tokens=4)
    drain(engine, rc)
    assert engine.prefix_hits == 2
    hit_hist = engine.registry.get("serve_prefix_hit_tokens").series_snapshot(tier="device")
    assert hit_hist["count"] == 2 and hit_hist["sum"] == 64.0  # 32 + 32
    for p, r in ((a, ra), (b, rb), (c, rc)):
        assert r.all_tokens(timeout=1) == reference_tokens(list(p), 4)


def test_prefix_cache_refcount_blocks_eviction():
    """A pinned match (segments mid-assembly) survives a byte-budget sweep;
    releasing the pin makes the path evictable again."""
    engine = make_engine(capacity=64, prefill_chunk=32, min_prefix=16,
                         prefix_cache_mb=64)
    cache = engine.prefix_cache
    prompt = list(range(40, 60))
    r = engine.submit(list(prompt), max_new_tokens=2)
    drain(engine, r)
    match = cache.match(prompt, limit=16)
    assert match is not None and match.length == 16
    cache.budget_bytes = 1  # everything must go — except the pinned path
    assert cache.evict_to_budget() == 0
    assert cache.bytes > 0 and cache.nodes == 1
    cache.release(match)
    assert cache.evict_to_budget() == 1
    assert cache.bytes == 0 and cache.nodes == 0


@pytest.mark.parametrize("overlap", [True, False], ids=["overlap", "sync"])
def test_prefix_cache_bit_identity_on_off(overlap):
    """Greedy outputs are bit-identical with the prefix cache disabled,
    device-only, and two-tier under device-budget pressure (segments spill
    to host RAM and hits re-upload), across the overlap and synchronous
    loops — neither the radix cache/assemble path nor the spill tier may be
    visible in the emitted tokens. (CI runs this matrix as the serve-engine
    smoke step.)"""
    pre = [(i * 19) % 300 + 2 for i in range(32)]
    alt = [(i * 23) % 300 + 2 for i in range(32)]  # disjoint preamble
    prompts = [
        pre + [7, 8, 9],
        pre + [100, 200],          # shares the full preamble with the first
        pre[:16] + [5, 5, 5, 5],   # shares only the first block
        [9, 8, 7],                 # no shared prefix at all
        alt + [1, 2],              # new preamble: under pressure, spills pre
        pre + [7, 8, 9],           # identical replay: re-uploads from host
    ]
    configs = {
        "off": dict(prefix_cache_mb=0),
        "device": dict(prefix_cache_mb=64),
        "host": dict(prefix_cache_mb=64, prefix_cache_host_mb=64),
    }
    outs = {}
    for name, kw in configs.items():
        engine = make_engine(capacity=128, prefill_chunk=32, min_prefix=16,
                             overlap=overlap, **kw)
        assert engine.overlap is overlap
        outs[name] = []
        for i, p in enumerate(prompts):
            req = engine.submit(list(p), max_new_tokens=8)
            drain(engine, req)
            outs[name].append(req.all_tokens(timeout=1))
            if name == "host" and i == 0:
                # squeeze the device budget to exactly the first stored
                # prefix: the alt-preamble store must demote, the replay
                # must re-upload (max(...,1): 0 would mean unbounded)
                engine.prefix_cache.budget_bytes = max(engine.prefix_cache.bytes, 1)
        if name != "off":
            assert engine.prefix_hits >= 3  # 2nd, 3rd, and replay prompts hit
        if name == "host":
            cache = engine.prefix_cache
            assert cache.spills > 0, "device pressure never spilled"
            assert cache.reuploads > 0, "replay never re-uploaded from host"
            assert cache.evictions == 0  # spill tier absorbed the pressure
            host_hist = engine.registry.get(
                "serve_prefix_hit_tokens"
            ).series_snapshot(tier="host")
            assert host_hist is not None and host_hist["count"] >= 1
    assert outs["device"] == outs["off"]
    assert outs["host"] == outs["off"]


@pytest.mark.parametrize("speculative", [False, True], ids=["plain", "spec"])
@pytest.mark.parametrize("overlap", [True, False], ids=["overlap", "sync"])
def test_paged_seeding_bit_identity_vs_copy(overlap, speculative):
    """Paged-gather hit seeding vs the contiguous assemble_row copy engine:
    greedy outputs are bit-identical across the overlap x speculative matrix.
    The paged engine must actually seed from the page pool (paged seeds
    counted, zero copy assembles) and the copy engine must keep the old
    path — the pool is pure data movement and may never show in tokens."""
    pre = [(i * 19) % 300 + 2 for i in range(32)]
    prompts = [
        pre + [7, 8, 9],
        pre + [100, 200],          # full-preamble hit
        pre[:16] + [5, 5, 5, 5],   # partial (one-block) hit
        [9, 8, 7],                 # cold, no prefix at all
        pre + [7, 8, 9],           # identical replay
    ]
    outs = {}
    for name, paged in (("paged", True), ("copy", False)):
        engine = make_engine(capacity=128, prefill_chunk=32, min_prefix=16,
                             prefix_cache_mb=64, overlap=overlap,
                             speculative=speculative, paged_prefix=paged)
        assert engine.paged_prefix is paged
        outs[name] = []
        for p in prompts:
            req = engine.submit(list(p), max_new_tokens=8)
            drain(engine, req)
            outs[name].append(req.all_tokens(timeout=1))
        assert engine.prefix_hits >= 3
        stats = engine.stats()
        if paged:
            assert stats["prefix_paged_seeds"] >= 3
            assert stats["prefix_assembles"] == 0
        else:
            assert stats["prefix_paged_seeds"] == 0
            assert stats["prefix_assembles"] >= 3
        hist = engine.registry.get("serve_prefix_seed_seconds").series_snapshot(
            path="paged" if paged else "copy"
        )
        assert hist is not None and hist["count"] >= 3
    assert outs["paged"] == outs["copy"]


def test_paged_seeding_interpret_kernel_bit_identity(monkeypatch):
    """The same paged seeding run through the actual pallas gather kernel
    (interpret mode on CPU) instead of the XLA gather fallback — outputs
    stay bit-identical to the copy engine (CI's kernels leg pins this)."""
    pre = [(i * 19) % 300 + 2 for i in range(32)]
    prompts = [pre + [7, 8, 9], pre + [100, 200], pre + [7, 8, 9]]

    def run(paged):
        engine = make_engine(capacity=128, prefill_chunk=32, min_prefix=16,
                             prefix_cache_mb=64, paged_prefix=paged)
        out = []
        for p in prompts:
            req = engine.submit(list(p), max_new_tokens=8)
            drain(engine, req)
            out.append(req.all_tokens(timeout=1))
        return engine, out

    copy_engine, copy_out = run(False)
    monkeypatch.setenv("PRIME_TPU_PALLAS_INTERPRET", "1")
    paged_engine, paged_out = run(True)
    assert paged_engine.stats()["prefix_paged_seeds"] >= 2
    assert paged_out == copy_out


def test_paged_prefix_gating(monkeypatch):
    """paged_prefix requires a prefix cache and a single device; the
    PRIME_SERVE_PAGED_PREFIX env knob and the kwarg both gate it off."""

    class _FakeMesh:
        size = 8

    assert make_engine(prefix_cache_mb=1).paged_prefix is True
    assert make_engine(prefix_cache_mb=0).paged_prefix is False
    assert make_engine(prefix_cache_mb=1, mesh=_FakeMesh()).paged_prefix is False
    assert make_engine(prefix_cache_mb=1, paged_prefix=False).paged_prefix is False
    monkeypatch.setenv("PRIME_SERVE_PAGED_PREFIX", "0")
    assert make_engine(prefix_cache_mb=1).paged_prefix is False
    monkeypatch.delenv("PRIME_SERVE_PAGED_PREFIX")
    assert make_engine(prefix_cache_mb=1, paged_prefix=True).paged_prefix is True


def test_prefix_cache_host_env_wiring(monkeypatch):
    """PRIME_SERVE_PREFIX_CACHE_HOST_MB and the kwarg both reach the cache as
    a host byte budget with the engine's real tier converters installed; the
    kwarg wins over the env, and the default is single-tier (0)."""
    assert make_engine(prefix_cache_mb=1).prefix_cache.host_budget_bytes == 0
    monkeypatch.setenv("PRIME_SERVE_PREFIX_CACHE_HOST_MB", "8")
    cache = make_engine(prefix_cache_mb=1).prefix_cache
    assert cache.host_budget_bytes == 8 * 2**20
    from prime_tpu.serve.engine import _segment_to_device, _segment_to_host
    assert cache._to_host is _segment_to_host
    assert cache._to_device is _segment_to_device
    kwarg = make_engine(prefix_cache_mb=1, prefix_cache_host_mb=2).prefix_cache
    assert kwarg.host_budget_bytes == 2 * 2**20

    class _FakeMesh:  # spill converters are not sharding-preserving yet
        size = 8

    with pytest.warns(UserWarning, match="host spill tier"):
        gated = make_engine(prefix_cache_mb=1, prefix_cache_host_mb=2, mesh=_FakeMesh())
    assert gated.prefix_cache.host_budget_bytes == 0
    assert gated.prefix_cache_host_mb == 0.0


def test_stats_snapshot_is_loop_ticked():
    """With the engine loop running, stats() serves the end-of-tick snapshot
    (one writer: the engine thread) instead of reading live state; a
    synchronous owner still gets a fresh computation."""
    engine = make_engine()
    fresh = engine.stats()  # no thread: computed live
    assert fresh["requests_admitted"] == 0
    with engine:
        req = engine.submit([1, 2, 3], max_new_tokens=4)
        req.all_tokens(timeout=120)
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            snap = engine.stats()
            if snap["requests_completed"] == 1 and snap["active_slots"] == 0:
                break
            time.sleep(0.01)
        assert snap["requests_completed"] == 1
        assert snap["requests_admitted"] == 1
        # the reader got the published snapshot, not a mid-tick recomputation
        assert engine._stats_snapshot is not None


# -- speculative continuous decoding ------------------------------------------


def test_spec_engine_greedy_matches_plain():
    """The load-bearing invariant, speculative edition: whatever the drafts
    do, a greedy request emits exactly the plain engine's tokens."""
    prompts = [
        list(range(1, 9)) * 2,           # periodic: drafts land
        [7, 100, 23, 451, 88, 3],        # aperiodic: drafts mostly miss
    ]
    refs = [reference_tokens(p, 12) for p in prompts]
    engine = make_engine(speculative=True, draft_len=4)
    reqs = [engine.submit(p, max_new_tokens=12) for p in prompts]
    for req in reqs:
        drain(engine, req)
    for req, ref in zip(reqs, refs):
        assert req.all_tokens(timeout=1) == ref


def test_spec_engine_eos_and_budget():
    prompt = [5, 9, 301, 42, 77]
    ref = reference_tokens(prompt, 12)
    eos = ref[3]
    engine = make_engine(speculative=True, eos_id=eos)
    req = engine.submit(prompt, max_new_tokens=12)
    drain(engine, req)
    assert req.all_tokens(timeout=1) == ref[:3]
    # budget: exactly max_new_tokens even when a verify run overshoots
    engine2 = make_engine(speculative=True)
    req2 = engine2.submit(list(range(1, 9)) * 2, max_new_tokens=5)
    drain(engine2, req2)
    assert len(req2.all_tokens(timeout=1)) == 5


def test_spec_engine_mixed_sampling_slots():
    """A sampled request and a greedy request decode concurrently through
    the one verify program; the greedy one still matches the reference."""
    greedy_prompt = list(range(1, 9)) * 2
    ref = reference_tokens(greedy_prompt, 10)
    engine = make_engine(speculative=True)
    sampled = engine.submit([3, 1, 4, 1, 5, 9], max_new_tokens=10, temperature=0.8, top_p=0.9)
    greedy = engine.submit(greedy_prompt, max_new_tokens=10)
    drain(engine, sampled)
    drain(engine, greedy)
    assert greedy.all_tokens(timeout=1) == ref
    assert len(sampled.all_tokens(timeout=1)) == 10


def test_spec_engine_with_kv_quant():
    prompt = list(range(1, 9)) * 2
    plain = make_engine(kv_quant=True)
    ref_req = plain.submit(prompt, max_new_tokens=10)
    drain(plain, ref_req)
    spec = make_engine(kv_quant=True, speculative=True)
    req = spec.submit(prompt, max_new_tokens=10)
    drain(spec, req)
    assert req.all_tokens(timeout=1) == ref_req.all_tokens(timeout=1)


def test_spec_engine_capacity_includes_verify_window():
    engine = make_engine(speculative=True, draft_len=4, capacity=32)
    with pytest.raises(ValueError, match="verify window"):
        engine.submit(list(range(1, 17)), max_new_tokens=12)  # 16+12+5 > 32


def test_kv_quant_engine_end_to_end():
    """int8-cache engine: requests complete, decode matches the one-shot
    sampler's kv-quant decode closely (prefill differs only by the chunked
    path attending over the int8 cache), and the cache really is int8."""
    engine = make_engine(kv_quant=True)
    assert engine._cache.k.dtype == jnp.int8 and engine._cache.quantized
    prompt = [1, 5, 9, 13, 9, 5]
    req = engine.submit(prompt, max_new_tokens=10)
    while not req.done:
        engine.tick()
    got = req.all_tokens(timeout=1)
    assert len(got) == 10
    # reference: plain generate with the same quantized-cache decode
    prompts = jnp.asarray([prompt], dtype=jnp.int32)
    lengths = jnp.asarray([len(prompt)], dtype=jnp.int32)
    ref = generate(
        PARAMS, prompts, lengths, CONFIG, jax.random.PRNGKey(7),
        max_new_tokens=10, temperature=0.0, kv_quant=True,
    ).tokens[0].tolist()
    assert got == ref


def test_kv_quant_prefix_cache_roundtrip():
    """Quantized staging rows (values + scales) survive the prefix cache:
    a warm admission reuses the int8 row and still completes correctly."""
    engine = make_engine(kv_quant=True, prefix_cache_mb=64, min_prefix=8)
    shared = list(range(1, 17))  # 16-token shared prefix
    first = engine.submit(shared + [21, 22], max_new_tokens=4)
    while not first.done:
        engine.tick()
    cold = first.all_tokens(timeout=1)
    second = engine.submit(shared + [21, 22], max_new_tokens=4)
    while not second.done:
        engine.tick()
    warm = second.all_tokens(timeout=1)
    assert engine.prefix_hits >= 1
    assert warm == cold  # identical prompt, identical int8 row -> same tokens


def test_cancel_retires_slot():
    """A cancelled request frees its slot at the next tick and its consumer
    sees a clean end-of-stream."""
    engine = make_engine(max_slots=1)
    victim = engine.submit([1, 2, 3], max_new_tokens=50)
    engine.tick()  # admit + first chunk
    assert engine._active[0]
    victim.cancel()
    next_req = engine.submit([4, 5, 6], max_new_tokens=4)
    drain(engine, next_req)  # only possible if the slot was freed
    assert victim.done
    assert next_req.all_tokens(timeout=1) == reference_tokens([4, 5, 6], 4)


def test_decode_failure_fails_requests_and_recovers():
    """A raised decode dispatch must not kill the engine: in-flight requests
    error out promptly and the next request is served fresh. (Synchronous
    loop — the overlapped error path is test_overlap_decode_failure_*.)"""
    engine = make_engine(overlap=False)
    req = engine.submit([1, 2, 3], max_new_tokens=8)
    engine._admit()
    boom = [True]
    real_dispatch = engine._dispatch_decode

    def exploding():
        if boom[0]:
            boom[0] = False
            raise RuntimeError("chip on fire")
        real_dispatch()

    engine._dispatch_decode = exploding
    engine.tick()
    with pytest.raises(RuntimeError, match="chip on fire"):
        req.all_tokens(timeout=1)
    # engine state was reallocated; a new request decodes correctly
    fresh = engine.submit([7, 8, 9], max_new_tokens=4)
    drain(engine, fresh)
    assert fresh.all_tokens(timeout=1) == reference_tokens([7, 8, 9], 4)


def test_shutdown_fails_waiting_requests_promptly():
    """Shutdown must not leave clients blocked until their read timeout:
    queued requests and in-flight slots both get a prompt error."""
    engine = make_engine()
    queued = engine.submit([5, 6], max_new_tokens=4)  # never admitted
    engine.shutdown()
    with pytest.raises(RuntimeError, match="shut down"):
        queued.all_tokens(timeout=5)

    engine2 = make_engine()
    in_flight = engine2.submit([1, 2, 3], max_new_tokens=8)
    engine2._admit()  # admitted into a slot, decode never finishes
    engine2.shutdown()
    with pytest.raises(RuntimeError, match="shut down"):
        in_flight.all_tokens(timeout=5)


# -- overlapped decode pipeline -----------------------------------------------


def test_overlap_default_env_and_spec_composes(monkeypatch):
    """Overlap is on by default, PRIME_SERVE_OVERLAP=0 switches it off, and
    speculative mode now RIDES the pipeline (drafting moved on-device, so
    the old serial-loop pin — drafts needing host tokens — is gone)."""
    assert make_engine().overlap
    monkeypatch.setenv("PRIME_SERVE_OVERLAP", "0")
    assert not make_engine().overlap
    monkeypatch.setenv("PRIME_SERVE_OVERLAP", "1")
    assert make_engine(speculative=True).overlap
    assert make_engine(speculative=True, overlap=True).overlap
    monkeypatch.delenv("PRIME_SERVE_OVERLAP")
    assert not make_engine(overlap=False).overlap
    assert not make_engine(speculative=True, overlap=False).overlap


def test_spec_env_knob_wiring(monkeypatch):
    """PRIME_SERVE_SPEC / PRIME_SERVE_DRAFT_LEN drive the constructor
    defaults through the env helpers; explicit kwargs beat the env."""
    assert not make_engine().speculative
    assert make_engine().draft_len == 4
    monkeypatch.setenv("PRIME_SERVE_SPEC", "1")
    monkeypatch.setenv("PRIME_SERVE_DRAFT_LEN", "6")
    engine = make_engine()
    assert engine.speculative and engine.draft_len == 6
    assert not make_engine(speculative=False).speculative
    assert make_engine(draft_len=3).draft_len == 3
    monkeypatch.setenv("PRIME_SERVE_SPEC", "0")
    assert not make_engine().speculative


def test_overlap_dispatches_next_chunk_before_syncing_previous(monkeypatch):
    """The load-bearing pipeline property, asserted via tracer-span order:
    chunk N+1's serve.dispatch span finishes BEFORE chunk N's serve.sync
    span — i.e. the host enqueued the next chunk before it blocked for the
    previous one's tokens."""
    from prime_tpu.obs.trace import Tracer
    from prime_tpu.serve import engine as engine_mod

    tracer = Tracer(enabled=True)
    monkeypatch.setattr(engine_mod, "TRACER", tracer)
    engine = make_engine()
    req = engine.submit([5, 9, 301, 42, 77], max_new_tokens=16)
    drain(engine, req)
    engine.tick()  # drain the lookahead chunk
    order = [
        (s["name"], s["attrs"]["seq"])
        for s in tracer.drain()
        if s["name"] in ("serve.dispatch", "serve.sync")
    ]
    assert ("serve.dispatch", 1) in order and ("serve.sync", 0) in order
    # every sync of chunk N comes after the dispatch of chunk N+1 (when one
    # exists: the final drained chunk has no successor)
    for name, seq in order:
        if name == "serve.sync" and ("serve.dispatch", seq + 1) in order:
            assert order.index(("serve.dispatch", seq + 1)) < order.index(
                ("serve.sync", seq)
            ), f"chunk {seq + 1} was not dispatched before chunk {seq}'s sync"
    assert req.all_tokens(timeout=1) == reference_tokens([5, 9, 301, 42, 77], 16)


def test_overlap_greedy_streams_identical_to_sync():
    """Bit-identical token streams: the overlapped pipeline reorders host
    work, never device math — greedy decode must emit exactly what the
    synchronous loop emits, request by request."""
    prompts = [[3, 1, 4, 1, 5], [2, 7, 18], [161, 80, 33, 98, 226, 50], [101]]

    def run(overlap):
        engine = make_engine(overlap=overlap)
        reqs = [engine.submit(list(p), max_new_tokens=11) for p in prompts]
        drain(engine, *reqs)
        engine.tick()  # overlapped mode: drain the lookahead chunk
        return [r.all_tokens(timeout=1) for r in reqs]

    assert run(True) == run(False)


def test_overlap_eos_lag_counts_wasted_decode():
    """A request retiring on EOS mid-pipeline emits nothing past EOS, and
    the lookahead chunk decoded for its slot is counted as wasted decode
    (bounded at one chunk per retirement)."""
    prompt = [5, 9, 301, 42, 77]
    ref = reference_tokens(prompt, 12)
    eos = ref[3]
    engine = make_engine(eos_id=eos)
    assert engine.overlap
    req = engine.submit(prompt, max_new_tokens=12)
    drain(engine, req)
    for _ in range(3):
        engine.tick()  # drain the pipeline
    assert req.all_tokens(timeout=1) == ref[:3]  # nothing past EOS
    stats = engine.stats()
    assert stats["wasted_decode_tokens"] >= engine.chunk
    assert stats["inflight_depth"] == 0
    assert stats["loop_starved_s"].get("sync_wait", 0.0) <= stats["loop_s"]["sync_wait"]


def test_overlap_cancel_retires_with_one_chunk_lag():
    """Cancellation under the pipeline: the slot frees at the next chunk
    boundary, its lookahead tokens are dropped (not leaked to the slot's
    next tenant), and the replacement request decodes reference-exactly."""
    engine = make_engine(max_slots=1)
    victim = engine.submit([1, 2, 3], max_new_tokens=50)
    engine.tick()  # admit
    engine.tick()  # dispatch first chunk
    assert engine._active[0] and engine._inflight
    victim.cancel()
    replacement = engine.submit([4, 5, 6], max_new_tokens=4)
    drain(engine, replacement)
    engine.tick()
    assert victim.done
    assert replacement.all_tokens(timeout=1) == reference_tokens([4, 5, 6], 4)
    assert engine.stats()["wasted_decode_tokens"] >= engine.chunk


def test_overlap_decode_failure_with_inflight_chunk_recovers():
    """A raised dispatch while a lookahead chunk is in flight: the pipeline
    is dropped, in-flight requests fail promptly (donated buffers are gone),
    device state reallocates, and the next request is served fresh."""
    engine = make_engine()
    req = engine.submit([1, 2, 3], max_new_tokens=32)
    engine.tick()  # admit
    engine.tick()  # dispatch chunk 0
    assert engine._inflight
    real_fn = engine._decode_fn

    def exploding(*args, **kwargs):
        raise RuntimeError("chip on fire")

    engine._decode_fn = exploding
    engine.tick()  # dispatch raises with a chunk still in flight
    engine._decode_fn = real_fn
    assert not engine._inflight
    with pytest.raises(RuntimeError, match="chip on fire"):
        req.all_tokens(timeout=1)
    fresh = engine.submit([7, 8, 9], max_new_tokens=4)
    drain(engine, fresh)
    assert fresh.all_tokens(timeout=1) == reference_tokens([7, 8, 9], 4)


def test_spec_serial_loop_reference(monkeypatch):
    """The serial speculative loop (overlap=False) is the bit-identity
    reference: the fused dispatch syncs immediately and never leaves a
    chunk in flight."""
    engine = make_engine(speculative=True, draft_len=4, overlap=False)
    assert not engine.overlap
    req = engine.submit(list(range(1, 9)) * 2, max_new_tokens=12)
    drain(engine, req)
    assert not engine._inflight
    assert req.all_tokens(timeout=1) == reference_tokens(list(range(1, 9)) * 2, 12)


def test_spec_overlap_pipelines_like_decode(monkeypatch):
    """The tentpole property: speculative mode rides the one-chunk-deep
    pipeline — spec chunk N+1's serve.spec_dispatch span finishes BEFORE
    chunk N's serve.sync span (the host enqueued the next fused
    propose+verify before blocking for the previous one's tokens), and the
    emitted greedy tokens still match the reference exactly."""
    from prime_tpu.obs.trace import Tracer
    from prime_tpu.serve import engine as engine_mod

    tracer = Tracer(enabled=True)
    monkeypatch.setattr(engine_mod, "TRACER", tracer)
    engine = make_engine(speculative=True, draft_len=4)
    assert engine.overlap
    prompt = [5, 9, 301, 42, 77]
    req = engine.submit(prompt, max_new_tokens=16)
    drain(engine, req)
    engine.tick()  # drain the lookahead chunk
    order = [
        (s["name"], s["attrs"]["seq"])
        for s in tracer.drain()
        if s["name"] in ("serve.spec_dispatch", "serve.sync")
    ]
    assert ("serve.spec_dispatch", 1) in order and ("serve.sync", 0) in order
    for name, seq in order:
        if name == "serve.sync" and ("serve.spec_dispatch", seq + 1) in order:
            assert order.index(("serve.spec_dispatch", seq + 1)) < order.index(
                ("serve.sync", seq)
            ), f"spec chunk {seq + 1} was not dispatched before chunk {seq}'s sync"
    assert req.all_tokens(timeout=1) == reference_tokens(prompt, 16)


@pytest.mark.parametrize("cache_mb", [0, 8], ids=["nocache", "prefixcache"])
def test_spec_bit_identity_matrix(cache_mb):
    """The acceptance matrix: greedy outputs with speculative mode on are
    bit-identical to the serial spec loop AND to non-spec decode across
    overlap x prefix-cache, including a second shared-prefix wave that
    actually hits the cache when it is on."""
    shared = list(range(5, 37))  # 32 tokens: two MIN_BUCKET blocks
    prompts = [
        list(range(1, 9)) * 2,            # periodic: drafts land
        [7, 100, 23, 451, 88, 3],         # aperiodic: drafts mostly miss
        shared + [61, 62],                # shared-prefix pair: wave 2 hits
        shared + [63],
    ]

    def run(**kw):
        engine = make_engine(prefix_cache_mb=cache_mb, min_prefix=16, **kw)
        waves = []
        for _ in range(2):
            reqs = [engine.submit(list(p), max_new_tokens=10) for p in prompts]
            drain(engine, *reqs)
            engine.tick()  # drain any lookahead chunk
            waves.append([r.all_tokens(timeout=1) for r in reqs])
        return engine, waves

    spec_overlap, out_spec_overlap = run(speculative=True, overlap=True)
    spec_serial, out_spec_serial = run(speculative=True, overlap=False)
    plain, out_plain = run(speculative=False, overlap=True)
    assert out_spec_overlap == out_spec_serial == out_plain
    for p, tokens in zip(prompts, out_spec_overlap[0]):
        assert tokens == reference_tokens(list(p), 10)
    if cache_mb:
        # the prefix cache really served the second wave under speculation
        assert spec_overlap.prefix_hits >= 2
        assert spec_overlap.prefix_hits == spec_serial.prefix_hits == plain.prefix_hits
    # acceptance evidence flowed: periodic prompts accept drafts
    stats = spec_overlap.stats()
    assert stats["speculative"] and stats["draft_len"] == 4
    assert stats["spec_accept_ratio"] > 0


def test_spec_acceptance_metrics_and_waste_accounting():
    """Spec obs satellite: serve_spec_accepted_tokens observes per-window
    accepted drafts, serve_spec_draft_tokens_total counts proposals, the
    accept-ratio gauge publishes their quotient, and a retirement-lagged
    spec window counts its accepted-length run as wasted decode."""
    prompt = [5, 9, 301, 42, 77]
    ref = reference_tokens(prompt, 12)
    eos = ref[3]
    engine = make_engine(speculative=True, draft_len=4, eos_id=eos)
    assert engine.overlap
    req = engine.submit(prompt, max_new_tokens=12)
    drain(engine, req)
    for _ in range(3):
        engine.tick()  # drain the pipeline (stale lookahead window)
    assert req.all_tokens(timeout=1) == ref[:3]
    values = engine.registry.values()
    proposed = values["serve_spec_draft_tokens_total"]
    assert proposed > 0 and proposed % engine.draft_len == 0
    hist = engine.registry.snapshot()["serve_spec_accepted_tokens"]["series"][0]
    assert hist["count"] == proposed / engine.draft_len
    expected_ratio = hist["sum"] / proposed
    engine.stats()
    assert engine.registry.values()["serve_spec_accept_ratio"] == pytest.approx(
        expected_ratio
    )
    # the EOS-retired slot's stale in-flight window was counted as waste
    assert engine.stats()["wasted_decode_tokens"] >= 1


def test_spec_overlap_admission_overhead_capacity_pin():
    """Satellite: with an in-flight spec chunk a slot can hold up to
    2*(draft_len+1) unretired token positions, so admission reserves them —
    a request at exactly the bound completes without any KV write past the
    slot capacity, and one more token is refused at submit()."""
    engine = make_engine(speculative=True, draft_len=4, capacity=64)
    assert engine.overlap and engine.spec_overhead == 10
    fits = 64 - 16 - engine.spec_overhead
    prompt = list(range(1, 9)) * 2  # periodic 16 tokens: windows really run
    req = engine.submit(prompt, max_new_tokens=fits)
    with pytest.raises(ValueError, match="verify window"):
        engine.submit(prompt, max_new_tokens=fits + 1)
    drain(engine, req)
    for _ in range(3):
        engine.tick()  # let the stale lookahead window land
    assert req.all_tokens(timeout=1) == reference_tokens(prompt, fits)
    import numpy as np

    # device truth: even after the stale lookahead window, no slot length
    # escapes the row — every KV write a LIVE request saw landed unclamped
    lengths = np.asarray(engine._cache.lengths)
    assert int(lengths.max()) <= engine.capacity
    # the serial loop reserves a single window
    serial = make_engine(speculative=True, draft_len=4, capacity=64, overlap=False)
    assert serial.spec_overhead == 5
    serial.submit(prompt, max_new_tokens=64 - 16 - 5)


def test_idle_burst_requeues_into_one_batched_wave():
    """The idle-path admission fix: a request popped by the idle loop is
    requeued at the FRONT (arrival order kept) and admitted through the
    batched _admit() path together with the rest of the burst — not
    prefilled singly via the old argmin path."""
    engine = make_engine()
    prompts = [[3, 1, 4], [2, 7, 18], [9, 9, 9], [5, 6]]
    reqs = [engine.submit(list(p), max_new_tokens=6) for p in prompts]
    # what _run's idle path does: pop one, requeue, tick
    first = engine._pending.get(timeout=1)
    assert first is reqs[0]
    engine._requeue(first)
    engine.tick()
    assert engine.batched_waves == 1  # ONE 4-wide wave, order preserved
    assert [engine._requests[s].id for s in sorted(engine._requests)] == [
        r.id for r in reqs
    ]
    drain(engine, *reqs)
    for p, r in zip(prompts, reqs):
        assert r.all_tokens(timeout=1) == reference_tokens(p, 6)


# -- AOT warmup ----------------------------------------------------------------


def test_warmup_compiles_programs_and_preserves_cold_state(monkeypatch):
    """warmup() executes the bounded program set (decode + every
    chunk-prefill/finalize shape) against the engine's own device state and
    leaves it indistinguishable from cold: the first real request still
    decodes reference-exactly. PRIME_SERVE_WARMUP gates the start() hook."""
    engine = make_engine(max_slots=2, capacity=32, prefill_chunk=16, warmup=True)
    assert engine.warmup_enabled
    rng_before = engine._rng
    programs = engine.warmup()
    # decode + per-(row, batch) chunk/finalize: rows {16, 32} x batches {1, 2}
    assert programs >= 1 + 2 * 2 * 2
    # cold-state indistinguishability includes the sampling stream: a warmed
    # engine's sampled requests must be bit-identical to a cold engine's
    assert (engine._rng == rng_before).all()
    stats = engine.stats()
    assert stats["warmup_programs"] == programs
    req = engine.submit([5, 9, 3], max_new_tokens=6)
    drain(engine, req)
    assert req.all_tokens(timeout=1) == reference_tokens([5, 9, 3], 6)
    # warmup against a live engine would splice zero-length garbage over
    # occupied slots: guarded
    busy = engine.submit([7, 8], max_new_tokens=20)
    engine.tick()
    assert any(engine._active)
    with pytest.raises(RuntimeError, match="idle engine"):
        engine.warmup()
    busy.cancel()

    monkeypatch.setenv("PRIME_SERVE_WARMUP", "1")
    assert make_engine().warmup_enabled
    monkeypatch.setenv("PRIME_SERVE_WARMUP", "0")
    assert not make_engine().warmup_enabled


@pytest.mark.parametrize("asked, widest", [(None, 8), (16, 8), (8, 8), (5, 4), (2, 2), (1, 1)])
def test_max_wave_narrows_the_waves_admit_forms(asked, widest, monkeypatch):
    """``max_wave`` asks for narrower admission waves than the rule from
    shapes allows (64 here), rounded down to a power of two: the waves
    ``_admit`` forms of a burst of one plan stay under it, and the warm-up
    reads the same number, so it builds no width that cannot come."""
    engine = make_engine(max_slots=16, capacity=2048, prefill_chunk=512, max_wave=asked)
    assert engine.max_wave == (64 if asked is None else 2 ** (asked.bit_length() - 1))
    waves = []
    monkeypatch.setattr(
        engine, "_prefill", lambda reqs, slots, cap, plan, *rest: waves.append(len(reqs))
    )
    for _ in range(11):
        engine.submit(list(range(1, 600)), max_new_tokens=2)
    engine.tick()
    assert sum(waves) == 11 and max(waves) == widest


def test_max_wave_below_one_is_refused():
    with pytest.raises(ValueError, match="max_wave"):
        make_engine(max_wave=0)


def test_warmup_failure_is_fatal_and_reallocates_state():
    """A warmup that was asked for and fails stops start() — the server must
    not come up in front of an engine that could not compile. The failed
    dispatch consumed its donated inputs, so warmup reallocates device state
    before raising: the same engine still decodes reference-exactly."""
    engine = make_engine(warmup=True)
    real_make = engine._make_decode
    boomed = []

    def flaky_make():
        fn = real_make()

        def wrapper(*args, **kwargs):
            out = fn(*args, **kwargs)  # donation already happened
            if not boomed:
                boomed.append(1)
                raise RuntimeError("warmup boom")
            return out

        return wrapper

    engine._make_decode = flaky_make
    with pytest.raises(RuntimeError, match="warmup boom"):
        engine.start()
    assert engine._thread is None and not engine._running
    engine._decode_fn = None  # drop the wrapper that already boomed
    engine._make_decode = real_make
    with engine:  # warmup again, clean this time, then serve
        req = engine.submit([1, 2, 3], max_new_tokens=4)
        assert req.all_tokens(timeout=120) == reference_tokens([1, 2, 3], 4)


def test_warmup_speculative_covers_spec_program_set():
    """The spec program set is pinned relative to the plain engine: one
    fused propose+verify program plus one history-seed program per
    admission-wave width (powers of two up to max_slots). A drifting count
    means a spec program real traffic compiles mid-pipeline that warmup
    missed. Warmup must also leave the history ring cold: the first real
    request still matches the reference."""
    kw = dict(max_slots=2, capacity=64, prefill_chunk=16)
    engine = make_engine(speculative=True, draft_len=4, **kw)
    programs = engine.warmup()
    plain_programs = make_engine(**kw).warmup()
    # + fused spec dispatch + hist-seed at wave widths {1, 2}
    assert programs == plain_programs + 1 + 2
    import numpy as np

    assert int(np.asarray(engine._hist_len).max()) == 0  # ring is cold
    prompt = list(range(1, 9)) * 2
    req = engine.submit(prompt, max_new_tokens=10)
    drain(engine, req)
    assert req.all_tokens(timeout=1) == reference_tokens(prompt, 10)


def test_stats_reports_pipeline_fields():
    engine = make_engine()
    req = engine.submit([1, 2, 3], max_new_tokens=6)
    drain(engine, req)
    engine.tick()
    s = engine.stats()
    assert s["overlap"] is True
    assert s["inflight_depth"] == 0
    assert s["loop_s"]["sync_wait"] > 0
    assert all(0.0 <= starved <= s["loop_s"][phase] + 1e-6
               for phase, starved in s["loop_starved_s"].items())
    assert s["loop_starved_unseen_s"] >= 0
    assert s["wasted_decode_tokens"] >= 0 and s["warmup_programs"] == 0


def test_engine_backend_server_integration():
    """EngineBackend behind InferenceServer: concurrent non-stream requests
    and true live SSE streaming, token deltas matching the reference."""
    import httpx

    from prime_tpu.evals.tokenizer import ByteTokenizer
    from prime_tpu.serve import InferenceServer
    from prime_tpu.serve.engine import EngineBackend

    tok = ByteTokenizer()
    with make_engine(capacity=128) as engine:
        backend = EngineBackend(engine, tok)
        with InferenceServer("tiny-test", backend, port=0) as srv:
            # non-streaming
            r = httpx.post(
                f"{srv.url}/v1/chat/completions",
                json={"messages": [{"role": "user", "content": "ab"}], "max_tokens": 8},
                timeout=60,
            )
            assert r.status_code == 200
            body = r.json()["choices"][0]["message"]["content"]

            # live streaming of the same prompt: identical final text
            streamed = ""
            with httpx.stream(
                "POST",
                f"{srv.url}/v1/chat/completions",
                json={
                    "messages": [{"role": "user", "content": "ab"}],
                    "max_tokens": 8,
                    "stream": True,
                },
                timeout=60,
            ) as resp:
                assert resp.headers["content-type"].startswith("text/event-stream")
                for line in resp.iter_lines():
                    if not line.startswith("data: ") or line == "data: [DONE]":
                        continue
                    chunk = json.loads(line[len("data: "):])
                    delta = chunk["choices"][0]["delta"]
                    streamed += delta.get("content", "")
            assert streamed == body


def test_engine_backend_generate_blocking():
    """The backend's generate() protocol (non-streaming path) detokenizes
    exactly the engine's emitted ids."""
    from prime_tpu.evals.tokenizer import ByteTokenizer
    from prime_tpu.serve.engine import EngineBackend

    tok = ByteTokenizer()
    prompt = "hello"
    with make_engine(capacity=128) as engine:
        backend = EngineBackend(engine, tok)
        [text] = backend.generate([prompt], max_new_tokens=6, temperature=0.0)
    ref = reference_tokens(tok.encode(prompt), 6)
    assert text == tok.decode(ref)


def test_engine_under_mesh():
    """The engine runs sharded over a device mesh (tp over kv heads)."""
    from prime_tpu.parallel.mesh import make_mesh
    from prime_tpu.parallel.sharding import cache_spec, shard_params

    mesh = make_mesh({"dp": 1, "fsdp": 1, "tp": 2}, devices=jax.devices()[:2])
    sharded = shard_params(PARAMS, mesh, CONFIG)
    # no outer jax.set_mesh: the engine must enter the mesh context itself
    # (its background thread would not inherit a caller's context manager)
    engine = ContinuousBatchingEngine(
        sharded, CONFIG, max_slots=2, capacity=64, chunk=4,
        mesh=mesh, cache_spec=cache_spec(),
    )
    prompt = [9, 8, 7, 6]
    req = engine.submit(prompt, max_new_tokens=6)
    drain(engine, req)
    assert req.all_tokens(timeout=1) == reference_tokens(prompt, 6)


def test_engine_under_sp_mesh():
    """Slot-sharded long-context serving (VERDICT r4 #7): the engine's KV
    cache slot axis shards over an sp axis (sp_cache_spec) and concurrent
    requests still decode exactly the one-shot sampler's tokens."""
    from prime_tpu.parallel.mesh import make_mesh
    from prime_tpu.parallel.sharding import prune_spec, shard_params, sp_cache_spec

    mesh = make_mesh({"dp": 1, "fsdp": 1, "tp": 2, "sp": 2}, devices=jax.devices()[:4])
    sharded = shard_params(PARAMS, mesh, CONFIG)
    engine = ContinuousBatchingEngine(
        sharded, CONFIG, max_slots=2, capacity=64, chunk=4,
        mesh=mesh, cache_spec=prune_spec(sp_cache_spec(), mesh),
    )
    prompts = [[9, 8, 7, 6], [5, 4, 3]]
    reqs = [engine.submit(p, max_new_tokens=6) for p in prompts]
    while not all(r.done for r in reqs):
        engine.tick()
    for p, r in zip(prompts, reqs):
        assert r.all_tokens(timeout=1) == reference_tokens(p, 6)


def test_serve_model_accepts_sequence_parallel():
    """`prime serve --sp N` reaches the engine: serve_model must accept
    sequence_parallel and build the sp-meshed continuous engine with a
    slot-sharded cache spec (this kwarg was dropped in round 4 — the CLI
    raised TypeError before any model loaded)."""
    from prime_tpu.serve import serve_model

    server = serve_model(
        "tiny-test", port=0, slice_name="v5e-8", sequence_parallel=2,
        continuous=True, max_slots=2, slot_capacity=64, chunk=4,
    )
    with server:
        engine = server.generator.engine
        assert engine.mesh is not None and engine.mesh.shape.get("sp") == 2
        assert engine.cache_spec[-1] == "sp"  # slot axis sharded
        import httpx

        response = httpx.post(
            f"{server.url}/v1/chat/completions",
            json={"messages": [{"role": "user", "content": "2+2="}], "max_tokens": 4},
            timeout=120,
        )
        assert response.status_code == 200


def test_device_ngram_proposals_match_backward_scan():
    """The device-resident drafter (propose_ngram_drafts over the history
    ring — the one the fused spec program calls) must propose exactly what
    the O(history) host backward scan it replaced proposed, across random
    histories: most recent earlier bigram occurrence wins, fallbacks repeat
    the trailing token."""
    import random

    from prime_tpu.models.speculative import propose_ngram_drafts

    def scan_reference(history, draft_len, pad_id):
        if len(history) < 2:
            return list(history[-1:]) * draft_len
        t0, t1 = history[-2], history[-1]
        for position in range(len(history) - 3, -1, -1):
            if history[position] == t0 and history[position + 1] == t1:
                window = history[position + 2 : position + 2 + draft_len]
                # a tail-adjacent match repeats the trailing token past the
                # valid length — "the run continues", never ring pads
                return window + [t1] * (draft_len - len(window))
        return [t1] * draft_len

    rng = random.Random(7)
    width, draft_len, pad_id = 40, 4, 0
    for _ in range(40):
        # small alphabet → plenty of repeated bigrams
        history = [rng.randrange(1, 6) for _ in range(rng.randrange(1, 30))]
        ring = history + [pad_id] * (width - len(history))
        drafts = propose_ngram_drafts(
            jnp.asarray([ring], dtype=jnp.int32),
            jnp.asarray([len(history)], dtype=jnp.int32),
            draft_len,
        )
        assert drafts[0].tolist() == scan_reference(history, draft_len, pad_id)


def test_engine_gptoss_matches_sampler():
    """GPT-OSS architecture through the continuous engine: attention sinks
    and the biased clamped-GLU MoE must produce the sampler's exact greedy
    tokens through chunked prefill + slot decode."""
    config = get_config("tiny-gptoss")
    params = init_params(jax.random.PRNGKey(3), config, dtype=jnp.float32)
    prompts = [[5, 42, 100, 7, 61, 9], [17, 3, 88]]
    refs = []
    for p in prompts:
        result = generate(
            params, jnp.asarray([p], dtype=jnp.int32),
            jnp.asarray([len(p)], dtype=jnp.int32), config,
            jax.random.PRNGKey(7), max_new_tokens=10, temperature=0.0,
        )
        refs.append(result.tokens[0].tolist())
    engine = ContinuousBatchingEngine(
        params, config, pad_id=0, max_slots=2, capacity=128, chunk=4,
    )
    reqs = [engine.submit(p, max_new_tokens=10) for p in prompts]
    drain(engine, *reqs)
    for req, ref in zip(reqs, refs):
        assert req.all_tokens(timeout=1) == ref


def test_spec_engine_gptoss_matches_plain():
    """Speculative decoding on the GPT-OSS architecture: the verify window
    runs attention sinks through the chunked-prefill path — greedy tokens
    must equal the plain engine's regardless of what the drafts do."""
    config = get_config("tiny-gptoss")
    params = init_params(jax.random.PRNGKey(3), config, dtype=jnp.float32)
    prompts = [list(range(1, 9)) * 3, [7, 100, 23, 451, 88, 3]]

    def run(speculative):
        engine = ContinuousBatchingEngine(
            params, config, pad_id=0, max_slots=2, capacity=128, chunk=4,
            speculative=speculative, draft_len=4,
        )
        reqs = [engine.submit(p, max_new_tokens=10) for p in prompts]
        drain(engine, *reqs)
        return [r.all_tokens(timeout=1) for r in reqs]

    assert run(False) == run(True)


def test_engine_gemma_style_window_softcap_matches_sampler():
    """Alternating sliding-window + score softcap (Gemma2 physics) through
    the continuous engine: chunked prefill and slot decode must reproduce
    the sampler's greedy tokens, incl. continuations past the window."""
    config = CONFIG.scaled(
        sliding_window=8, sliding_pattern="even", attn_softcap=30.0,
    )
    params = init_params(jax.random.PRNGKey(5), config, dtype=jnp.float32)
    prompts = [list(range(1, 20)), [7, 100, 23, 451, 88, 3]]
    refs = []
    for p in prompts:
        result = generate(
            params, jnp.asarray([p], dtype=jnp.int32),
            jnp.asarray([len(p)], dtype=jnp.int32), config,
            jax.random.PRNGKey(7), max_new_tokens=12, temperature=0.0,
        )
        refs.append(result.tokens[0].tolist())
    engine = ContinuousBatchingEngine(
        params, config, pad_id=0, max_slots=2, capacity=64, chunk=4,
    )
    reqs = [engine.submit(p, max_new_tokens=12) for p in prompts]
    drain(engine, *reqs)
    for req, ref in zip(reqs, refs):
        assert req.all_tokens(timeout=1) == ref


def test_submit_bounded_queue_raises_queue_full():
    """max_queue bounds the pending queue: submissions past it get the typed
    QueueFullError (the 429 the server maps it to carries retry_after)."""
    from prime_tpu.serve.errors import QueueFullError

    engine = make_engine(max_queue=2)
    # not started: nothing consumes the queue, so the bound is exact
    engine.submit([1, 2, 3], max_new_tokens=4)
    engine.submit([1, 2, 4], max_new_tokens=4)
    with pytest.raises(QueueFullError) as excinfo:
        engine.submit([1, 2, 5], max_new_tokens=4)
    assert excinfo.value.retry_after > 0
    assert engine.stats()["max_queue"] == 2
    # working the queue down reopens admission
    for _ in range(40):
        engine.tick()
        stats = engine.stats()
        if stats["queue_depth"] == 0 and stats["active_slots"] == 0:
            break
    engine.submit([1, 2, 6], max_new_tokens=4)


def test_drain_finishes_inflight_then_refuses_new_work():
    """drain(): in-flight requests decode to completion, new submits raise
    DrainingError, and `drained` flips once the engine is quiescent."""
    from prime_tpu.serve.errors import DrainingError

    engine = make_engine()
    req = engine.submit([1, 5, 9, 2], max_new_tokens=6)
    engine.tick()  # admit
    engine.drain()
    assert engine.stats()["state"] == "draining"
    with pytest.raises(DrainingError):
        engine.submit([1, 2, 3], max_new_tokens=4)
    drain(engine, req)  # the in-flight request still completes
    assert req.done and req.error is None
    assert len(req.all_tokens(timeout=1)) == 6
    engine.tick()  # retire the lookahead chunk
    assert engine.drained


def test_max_queue_env_default(monkeypatch):
    monkeypatch.setenv("PRIME_SERVE_MAX_QUEUE", "7")
    engine = make_engine()
    assert engine.max_queue == 7
    monkeypatch.delenv("PRIME_SERVE_MAX_QUEUE")
    assert make_engine().max_queue == 0  # unbounded by default


# ---- a retired slot belongs to nobody: attended at length 0 ----------------------


@pytest.mark.parametrize("overlap", [True, False], ids=["overlap", "sync"])
def test_retired_slot_beside_a_live_one(overlap):
    """A slot that retired while another decodes: the live slot's greedy
    tokens are the one-shot sampler's, the retired row's cache columns below
    its length are byte-identical after further chunks (only the ATTENDED
    length went to 0: the column write still lands at the row's length, which
    does not move), a later admission into that slot answers as a fresh
    engine does, and serve_decode_rows_total counts rows x steps by state."""
    import numpy as np

    engine = make_engine(max_slots=2, overlap=overlap)
    masks = []
    dispatch = engine._dispatch_decode

    def recording_dispatch():
        masks.append(engine._active.copy())
        dispatch()

    engine._dispatch_decode = recording_dispatch
    short = engine.submit([3, 8, 21, 144], max_new_tokens=3)
    long = engine.submit([7, 7, 101, 19, 250, 6], max_new_tokens=40)
    drain(engine, short)
    slot = next(s for s in range(2) if s not in engine._requests)
    for _ in range(2):  # a chunk dispatched on the stale mask lands
        engine.tick()
    assert not long.done and not engine._active[slot]
    length = int(np.asarray(engine._cache.lengths)[slot])
    assert length >= 4 + 3 - 1
    below = [np.asarray(leaf[:, slot, :, :, :length]) for leaf in (engine._cache.k, engine._cache.v)]

    drain(engine, long)
    assert long.all_tokens(timeout=1) == reference_tokens([7, 7, 101, 19, 250, 6], 40)
    assert short.all_tokens(timeout=1) == reference_tokens([3, 8, 21, 144], 3)
    assert int(np.asarray(engine._cache.lengths)[slot]) == length
    for before, leaf in zip(below, (engine._cache.k, engine._cache.v)):
        np.testing.assert_array_equal(before, np.asarray(leaf[:, slot, :, :, :length]))
    # the column at the row's length was rewritten by every chunk meanwhile
    retired_steps = sum(int((~m).sum()) for m in masks) * engine.chunk
    live_steps = sum(int(m.sum()) for m in masks) * engine.chunk
    assert retired_steps > 0 and live_steps > 0
    rows = engine.registry.get("serve_decode_rows_total")
    assert rows.value(state="live") == live_steps
    assert rows.value(state="retired") == retired_steps
    assert live_steps + retired_steps == len(masks) * 2 * engine.chunk

    # the freed slot serves a new request as a fresh engine does
    again = engine.submit([9, 9, 12, 300, 41], max_new_tokens=6)
    drain(engine, again)
    assert again.all_tokens(timeout=1) == reference_tokens([9, 9, 12, 300, 41], 6)
    engine.shutdown()


@pytest.mark.parametrize("preset", ["tiny-test", "tiny-moe", "tiny-mla", "tiny-longcat", "tiny-gptoss"])
def test_decode_step_masks_only_the_attended_length(preset):
    """forward(decode=True) with rows masked out: the live rows' logits are
    bit-equal to the unmasked step's, the cache below every row's length is
    untouched, the live rows' new columns are the unmasked step's, the carried
    lengths advance for every row as before (the engine holds a retired row's
    back), and nothing is NaN. The kernel's work list for the masked lengths
    is made once a step, outside the layer scan."""
    import numpy as np

    from prime_tpu.models.llama import forward, init_cache

    config = get_config(preset)
    params = init_params(jax.random.PRNGKey(1), config, dtype=jnp.float32)
    batch, capacity = 4, 64
    cache = init_cache(config, batch, capacity, dtype=jnp.float32)
    prompt = jax.random.randint(jax.random.PRNGKey(2), (batch, 12), 3, config.vocab_size)
    _, cache = forward(params, prompt, config, cache=cache)
    cache = cache._replace(lengths=jnp.asarray([12, 5, 9, 12], jnp.int32))
    tok = jnp.asarray([[4], [5], [6], [7]], jnp.int32)
    live = jnp.asarray([True, False, True, False])

    def step(mask):
        return forward(
            params, tok, config, positions=cache.lengths[:, None], cache=cache,
            decode=True, attn_impl="xla", token_mask=mask,
        )

    (logits, new), (ref_logits, ref_new) = step(live[:, None]), step(None)
    rows = np.asarray(live)
    np.testing.assert_array_equal(np.asarray(logits)[rows], np.asarray(ref_logits)[rows])
    assert np.isfinite(np.asarray(logits)).all()
    np.testing.assert_array_equal(np.asarray(new.lengths), np.asarray(ref_new.lengths))
    for leaf, ref_leaf, old in zip(new[:2], ref_new[:2], cache[:2]):
        leaf, ref_leaf, old = (np.asarray(a) for a in (leaf, ref_leaf, old))
        np.testing.assert_array_equal(leaf[:, rows], ref_leaf[:, rows])
        for row, n in enumerate(np.asarray(cache.lengths)):
            np.testing.assert_array_equal(leaf[:, row, ..., :n], old[:, row, ..., :n])

    def primitives(mask, impl):
        """(primitives outside every loop, primitives inside one) of the step."""
        outside, inside = [], []

        def walk(jaxpr, names):
            for eqn in jaxpr.eqns:
                names.append(eqn.primitive.name)
                looped = inside if eqn.primitive.name in ("scan", "while") else names
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    walk(sub, looped)

        step_fn = lambda: forward(
            params, tok, config, positions=cache.lengths[:, None], cache=cache,
            decode=True, attn_impl=impl, token_mask=mask,
        )
        walk(jax.make_jaxpr(step_fn)().jaxpr, outside)
        return outside, inside

    # the kernels' work lists (one cumsum over the rows each; the experts' routing
    # has cumsums of its own, on either path) are made once a step, outside the
    # layer scan, where the rows are masked and the kernels run: the column
    # write's always, the attention's unless the config's layers alternate a
    # window, which leaves it to each layer's call
    (xla_out, xla_in), (outside, inside) = (
        primitives(live[:, None], impl) for impl in ("xla", "pallas")
    )
    assert "pallas_call" in inside and "pallas_call" not in xla_in
    made = (outside.count("cumsum") - xla_out.count("cumsum"), inside.count("cumsum") - xla_in.count("cumsum"))
    assert made == ((1, 1) if config.sliding_window else (2, 0))


def test_decode_attn_steps_counter_follows_the_lengths(monkeypatch):
    """serve_decode_attn_steps_total over the synced decode chunks: walked is
    the grid steps the decode-attention kernel's calls made (one for every
    cache block that holds a live column; one for a call whose rows are all
    dead), spared what is left of the most they could have made, the gauge
    serve_decode_attn_grid_steps a call. 2 slots x 384 columns in blocks of
    128: a prompt of 10 tokens walks 1 step of 6 a call, one of 140 walks 2."""
    monkeypatch.setenv("PRIME_TPU_PALLAS_INTERPRET", "1")
    engine = make_engine(max_slots=2, capacity=384, attn_impl="pallas", overlap=False)
    assert engine.registry.get("serve_decode_attn_grid_steps").value() == 2 * 3
    synced = []
    sync = engine._sync_decode
    engine._sync_decode = lambda: (synced.append(1), sync())[1]
    steps = engine.registry.get("serve_decode_attn_steps_total")

    def counted():
        return steps.value(kind="walked"), steps.value(kind="spared")

    short = engine.submit(list(range(3, 13)), max_new_tokens=9)
    drain(engine, short)
    calls = len(synced) * engine.chunk * CONFIG.n_layers
    assert calls > 0 and counted() == (calls, calls * 5)

    long = engine.submit(list(range(3, 143)), max_new_tokens=9)
    drain(engine, long)
    more = len(synced) * engine.chunk * CONFIG.n_layers - calls
    assert more > 0 and counted() == (calls + 2 * more, calls * 5 + 4 * more)
    assert long.all_tokens(timeout=1) == reference_tokens(list(range(3, 143)), 9)
    engine.shutdown()


def test_decode_column_writes_counter_follows_the_mask(monkeypatch):
    """serve_decode_column_writes_total over the synced decode chunks: written
    is the rows the column-write kernel's calls wrote (the dispatch mask's live
    rows, a call a layer and step), spared the rest of the slots' rows it was
    offered: the mask serve_decode_rows_total counts, so the two agree row for
    row. 2 slots: one request alone writes one row of two a call, two together
    both. The benchmark's reader (``column_write_spared_share``) over the same
    snapshots reads the spared share, and nothing where there is no counter."""
    from benchmark.layer_metrics import column_write_spared_share
    from benchmark.systems.serve_http import counter_delta

    monkeypatch.setenv("PRIME_TPU_PALLAS_INTERPRET", "1")
    engine = make_engine(max_slots=2, capacity=384, attn_impl="pallas", overlap=False)
    writes = engine.registry.get("serve_decode_column_writes_total")
    rows = engine.registry.get("serve_decode_rows_total")
    before = engine.registry.snapshot()

    def counted():
        return writes.value(kind="written"), writes.value(kind="spared")

    def share(pair):
        return column_write_spared_share.read({"counters": pair, "counter_delta": counter_delta})

    alone = engine.submit(list(range(3, 13)), max_new_tokens=9)
    drain(engine, alone)
    written, spared = counted()
    assert written == spared > 0
    after = engine.registry.snapshot()
    assert share((before, after)) == 50.0
    assert share((after, after)) is None and share(({}, {})) is None  # an idle window; the parent
    pair = [engine.submit(list(range(3 + i, 13 + i)), max_new_tokens=9) for i in range(2)]
    drain(engine, *pair)
    assert counted()[0] - written > counted()[1] - spared >= 0
    assert counted() == (
        rows.value(state="live") * CONFIG.n_layers, rows.value(state="retired") * CONFIG.n_layers
    )
    engine.shutdown()


@pytest.mark.parametrize("preset", ["tiny-test", "tiny-longcat", "tiny-phi4flash", "tiny-qwen3next"])
def test_column_writes_count_the_live_rows(preset, monkeypatch):
    """forward(decode=True, return_column_writes=True) under the kernel's gate:
    (live rows, rows) x the step's column-write calls (a cache row each; for the
    sambay block the rings' and the row's), every row without a mask, zeros on
    the XLA path. A row masked out keeps its cache bytes, the slot its column
    would have taken included, and a live row's are the unmasked step's."""
    import numpy as np

    from prime_tpu.models.llama import forward, init_cache

    monkeypatch.setenv("PRIME_TPU_PALLAS_INTERPRET", "1")
    config = get_config(preset)
    params = init_params(jax.random.PRNGKey(1), config, dtype=jnp.float32)
    batch, capacity = 4, 256
    cache = init_cache(config, batch, capacity, dtype=jnp.float32)
    prompt = jax.random.randint(jax.random.PRNGKey(2), (batch, 12), 3, config.vocab_size)
    _, cache = forward(params, prompt, config, cache=cache)
    cache = cache._replace(lengths=jnp.asarray([12, 5, 9, 12], jnp.int32))
    tok = jnp.asarray([[4], [5], [6], [7]], jnp.int32)

    def step(mask, impl="pallas"):
        _, new, writes = forward(
            params, tok, config, positions=cache.lengths[:, None], cache=cache, decode=True,
            attn_impl=impl, token_mask=None if mask is None else jnp.asarray(mask)[:, None],
            return_column_writes=True,
        )
        return new, [int(n) for n in np.asarray(writes)]

    calls = config.window_pairs + 1 if config.block == "sambay" else cache.k.shape[0]
    live = [True, False, True, False]
    (masked, writes), (unmasked, every) = step(live), step(None)
    assert writes == [2 * calls, batch * calls] and every == [batch * calls] * 2
    assert step(live, "xla")[1] == step(None, "xla")[1] == [0, 0]
    written = ("k",) if config.mla else ("k", "v")  # the latent's v is a dummy
    written += ("ring_k", "ring_v") if config.block == "sambay" else ()
    for name in written:
        got, ref, old = (np.asarray(getattr(c, name)) for c in (masked, unmasked, cache))
        assert not np.array_equal(ref, old)
        for row, alive in enumerate(live):
            np.testing.assert_array_equal(got[:, row], (ref if alive else old)[:, row])


@pytest.mark.parametrize("preset", ["tiny-test", "tiny-longcat", "tiny-phi4flash", "tiny-qwen3next"])
def test_a_batch_of_full_rows_walks_every_step(preset, monkeypatch):
    """forward(decode=True, return_attn_steps=True) with every row one column
    short of its capacity: the kernel's calls walk the whole static grid
    (walked / most = 1.0, nothing spared), the vector counting a call a cache
    row the step attends over (the rings' and the row's eight for the sambay
    block); with half the rows masked out, half of it."""
    import numpy as np

    from prime_tpu.models.llama import forward, init_cache

    monkeypatch.setenv("PRIME_TPU_PALLAS_INTERPRET", "1")
    monkeypatch.setenv("PRIME_TPU_BLOCK_C", "128")
    config = get_config(preset)
    params = init_params(jax.random.PRNGKey(1), config, dtype=jnp.float32)
    batch, capacity = 4, 256
    cache = init_cache(config, batch, capacity, dtype=jnp.float32)
    cache = cache._replace(lengths=jnp.full((batch,), capacity - 1, jnp.int32))
    tok = jnp.asarray([[4], [5], [6], [7]], jnp.int32)

    def steps(mask):
        *_, attn_steps = forward(
            params, tok, config, positions=cache.lengths[:, None], cache=cache,
            decode=True, attn_impl="pallas", token_mask=jnp.asarray(mask)[:, None],
            return_attn_steps=True,
        )
        return [int(n) for n in np.asarray(attn_steps)]

    walked, most = steps([True] * 4)
    assert walked == most > 0
    if config.block == "sambay":
        ring_blocks = -(-config.sliding_window // 128)
        assert most == batch * (config.window_pairs * ring_blocks + (1 + config.cross_pairs) * 2)
    else:
        assert most == batch * 2 * cache.k.shape[0]
    assert steps([True, False, True, False]) == [most // 2, most]


# ---- the chunk cap and the wave width: one rule from shapes (prefill_shapes) ----

# the six benchmark configurations as their cells serve them: (preset, slot
# capacity, max_wave asked) -> (chunk cap, widest wave, row capacities). Those
# that keep 512 keep the parent's wave width and its row set, number for number:
# a change to the rule that moves another configuration's programs fails here.
_ROWS_512_2048 = [16, 32, 64, 128, 256, 512, 1024, 1536, 2048]
_ROWS_512_4096 = _ROWS_512_2048 + [2560, 3072, 3584, 4096]
RULE_TABLE = {
    "qwen3-next-ep2 as served": (
        "qwen3-next-ep2", 4096, 1, 2048, 1, [16, 32, 64, 128, 256, 512, 1024, 2048, 4096]),
    "qwen3-next-ep2 at two rows a wave": (
        "qwen3-next-ep2", 4096, 2, 2048, 2, [16, 32, 64, 128, 256, 512, 1024, 2048, 4096]),
    "qwen3-next-ep2 at the width its shapes allow": (
        "qwen3-next-ep2", 4096, None, 512, 8, _ROWS_512_4096),
    "longcat-flash-ep32": ("longcat-flash-ep32", 2048, None, 512, 4, _ROWS_512_2048),
    "qwen3-4b": ("qwen3-4b", 2048, None, 512, 8, _ROWS_512_2048),
    "phi4-mini-flash": ("phi4-mini-flash", 4096, None, 512, 2, _ROWS_512_4096),
    "olmo-hybrid-7b-pp2": ("olmo-hybrid-7b-pp2", 2048, 1, 512, 1, _ROWS_512_2048),
    # layers of two kinds: the full layers' 48 heads over the row bind, not the
    # window layers' 72 over a ring and the chunk (tests/test_laguna.py)
    "laguna-s-ep2": ("laguna-s-ep2", 8192, None, 512, 1, _ROWS_512_4096 + list(range(4608, 8193, 512))),
}


@pytest.mark.parametrize("case", sorted(RULE_TABLE))
def test_the_chunk_cap_and_the_wave_width_follow_from_shapes(case):
    """An expert model that gives wave width up gets chunk length for it, until
    an expert's rows a chunk fill one row tile (2048 x 10 / 512 = 40); one that
    spends the score budget on width, a dense model, or any model served at the
    width its shapes allow keeps 512 tokens, the parent's width and its rows."""
    from prime_tpu.serve.engine import prefill_shapes, row_capacities

    preset, capacity, asked, chunk, wave, rows = RULE_TABLE[case]
    config = get_config(preset)
    got = prefill_shapes(config, capacity, max_wave=asked, dropless_experts=config.is_moe)
    assert got == (chunk, wave)
    assert row_capacities(chunk, capacity) == rows
    # the parent's width: the score budget at a 512-token chunk over a whole slot
    widest = 2 ** ((2**30 // (4 * config.n_heads * 512 * capacity)).bit_length() - 1)
    assert wave == (widest if asked is None else min(widest, asked))
    # experts that keep the capacity einsum (a mesh, quantized experts) keep 512
    assert prefill_shapes(config, capacity, max_wave=asked)[0] == 512


def test_the_rule_refuses_no_prompt_that_fit_and_an_explicit_chunk_wins():
    """Rows are multiples of the cap, so the chunk doubles only while a slot is
    a whole number of such chunks; ``prefill_chunk=`` stands as given, and sets
    the wave width as before."""
    from prime_tpu.serve.engine import prefill_shapes

    config = get_config("qwen3-next-ep2")
    rule = lambda capacity, **kw: prefill_shapes(  # noqa: E731
        config, capacity, dropless_experts=True, **kw)
    assert rule(4096, max_wave=1) == (2048, 1)
    assert rule(3072, max_wave=1) == (1024, 1)  # 3072 is no whole number of 2048s
    assert rule(3000, max_wave=1) == (512, 1)
    assert rule(8192, max_wave=1) == (2048, 1)  # 40 rows an expert: a full tile, stop
    assert rule(4096, max_wave=1, prefill_chunk=512) == (512, 1)
    assert rule(4096, prefill_chunk=4096) == (4096, 1)
    assert rule(4096, prefill_chunk=3) == (16, 256)
    with pytest.raises(ValueError, match="max_wave"):
        rule(4096, max_wave=0)
    # the engine reads the same rule: tiny-test is dense, tiny-moe's experts see
    # 512 x 2 / 4 rows a chunk, and a slot of 128 holds no longer chunk anyway
    assert make_engine().prefill_chunk == 512
    assert make_engine(prefill_chunk=32).prefill_chunk == 32
    assert make_engine(prefill_chunk=32, capacity=2048).max_wave == 1024  # 4 heads


@pytest.mark.parametrize("cap, capacity", [(1024, 4096), (2048, 4096), (4096, 4096), (1024, 3072)])
def test_chunk_plans_stay_inside_their_row_at_long_caps(cap, capacity):
    """At caps of 1024, 2048 and the whole slot: every chunk is a power of two
    no longer than the cap, aligned to its size, divides the row, and the plan
    covers the prompt without a write past the row, from 0 or from a prefix
    hit's block-aligned offset."""
    import numpy as np

    from prime_tpu.serve.engine import chunk_plan, row_capacities, row_capacity_for

    rng = np.random.default_rng(cap + capacity)
    lengths = {1, 15, 16, 17, cap - 1, cap, cap + 1, capacity - 1, capacity,
               *rng.integers(1, capacity + 1, size=200).tolist()}
    for length in sorted(n for n in lengths if n <= capacity):
        row = row_capacity_for(length, cap, capacity)
        assert length <= row <= capacity and row in row_capacities(cap, capacity)
        for start in {0, 16 * int(rng.integers(0, (length - 1) // 16 + 1))}:
            plan = chunk_plan(start, length, cap, row)
            at = start
            for off, size in plan:
                assert off == at and size & (size - 1) == 0 and size <= cap
                assert off % size == 0 and row % size == 0 and off + size <= row
                at += size
            assert at >= length and (not plan or at - plan[-1][1] < length)
    with pytest.raises(ValueError, match=f"prefill chunk \\({cap}\\)"):
        row_capacity_for(capacity + 1, cap, capacity)
