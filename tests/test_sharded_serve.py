"""Sharded-replica serving tests (forced multi-device CPU mesh).

The load-bearing property mirrors test_engine.py's: whatever the topology,
a greedy request decoded by the engine produces exactly the tokens the
single-chip engine (and the one-shot sampler) produces. Everything here
runs on the virtual CPU mesh the conftest forces (8 devices; the CI
serve-smoke mesh leg re-runs it at ``host_platform_device_count=4``) — the
engine builds a 4-device ``(dp, fsdp, tp)`` mesh from the declarative spec
and shards params + paged KV itself (docs/architecture.md "Sharded
replica").
"""

import jax
import jax.numpy as jnp
import pytest

from prime_tpu.models import get_config
from prime_tpu.models.llama import init_params
from prime_tpu.models.sampler import generate
from prime_tpu.serve.engine import ContinuousBatchingEngine
from prime_tpu.serve.mesh_config import ServeMeshConfig, parse_mesh_spec

CONFIG = get_config("tiny-test")
PARAMS = init_params(jax.random.PRNGKey(0), CONFIG, dtype=jnp.float32)
MESH_SPEC = "dp=1,fsdp=2,tp=2"

requires_multichip = pytest.mark.skipif(
    jax.device_count() < 4,
    reason="needs >=4 devices (XLA_FLAGS=--xla_force_host_platform_device_count=4)",
)


@pytest.fixture(autouse=True)
def _default_serve_env(monkeypatch):
    """Pin the env-driven engine defaults (same rationale as test_engine)."""
    monkeypatch.delenv("PRIME_SERVE_OVERLAP", raising=False)
    monkeypatch.delenv("PRIME_SERVE_WARMUP", raising=False)
    monkeypatch.delenv("PRIME_SERVE_MESH", raising=False)
    monkeypatch.delenv("PRIME_SERVE_SPEC", raising=False)
    monkeypatch.delenv("PRIME_SERVE_DRAFT_LEN", raising=False)
    monkeypatch.delenv("PRIME_SERVE_PREFIX_CACHE_MB", raising=False)
    monkeypatch.delenv("PRIME_SERVE_PREFIX_CACHE_HOST_MB", raising=False)


def reference_tokens(prompt_ids: list[int], n: int) -> list[int]:
    result = generate(
        PARAMS, jnp.asarray([prompt_ids], dtype=jnp.int32),
        jnp.asarray([len(prompt_ids)], dtype=jnp.int32), CONFIG,
        jax.random.PRNGKey(7), max_new_tokens=n, temperature=0.0,
    )
    return result.tokens[0].tolist()


def make_engine(**kw) -> ContinuousBatchingEngine:
    kw.setdefault("max_slots", 4)
    kw.setdefault("capacity", 128)
    kw.setdefault("chunk", 4)
    kw.setdefault("prefix_cache_mb", 0)
    return ContinuousBatchingEngine(PARAMS, CONFIG, **kw)


def drain(engine, *requests, max_ticks=300):
    for _ in range(max_ticks):
        engine.tick()
        if all(r.done for r in requests):
            return
    raise AssertionError("requests did not finish")


# two waves of shared-prefix prompts: long enough (>= 2 blocks) that the
# store/hit path engages when the cache is on, divergent tails so the radix
# tree actually branches
_PREAMBLE = [(7 * i) % 50 + 3 for i in range(34)]
WAVE_PROMPTS = [
    _PREAMBLE + [61, 62, 63],
    _PREAMBLE + [64, 65],
    [9, 8, 7, 6, 5, 4, 3, 2],
]


# ---- declarative mesh config -------------------------------------------------


def test_parse_mesh_spec_explicit_and_absorbing():
    cfg = parse_mesh_spec("dp=1,fsdp=2,tp=2", 8)
    assert cfg.axes == {"dp": 1, "fsdp": 2, "tp": 2}
    assert cfg.total_devices == 4
    assert cfg.spec == "dp=1,fsdp=2,tp=2"
    # bare names: sizes default to 1 except the LAST unsized axis, which
    # absorbs every remaining device
    cfg = parse_mesh_spec("dp,fsdp,tp", 8)
    assert cfg.axes == {"dp": 1, "fsdp": 1, "tp": 8}
    cfg = parse_mesh_spec("fsdp=2,tp", 8)
    assert cfg.axes == {"fsdp": 2, "tp": 4}
    # fully sized specs may describe a SUB-slice of the host (build() takes
    # the first total_devices devices) — only an absorbing axis needs the
    # device count to factor cleanly
    assert parse_mesh_spec("dp=1,fsdp=2,tp=2", 6).total_devices == 4
    assert parse_mesh_spec("", 8) is None
    assert parse_mesh_spec("  ", 8) is None


def test_parse_mesh_spec_rejects_junk():
    with pytest.raises(ValueError, match="unknown mesh axis"):
        parse_mesh_spec("dp=1,warp=2", 8)
    with pytest.raises(ValueError, match="integer"):
        parse_mesh_spec("tp=two", 8)
    with pytest.raises(ValueError, match="positive"):
        parse_mesh_spec("tp=0", 8)
    with pytest.raises(ValueError, match="available"):
        parse_mesh_spec("tp=16", 8)  # fully sized but bigger than the host
    with pytest.raises(ValueError, match="divide"):
        parse_mesh_spec("dp,tp=3", 8)  # absorbing axis can't resolve 8/3
    with pytest.raises(ValueError, match="duplicate"):
        ServeMeshConfig(("tp", "tp"), (2, 2))
    with pytest.raises(ValueError, match="equal rank"):
        ServeMeshConfig(("dp", "tp"), (2,))


@requires_multichip
def test_mesh_config_build_uses_prefix_of_devices():
    mesh = parse_mesh_spec(MESH_SPEC, jax.device_count()).build()
    assert mesh.size == 4
    assert dict(mesh.shape) == {"dp": 1, "fsdp": 2, "tp": 2}


def test_mesh_config_build_rejects_oversize():
    cfg = ServeMeshConfig(("tp",), (jax.device_count() * 2,))
    with pytest.raises(ValueError, match="devices"):
        cfg.build()


# ---- greedy bit-identity matrix: sharded vs single-chip ----------------------


@requires_multichip
@pytest.mark.parametrize("overlap", [True, False], ids=["overlap", "sync"])
@pytest.mark.parametrize("cache_mb", [0, 1], ids=["nocache", "prefixcache"])
def test_sharded_bit_identity_matrix(overlap, cache_mb):
    """Greedy outputs on the 4-device mesh are bit-identical to the
    single-chip engine (itself pinned to the one-shot sampler) across the
    overlap x prefix-cache matrix — two waves, so the cached leg's second
    wave actually assembles from the sharded radix cache."""

    def run(**engine_kw):
        engine = make_engine(overlap=overlap, prefix_cache_mb=cache_mb, **engine_kw)
        out = []
        for _ in range(2):  # second wave prefix-hits when the cache is on
            reqs = [engine.submit(p, max_new_tokens=8) for p in WAVE_PROMPTS]
            drain(engine, *reqs)
            out.append([r.all_tokens(timeout=5) for r in reqs])
        return engine, out

    sharded, sharded_out = run(mesh_config=MESH_SPEC)
    assert sharded.mesh_devices == 4
    assert sharded.attn_impl == "sharded"
    single, single_out = run()
    assert single.mesh_devices == 1
    assert sharded_out == single_out
    for prompt, tokens in zip(WAVE_PROMPTS, sharded_out[0]):
        assert tokens == reference_tokens(prompt, 8)
    if cache_mb:
        # the sharded cache really served the second wave (no silent miss)
        assert sharded.prefix_hits >= 2
        assert sharded.prefix_hits == single.prefix_hits


@requires_multichip
def test_sharded_warmup_program_set_pin():
    """AOT warmup covers the sharded program set: the bounded program
    shapes are topology-independent, so the sharded engine must execute
    EXACTLY as many warmup programs as the single-chip engine — a drifting
    count means a program real traffic compiles mid-pipeline that warmup
    missed (or warmup compiling shapes traffic never runs). Warmup must
    also leave the sharded engine cold-state clean: the first real request
    after it still matches the reference."""
    sharded = make_engine(prefix_cache_mb=1, capacity=64, mesh_config=MESH_SPEC)
    single = make_engine(prefix_cache_mb=1, capacity=64)
    assert sharded.warmup() == single.warmup()
    assert int(sharded.registry.values()["serve_warmup_programs"]) > 0
    req = sharded.submit(WAVE_PROMPTS[2], max_new_tokens=6)
    drain(sharded, req)
    assert req.all_tokens(timeout=5) == reference_tokens(WAVE_PROMPTS[2], 6)


# ---- speculative decoding on the mesh ----------------------------------------


# periodic + aperiodic + the shared-prefix pair: drafts land on the first,
# miss on the second, and the pair's second wave exercises spec + cache hit
SPEC_PROMPTS = [
    list(range(1, 9)) * 2,
    [7, 100, 23, 451, 88, 3],
    _PREAMBLE + [61, 62],
    _PREAMBLE + [63],
]


@requires_multichip
@pytest.mark.parametrize("overlap", [True, False], ids=["overlap", "sync"])
def test_sharded_spec_bit_identity(overlap):
    """Speculative decoding spans the mesh: the fused propose+verify program
    (history ring + draft buffers placed with the (dp, fsdp, tp) layout)
    emits greedy tokens bit-identical to the single-chip spec engine, to the
    serial spec loop, and to non-spec decode — two waves with the prefix
    cache on, so the second wave assembles from the sharded radix cache
    while speculating."""

    def run(**engine_kw):
        engine = make_engine(
            overlap=overlap, prefix_cache_mb=1, min_prefix=16, **engine_kw
        )
        waves = []
        for _ in range(2):
            reqs = [engine.submit(list(p), max_new_tokens=8) for p in SPEC_PROMPTS]
            drain(engine, *reqs)
            engine.tick()  # drain any lookahead chunk
            waves.append([r.all_tokens(timeout=5) for r in reqs])
        return engine, waves

    sharded, sharded_out = run(speculative=True, mesh_config=MESH_SPEC)
    assert sharded.mesh_devices == 4 and sharded.speculative
    single, single_out = run(speculative=True)
    plain, plain_out = run()
    assert sharded_out == single_out == plain_out
    for prompt, tokens in zip(SPEC_PROMPTS, sharded_out[0]):
        assert tokens == reference_tokens(list(prompt), 8)
    # the sharded cache served the second wave while speculating
    assert sharded.prefix_hits >= 2
    assert sharded.prefix_hits == single.prefix_hits
    # acceptance evidence from the sharded verify windows
    assert sharded.stats()["spec_accept_ratio"] > 0


@requires_multichip
def test_sharded_spec_warmup_program_set_pin():
    """The spec program set is topology-independent too: a speculative
    sharded engine executes exactly the speculative single-chip engine's
    warmup program count (fused spec dispatch + hist-seed wave widths
    included)."""
    sharded = make_engine(
        prefix_cache_mb=1, capacity=64, speculative=True, mesh_config=MESH_SPEC
    )
    single = make_engine(prefix_cache_mb=1, capacity=64, speculative=True)
    assert sharded.warmup() == single.warmup()
    req = sharded.submit(SPEC_PROMPTS[0], max_new_tokens=6)
    drain(sharded, req)
    assert req.all_tokens(timeout=5) == reference_tokens(SPEC_PROMPTS[0], 6)


@requires_multichip
def test_sharded_spec_dispatch_spans_carry_mesh_devices(tmp_path):
    """The serve.spec_dispatch span (satellite obs) stamps mesh_devices on a
    sharded engine, read back from a real JSONL sink."""
    import json

    from prime_tpu.obs.trace import TRACER

    sink = tmp_path / "trace.jsonl"
    engine = make_engine(speculative=True, mesh_config=MESH_SPEC)
    prev = TRACER.reconfigure(enabled=True, sink_path=str(sink))
    try:
        req = engine.submit(SPEC_PROMPTS[0], max_new_tokens=4)
        drain(engine, req)
        engine.tick()
    finally:
        TRACER.reconfigure(**prev)
    spans = [
        json.loads(line)["attrs"]
        for line in sink.read_text().splitlines()
        if json.loads(line)["name"] == "serve.spec_dispatch"
    ]
    assert spans and all(a.get("mesh_devices") == 4 for a in spans)
    assert all(a.get("draft_len") == 4 for a in spans)


# ---- mesh observability ------------------------------------------------------


@requires_multichip
def test_sharded_stats_and_gauge_report_mesh():
    engine = make_engine(mesh_config=MESH_SPEC)
    stats = engine.stats()
    assert stats["mesh_devices"] == 4
    assert stats["mesh_axes"] == {"dp": 1, "fsdp": 2, "tp": 2}
    assert int(engine.registry.values()["serve_mesh_devices"]) == 4
    # single-chip engines report the same keys with the trivial values
    single = make_engine()
    stats = single.stats()
    assert stats["mesh_devices"] == 1
    assert stats["mesh_axes"] == {}
    assert int(single.registry.values()["serve_mesh_devices"]) == 1


@requires_multichip
def test_sharded_healthz_reports_mesh_shape():
    from prime_tpu.evals.tokenizer import ByteTokenizer
    from prime_tpu.serve.engine import EngineBackend
    from prime_tpu.serve.server import InferenceServer

    import httpx

    engine = make_engine(mesh_config=MESH_SPEC)
    with engine:
        backend = EngineBackend(engine, ByteTokenizer())
        with InferenceServer("tiny-test", backend, port=0) as srv:
            payload = httpx.get(f"{srv.url}/healthz").json()
            assert payload["mesh_devices"] == 4
            assert payload["mesh"] == {"dp": 1, "fsdp": 2, "tp": 2}


def test_single_chip_healthz_omits_mesh():
    from prime_tpu.evals.tokenizer import ByteTokenizer
    from prime_tpu.serve.engine import EngineBackend
    from prime_tpu.serve.server import InferenceServer

    import httpx

    engine = make_engine()
    with engine:
        backend = EngineBackend(engine, ByteTokenizer())
        with InferenceServer("tiny-test", backend, port=0) as srv:
            payload = httpx.get(f"{srv.url}/healthz").json()
            assert "mesh_devices" not in payload
            assert "mesh" not in payload


@requires_multichip
def test_sharded_dispatch_spans_carry_mesh_devices(tmp_path):
    import json

    from prime_tpu.obs.trace import TRACER

    sink = tmp_path / "trace.jsonl"
    engine = make_engine(mesh_config=MESH_SPEC)
    prev = TRACER.reconfigure(enabled=True, sink_path=str(sink))
    try:
        req = engine.submit(WAVE_PROMPTS[2], max_new_tokens=4)
        drain(engine, req)
    finally:
        TRACER.reconfigure(**prev)
    by_name: dict[str, list[dict]] = {}
    for line in sink.read_text().splitlines():
        span = json.loads(line)
        by_name.setdefault(span["name"], []).append(span["attrs"])
    assert by_name["serve.prefill"] and all(
        a.get("mesh_devices") == 4 for a in by_name["serve.prefill"]
    )
    device_spans = by_name.get("serve.dispatch", [])
    assert device_spans and all(a.get("mesh_devices") == 4 for a in device_spans)


# ---- env knob + host-tier gate ----------------------------------------------


@requires_multichip
def test_prime_serve_mesh_env_wiring(monkeypatch):
    monkeypatch.setenv("PRIME_SERVE_MESH", MESH_SPEC)
    engine = make_engine()
    assert engine.mesh_devices == 4
    assert engine.mesh_axes == {"dp": 1, "fsdp": 2, "tp": 2}
    # explicit kwarg beats env; empty env means single-chip
    monkeypatch.setenv("PRIME_SERVE_MESH", "")
    assert make_engine().mesh_devices == 1
    monkeypatch.delenv("PRIME_SERVE_MESH")
    assert make_engine(mesh_config="fsdp=2,tp=2").mesh_devices == 4


@requires_multichip
def test_host_tier_gate_is_explicit_in_stats_and_gauge():
    """Satellite: configuring a prefix-cache host tier on a multi-device
    mesh must surface as the serve_prefix_host_tier_disabled gauge and the
    prefix_host_tier_disabled stats key — not only a log warning."""
    with pytest.warns(UserWarning, match="host spill tier"):
        gated = make_engine(
            prefix_cache_mb=1, prefix_cache_host_mb=2, mesh_config=MESH_SPEC
        )
    assert gated.prefix_cache_host_mb == 0.0
    assert int(gated.registry.values()["serve_prefix_host_tier_disabled"]) == 1
    assert gated.stats()["prefix_host_tier_disabled"] == 1
    # single-chip engines keep the tier and report 0
    kept = make_engine(prefix_cache_mb=1, prefix_cache_host_mb=2)
    assert kept.prefix_cache_host_mb == 2
    assert int(kept.registry.values()["serve_prefix_host_tier_disabled"]) == 0
    assert kept.stats()["prefix_host_tier_disabled"] == 0


def test_serve_model_mesh_validation():
    from prime_tpu.serve import serve_model

    with pytest.raises(ValueError, match="--continuous"):
        serve_model("tiny-test", port=0, mesh="tp=2")
    with pytest.raises(ValueError, match="one"):
        serve_model("tiny-test", port=0, continuous=True, mesh="tp=2", slice_name="v5e-8")


# ---- perf delta: MULTICHIP rounds render as their own rows -------------------


def test_perf_delta_multichip_rounds_own_rows(tmp_path):
    import json

    from prime_tpu.loadgen.perf_delta import delta_table, load_all_rounds

    (tmp_path / "BENCH_r01.json").write_text(json.dumps(
        {"value": 100.0, "metric": "decode_tokens_per_sec", "serve_tok_s": 50.0}
    ))
    # legacy dryrun wrapper (rounds 1-5's shape)
    (tmp_path / "MULTICHIP_r01.json").write_text(json.dumps(
        {"n_devices": 8, "rc": 0, "ok": True, "skipped": False, "tail": "..."}
    ))
    # schema-2 sharded loadgen record (this PR's shape)
    (tmp_path / "MULTICHIP_loadgen_cpu_r02.json").write_text(json.dumps(
        {
            "schema": 2, "metric": "serve_sharded_tok_s (...)", "value": 34.9,
            "unit": "tokens/s", "backend": "cpu",
            "mesh": {"dp": 1, "fsdp": 2, "tp": 2}, "mesh_devices": 4,
            "loadgen": {"headline": {"tok_s": 34.9}, "scenarios": [
                {"scenario": "smoke", "tok_s": 34.9, "ttft_s": {"p50": 0.75}},
            ]},
        }
    ))
    # a full bench.py record committed as a MULTICHIP round: the sharded
    # headline is serve_sharded_tok_s — "value" is the single-chip decode
    # headline and must NOT render as the multichip number
    (tmp_path / "MULTICHIP_r03.json").write_text(json.dumps(
        {
            "schema": 2, "value": 1800.0, "metric": "decode_tokens_per_sec",
            "serve_sharded_tok_s": 210.5, "serve_mesh": "dp=1,fsdp=2,tp=4",
            "serve_mesh_devices": 8,
        }
    ))
    # a bench record whose sharded section FAILED: no serve_sharded_tok_s,
    # no mesh stamp — the single-chip decode headline must not masquerade
    # as the multichip number
    (tmp_path / "MULTICHIP_r04.json").write_text(json.dumps(
        {
            "schema": 2, "value": 1800.0, "metric": "decode_tokens_per_sec",
            "serve_sharded_error": "RuntimeError: boom",
        }
    ))
    # a sharded smoke record committed under a BENCH name: its own stamps
    # (mesh_devices + serve_sharded_tok_s metric) route it to the mc rows —
    # its headline must never land in the single-chip 'cpu-smoke tok/s' row
    (tmp_path / "BENCH_loadgen_cpu_r05.json").write_text(json.dumps(
        {
            "schema": 2, "metric": "serve_sharded_tok_s (...)", "value": 33.0,
            "backend": "cpu", "mesh": {"tp": 4}, "mesh_devices": 4,
        }
    ))
    rounds = load_all_rounds(str(tmp_path))
    assert [r.label for r in rounds] == [
        "r01", "mc01", "mc02-loadgen_cpu", "mc03", "mc04", "mc05-loadgen_cpu",
    ]
    mc05 = rounds[5]
    assert mc05.metrics["mc sharded tok/s"] == 33.0
    assert "cpu-smoke tok/s" not in mc05.metrics
    mc01, mc02, mc03, mc04 = rounds[1], rounds[2], rounds[3], rounds[4]
    assert mc01.metrics == {"mc dryrun ok": 1.0}
    assert mc02.metrics["mc sharded tok/s"] == 34.9
    assert mc02.metrics["mc mesh devices"] == 4.0
    assert mc02.metrics["mc-slo:smoke tok/s"] == 34.9
    assert mc03.metrics["mc sharded tok/s"] == 210.5
    assert mc03.metrics["mc mesh devices"] == 8.0
    assert "mc sharded tok/s" not in mc04.metrics
    # multichip metric names are disjoint from every BENCH row: the delta
    # math can therefore never produce a cross-backend delta
    bench_names = set(rounds[0].metrics)
    assert not (bench_names & set(mc01.metrics) | bench_names & set(mc02.metrics))
    table = delta_table(rounds)
    assert "mc sharded tok/s" in table and "mc01" in table
    # the committed repo rounds must keep parsing too (r01..r06 + mc01..mc06)
    import os

    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    committed = load_all_rounds(repo_root)
    assert any(r.label.startswith("mc06") for r in committed)
    assert any(r.metrics.get("mc sharded tok/s", 0) > 0 for r in committed)


# ---- multi-LoRA on the mesh --------------------------------------------------


@requires_multichip
def test_sharded_multilora_bit_identity(tmp_path):
    """The multi-LoRA × mesh cell of the acceptance matrix: an adapter
    request through a SHARDED engine's gathered path emits the same greedy
    tokens as (a) the single-chip banked engine and (b) a merged-adapter
    engine — and base traffic on the sharded banked engine stays
    bit-identical to the plain sharded engine's."""
    from prime_tpu.train.lora import (
        LoraConfig,
        init_lora_params,
        merge_lora,
        save_adapters,
    )

    lora = LoraConfig(r=4, alpha=8)
    factors = init_lora_params(jax.random.PRNGKey(11), CONFIG, lora)
    factors["layers"] = {
        name: {
            "a": ab["a"],
            "b": (
                jax.random.normal(jax.random.PRNGKey(12), ab["b"].shape) * 0.05
            ).astype(ab["b"].dtype),
        }
        for name, ab in factors["layers"].items()
    }
    path = tmp_path / "tenant-a"
    save_adapters(path, factors, lora, CONFIG, base_params=PARAMS)
    prompt = WAVE_PROMPTS[0]

    def run(engine, adapter=None):
        req = engine.submit(prompt, max_new_tokens=10, adapter=adapter)
        drain(engine, req)
        toks = req.all_tokens(timeout=2)
        engine.shutdown()
        return toks

    single = run(
        make_engine(adapters={"tenant-a": str(path)}), adapter="tenant-a"
    )
    merged = run(
        ContinuousBatchingEngine(
            merge_lora(PARAMS, factors, lora), CONFIG,
            max_slots=4, capacity=128, chunk=4, prefix_cache_mb=0,
        )
    )
    assert single == merged
    sharded = run(
        make_engine(adapters={"tenant-a": str(path)}, mesh_config=MESH_SPEC),
        adapter="tenant-a",
    )
    assert sharded == single
    # base traffic: banked sharded == plain sharded (slot 0 is exact zero)
    plain = run(make_engine(mesh_config=MESH_SPEC))
    base_on_banked = run(
        make_engine(adapters={"tenant-a": str(path)}, mesh_config=MESH_SPEC)
    )
    assert base_on_banked == plain


@requires_multichip
def test_sharded_bank_placement_follows_projection_axes(tmp_path):
    """The bank shards consistently with the wrapped projections: A on the
    base weight's input (fsdp) axis, B on its output (tp) axis."""
    from prime_tpu.train.lora import LoraConfig, init_lora_params, save_adapters

    lora = LoraConfig(r=4, alpha=8)
    factors = init_lora_params(jax.random.PRNGKey(11), CONFIG, lora)
    path = tmp_path / "tenant-a"
    save_adapters(path, factors, lora, CONFIG, base_params=PARAMS)
    engine = make_engine(
        adapters={"tenant-a": str(path)}, mesh_config=MESH_SPEC
    )
    try:
        stacks = engine.adapter_bank.stacks["layers"]
        a_spec = stacks["wq"]["a"].sharding.spec
        b_spec = stacks["wq"]["b"].sharding.spec
        # (L, A, d_in, r): d_in on fsdp; (L, A, r, d_out): d_out on tp
        assert tuple(a_spec) == (None, None, "fsdp", None)
        assert tuple(b_spec) == (None, None, None, "tp")
    finally:
        engine.shutdown()
