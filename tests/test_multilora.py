"""Batched multi-LoRA serving tests (CPU, tiny model).

The load-bearing properties (docs/architecture.md "Multi-LoRA serving"):

- **Bit-identity vs merged serving.** A request selecting adapter X through
  the batched gathered path emits exactly the greedy tokens a dedicated
  engine serving ``merge_lora(base, X)`` emits — across the overlap ×
  speculative × mesh matrix.
- **Mixed-wave isolation.** A tenant's adapter must never perturb another
  tenant's base-model tokens: bank slot 0 is the all-zeros base adapter and
  the per-row gather makes every row's math independent, so base requests
  in a mixed wave are bit-identical to a bankless engine's.
- **Prefix-cache isolation.** Cached KV is only valid under the adapter
  that computed it: adapter paths live in a salted key space, so a base
  request can never assemble an adapter's KV (or vice versa).
- **Fair admission.** Per-adapter round-robin pop with an optional
  ``adapter_max_inflight`` cap — one tenant's burst cannot starve others.
"""

import json

import jax
import jax.numpy as jnp
import pytest

from prime_tpu.models import get_config
from prime_tpu.models.llama import init_params
from prime_tpu.serve.adapters import load_adapter_bank, parse_adapter_spec
from prime_tpu.serve.engine import ContinuousBatchingEngine
from prime_tpu.train.lora import (
    LoraConfig,
    init_lora_params,
    merge_lora,
    save_adapters,
)

CONFIG = get_config("tiny-test")
PARAMS = init_params(jax.random.PRNGKey(0), CONFIG, dtype=jnp.float32)


@pytest.fixture(autouse=True)
def _default_env(monkeypatch):
    for knob in (
        "PRIME_SERVE_OVERLAP", "PRIME_SERVE_WARMUP", "PRIME_SERVE_MESH",
        "PRIME_SERVE_SPEC", "PRIME_SERVE_DRAFT_LEN", "PRIME_SERVE_ADAPTERS",
        "PRIME_SERVE_ADAPTER_MAX_INFLIGHT", "PRIME_SERVE_ADAPTER_WEIGHTS",
        "PRIME_SERVE_PREFIX_CACHE_MB",
    ):
        monkeypatch.delenv(knob, raising=False)


def make_factors(seed: int, lora: LoraConfig, scale: float = 0.05):
    """Trained-shaped random adapter factors: nonzero B so the adapter
    actually changes outputs (zero-init B is a no-op)."""
    factors = init_lora_params(jax.random.PRNGKey(seed), CONFIG, lora)
    factors["layers"] = {
        name: {
            "a": ab["a"],
            "b": (
                jax.random.normal(jax.random.PRNGKey(seed + 100), ab["b"].shape)
                * scale
            ).astype(ab["b"].dtype),
        }
        for name, ab in factors["layers"].items()
    }
    return factors


@pytest.fixture(scope="module")
def artifacts(tmp_path_factory):
    """Two saved adapter artifacts (different ranks — the bank must pad)
    plus their factor pytrees for merged references."""
    root = tmp_path_factory.mktemp("adapters")
    out = {}
    for name, seed, lora in (
        ("tenant-a", 1, LoraConfig(r=4, alpha=8)),
        ("tenant-b", 2, LoraConfig(r=2, alpha=4)),
    ):
        factors = make_factors(seed, lora)
        path = root / name
        save_adapters(path, factors, lora, CONFIG, base_params=PARAMS)
        out[name] = (str(path), factors, lora)
    return out


def make_engine(params=PARAMS, **kw) -> ContinuousBatchingEngine:
    kw.setdefault("max_slots", 4)
    kw.setdefault("capacity", 128)
    kw.setdefault("chunk", 4)
    kw.setdefault("prefix_cache_mb", 0)
    return ContinuousBatchingEngine(params, CONFIG, **kw)


def drain(engine, *requests, max_ticks=400):
    for _ in range(max_ticks):
        engine.tick()
        if all(r.done for r in requests):
            return
    raise AssertionError("requests did not finish")


def run_one(engine, prompt, n=10, adapter=None):
    req = engine.submit(prompt, max_new_tokens=n, adapter=adapter)
    drain(engine, req)
    return req.all_tokens(timeout=2)


PROMPT = list(range(5, 41))  # 36 tokens: spans two radix blocks


# ---- bank construction -------------------------------------------------------


def test_parse_adapter_spec():
    assert parse_adapter_spec("a=/x,b=/y") == {"a": "/x", "b": "/y"}
    assert parse_adapter_spec("") == {}
    assert parse_adapter_spec(" a = /x , ") == {"a": "/x"}
    with pytest.raises(ValueError, match="name=path"):
        parse_adapter_spec("justaname")
    with pytest.raises(ValueError, match="reserved"):
        parse_adapter_spec("base=/x")
    with pytest.raises(ValueError, match="duplicate"):
        parse_adapter_spec("a=/x,a=/y")


def test_bank_load_pads_ranks_and_reserves_base(artifacts):
    bank = load_adapter_bank(
        {name: path for name, (path, _, _) in artifacts.items()},
        PARAMS, CONFIG,
    )
    assert bank.names[0] == "base"
    assert bank.adapter_names == ("tenant-a", "tenant-b")
    assert bank.rank == 4  # max over (4, 2): tenant-b pads
    assert bank.index_of(None) == 0 and bank.index_of("base") == 0
    assert bank.index_of("tenant-a") == 1
    with pytest.raises(KeyError):
        bank.index_of("nope")
    # slot 0 is exactly zero: base rides the gathered matmul as a no-op
    for ab in bank.stacks["layers"].values():
        assert float(jnp.abs(ab["a"][:, 0]).max()) == 0.0
        assert float(jnp.abs(ab["b"][:, 0]).max()) == 0.0


def test_bank_rejects_wrong_base_fingerprint(tmp_path, artifacts):
    lora = LoraConfig(r=4, alpha=8)
    factors = make_factors(7, lora)
    other_base = init_params(jax.random.PRNGKey(99), CONFIG, dtype=jnp.float32)
    save_adapters(tmp_path / "bad", factors, lora, CONFIG, base_params=other_base)
    with pytest.raises(ValueError, match="DIFFERENT base weights"):
        load_adapter_bank({"bad": tmp_path / "bad"}, PARAMS, CONFIG)


def test_bank_rejects_wrong_base_model(tmp_path):
    other = get_config("debug-128m")
    other_params = init_params(jax.random.PRNGKey(0), other, dtype=jnp.float32)
    lora = LoraConfig(r=2, alpha=4)
    factors = init_lora_params(jax.random.PRNGKey(0), other, lora)
    save_adapters(tmp_path / "other", factors, lora, other, base_params=other_params)
    with pytest.raises(ValueError, match="trained on"):
        load_adapter_bank({"other": tmp_path / "other"}, PARAMS, CONFIG)


# ---- bit-identity vs merged serving ------------------------------------------


@pytest.mark.parametrize("overlap", [False, True])
@pytest.mark.parametrize("speculative", [False, True])
def test_adapter_bit_identity_vs_merged(artifacts, overlap, speculative):
    """The acceptance matrix: an adapter request through the batched
    gathered path emits the SAME greedy tokens as a dedicated engine
    serving merge_lora(base, adapter) — overlap × speculative."""
    path, factors, lora = artifacts["tenant-a"]
    merged_engine = make_engine(merge_lora(PARAMS, factors, lora))
    reference = run_one(merged_engine, PROMPT, n=12)
    merged_engine.shutdown()

    engine = make_engine(
        adapters={"tenant-a": path}, overlap=overlap, speculative=speculative,
    )
    got = run_one(engine, PROMPT, n=12, adapter="tenant-a")
    engine.shutdown()
    assert got == reference


def test_second_adapter_matches_its_own_merge(artifacts):
    """Adapter selection gathers the RIGHT slot: tenant-b's tokens match
    merge_lora(base, tenant-b), not tenant-a's."""
    engine = make_engine(
        adapters={name: p for name, (p, _, _) in artifacts.items()},
    )
    got_a = run_one(engine, PROMPT, n=10, adapter="tenant-a")
    got_b = run_one(engine, PROMPT, n=10, adapter="tenant-b")
    engine.shutdown()
    for name, got in (("tenant-a", got_a), ("tenant-b", got_b)):
        _, factors, lora = artifacts[name]
        ref_engine = make_engine(merge_lora(PARAMS, factors, lora))
        assert got == run_one(ref_engine, PROMPT, n=10), name
        ref_engine.shutdown()
    assert got_a != got_b  # the two fine-tunes genuinely diverge


def test_mixed_wave_isolation(artifacts):
    """Tenant A's adapter never perturbs tenant B's base tokens: a mixed
    concurrent wave's base members are bit-identical to a bankless engine,
    and its adapter members to their merged references."""
    bankless = make_engine()
    base_ref = run_one(bankless, PROMPT, n=10)
    base_ref2 = run_one(bankless, [7, 8, 9, 10, 11], n=10)
    bankless.shutdown()

    engine = make_engine(
        adapters={name: p for name, (p, _, _) in artifacts.items()},
    )
    reqs = [
        engine.submit(PROMPT, max_new_tokens=10, adapter="tenant-a"),
        engine.submit(PROMPT, max_new_tokens=10),
        engine.submit([7, 8, 9, 10, 11], max_new_tokens=10),
        engine.submit(PROMPT, max_new_tokens=10, adapter="tenant-b"),
    ]
    drain(engine, *reqs)
    assert reqs[1].all_tokens(timeout=2) == base_ref
    assert reqs[2].all_tokens(timeout=2) == base_ref2
    _, factors_a, lora_a = artifacts["tenant-a"]
    ref = make_engine(merge_lora(PARAMS, factors_a, lora_a))
    assert reqs[0].all_tokens(timeout=2) == run_one(ref, PROMPT, n=10)
    ref.shutdown()
    engine.shutdown()


def test_base_traffic_on_banked_engine_matches_bankless(artifacts):
    """Bank slot 0 is an exact zero: loading a bank changes NOTHING for
    base traffic (greedy tokens identical to a bankless engine)."""
    bankless = make_engine(prefix_cache_mb=8)
    ref = run_one(bankless, PROMPT, n=12)
    bankless.shutdown()
    banked = make_engine(
        prefix_cache_mb=8,
        adapters={"tenant-a": artifacts["tenant-a"][0]},
    )
    assert run_one(banked, PROMPT, n=12) == ref
    banked.shutdown()


def test_lora_kernel_engine_bit_identity_vs_einsum(artifacts, monkeypatch):
    """The fused gathered-LoRA pallas kernel vs the einsum reference chain
    at ENGINE level: the same mixed-adapter wave with the kernel forced in
    (interpret mode, CPU) emits bit-identical greedy tokens to the einsum
    path. Only the LoRA projection is flipped — attention and everything
    else stay on the exact same code — so any token drift is the kernel's
    rounding contract breaking."""
    from prime_tpu.models import llama
    from prime_tpu.ops import pallas_lora

    adapters = {name: p for name, (p, _, _) in artifacts.items()}
    wave = [
        (PROMPT, "tenant-a"),
        (PROMPT, None),
        (PROMPT, "tenant-b"),
        ([7, 8, 9, 10, 11], "tenant-a"),
    ]

    def run():
        engine = make_engine(adapters=adapters)
        reqs = [
            engine.submit(list(p), max_new_tokens=10, adapter=ad)
            for p, ad in wave
        ]
        drain(engine, *reqs)
        out = [r.all_tokens(timeout=2) for r in reqs]
        engine.shutdown()
        return out

    einsum_out = run()

    calls = []
    real = pallas_lora.fused_lora_matmul

    def forced(*args, **kw):
        calls.append(1)
        kw["interpret"] = True
        return real(*args, **kw)

    monkeypatch.setattr(llama, "_lora_kernel_eligible", lambda w, x, b: True)
    monkeypatch.setattr(pallas_lora, "fused_lora_matmul", forced)
    jax.clear_caches()  # the gate is trace-time: force a re-trace
    try:
        kernel_out = run()
    finally:
        jax.clear_caches()  # don't leak kernel-path traces past the patch
    assert calls, "the fused kernel never dispatched"
    assert kernel_out == einsum_out


# ---- prefix-cache isolation --------------------------------------------------


def test_prefix_cache_never_crosses_adapters(artifacts):
    """The salted key space: serving a prompt under tenant-a caches its KV,
    but the SAME prompt under base (or tenant-b) must not prefix-hit it —
    and same-adapter repeats must."""
    engine = make_engine(
        prefix_cache_mb=32,
        adapters={name: p for name, (p, _, _) in artifacts.items()},
    )
    run_one(engine, PROMPT + [50], n=4, adapter="tenant-a")
    hits0 = engine.prefix_hits
    # same prompt, other tenants: no hit (a cross hit would serve KV
    # computed under the wrong weights)
    run_one(engine, PROMPT + [51], n=4)
    run_one(engine, PROMPT + [52], n=4, adapter="tenant-b")
    assert engine.prefix_hits == hits0
    # same adapter again: hit
    run_one(engine, PROMPT + [53], n=4, adapter="tenant-a")
    assert engine.prefix_hits == hits0 + 1
    # and the hit-seeded tokens are still bit-identical to merged serving
    _, factors, lora = artifacts["tenant-a"]
    ref = make_engine(merge_lora(PARAMS, factors, lora))
    reference = run_one(ref, PROMPT + [53], n=4)
    ref.shutdown()
    hit = run_one(engine, PROMPT + [53], n=4, adapter="tenant-a")
    assert hit == reference
    engine.shutdown()


# ---- fair admission ----------------------------------------------------------


def test_fair_pop_round_robins_across_adapters(artifacts):
    """A burst of one tenant queued ahead of another must not starve it:
    with 2 slots and 4 queued requests of tenant-a followed by 2 of base,
    the round-robin pop interleaves tenants instead of FIFO-draining a."""
    engine = make_engine(
        max_slots=2,
        adapters={"tenant-a": artifacts["tenant-a"][0]},
    )
    a_reqs = [
        engine.submit(PROMPT, max_new_tokens=4, adapter="tenant-a")
        for _ in range(4)
    ]
    b_reqs = [engine.submit([9, 9, 9], max_new_tokens=4) for _ in range(2)]
    engine._admit()  # one wave: 2 slots
    admitted = {r.adapter_idx for r in engine._requests.values()}
    # one slot per tenant, not two tenant-a slots
    assert admitted == {0, 1}
    drain(engine, *a_reqs, *b_reqs)
    engine.shutdown()


def test_adapter_max_inflight_caps_one_tenant(artifacts):
    """adapter_max_inflight=1: no tenant (base included — base is tenant 0)
    ever holds more than one admitted slot even with free capacity, one
    admission wave cannot blow past the cap, and the capped backlog stays
    counted (queue_depth/drained) and still completes."""
    engine = make_engine(
        max_slots=4,
        adapters={"tenant-a": artifacts["tenant-a"][0]},
        adapter_max_inflight=1,
    )
    a_reqs = [
        engine.submit(PROMPT, max_new_tokens=4, adapter="tenant-a")
        for _ in range(3)
    ]
    base_reqs = [engine.submit([9, 9, 9], max_new_tokens=4) for _ in range(2)]
    engine._admit()
    by_adapter: dict[int, int] = {}
    for r in engine._requests.values():
        by_adapter[r.adapter_idx] = by_adapter.get(r.adapter_idx, 0) + 1
    assert by_adapter == {0: 1, 1: 1}  # one slot per tenant, cap respected
    # the capped backlog is still counted and still completes
    assert engine.queue_depth() == 3
    drain(engine, *a_reqs, *base_reqs)
    assert engine.queue_depth() == 0
    engine.shutdown()


def test_weighted_shares_pop_order_pin(artifacts):
    """WEIGHTED round-robin (ROADMAP item 3 follow-up): tenant-a at weight
    2 pops twice per rotation, INTERLEAVED — the smooth-WRR sequence for
    weights {base: 1, a: 2} with both backlogged is a, base, a, a, base, a
    (never a-a back to back at a rotation boundary, never base starved)."""
    engine = make_engine(
        adapters={"tenant-a": artifacts["tenant-a"][0]},
        adapter_weights={"tenant-a": 2},
    )
    assert engine.adapter_weights == {"tenant-a": 2}
    for _ in range(4):
        engine.submit(PROMPT, max_new_tokens=2, adapter="tenant-a")
        engine.submit([9, 9, 9], max_new_tokens=2)
    order = [engine._pop_pending().adapter_idx for _ in range(6)]
    assert order == [1, 0, 1, 1, 0, 1]  # idx 1 = tenant-a, idx 0 = base
    engine.shutdown()


def test_weighted_shares_uniform_is_plain_round_robin(artifacts):
    """Default (no weights) must reproduce the historical rotation: two
    backlogged tenants alternate strictly."""
    engine = make_engine(adapters={"tenant-a": artifacts["tenant-a"][0]})
    for _ in range(3):
        engine.submit(PROMPT, max_new_tokens=2, adapter="tenant-a")
        engine.submit([9, 9, 9], max_new_tokens=2)
    order = [engine._pop_pending().adapter_idx for _ in range(6)]
    assert order in ([0, 1, 0, 1, 0, 1], [1, 0, 1, 0, 1, 0])
    engine.shutdown()


def test_weighted_shares_validation(artifacts):
    from prime_tpu.serve.adapters import parse_adapter_weights

    with pytest.raises(ValueError, match="name=K"):
        parse_adapter_weights("broken")
    with pytest.raises(ValueError, match=">= 1"):
        parse_adapter_weights("a=0")
    with pytest.raises(ValueError, match="duplicate"):
        parse_adapter_weights("a=1,a=2")
    # weights without a bank are a loud construction error
    with pytest.raises(ValueError, match="bank"):
        make_engine(adapter_weights={"tenant-a": 2})
    # an unknown tenant name is a loud construction error too
    with pytest.raises(KeyError):
        make_engine(
            adapters={"tenant-a": artifacts["tenant-a"][0]},
            adapter_weights={"nope": 2},
        )


def test_weighted_shares_env_wiring(monkeypatch, artifacts):
    monkeypatch.setenv("PRIME_SERVE_ADAPTER_WEIGHTS", "tenant-a=3,base=2")
    engine = make_engine(adapters={"tenant-a": artifacts["tenant-a"][0]})
    assert engine.adapter_weights == {"tenant-a": 3, "base": 2}
    assert engine._fair_weights == {0: 2, 1: 3}
    assert engine.stats()["adapter_weights"] == {"tenant-a": 3, "base": 2}
    engine.shutdown()


def test_env_wiring(monkeypatch, artifacts):
    path = artifacts["tenant-a"][0]
    monkeypatch.setenv("PRIME_SERVE_ADAPTERS", f"tenant-a={path}")
    monkeypatch.setenv("PRIME_SERVE_ADAPTER_MAX_INFLIGHT", "3")
    engine = make_engine()
    assert engine.adapter_bank is not None
    assert engine.adapter_bank.adapter_names == ("tenant-a",)
    assert engine.adapter_max_inflight == 3
    stats = engine.stats()
    assert stats["adapters_loaded"] == 1 and stats["adapters"] == ["tenant-a"]
    engine.shutdown()
    # kwarg beats env
    monkeypatch.setenv("PRIME_SERVE_ADAPTERS", "tenant-a=/nonexistent")
    engine = make_engine(adapters={"tenant-a": path})
    assert engine.adapter_bank is not None
    engine.shutdown()


# ---- obs ---------------------------------------------------------------------


def test_adapter_token_and_ttft_metrics(artifacts):
    engine = make_engine(
        adapters={"tenant-a": artifacts["tenant-a"][0]},
    )
    run_one(engine, PROMPT, n=6, adapter="tenant-a")
    run_one(engine, [7, 8, 9], n=4)
    snap = engine.registry.snapshot()
    tokens = {
        s["labels"]["adapter"]: s["value"]
        for s in snap["serve_adapter_tokens_total"]["series"]
    }
    assert tokens == {"tenant-a": 6.0, "base": 4.0}
    ttft = {
        s["labels"]["adapter"]: s["count"]
        for s in snap["serve_adapter_ttft_seconds"]["series"]
    }
    assert ttft == {"tenant-a": 1, "base": 1}
    assert engine.registry.values()["serve_adapters_loaded"] == 1.0
    engine.shutdown()


def test_bankless_engine_has_no_adapter_series():
    engine = make_engine()
    run_one(engine, [5, 6, 7], n=4)
    snap = engine.registry.snapshot()
    assert snap["serve_adapter_tokens_total"]["series"] == []
    engine.shutdown()


# ---- server + fleet ----------------------------------------------------------


def test_server_model_registry_and_fleet_adapter_affinity(artifacts):
    """E2E over real HTTP: /v1/models lists adapters, unknown models 404
    with the authoritative list, /healthz advertises the bank, and the
    router narrows adapter traffic to the replica holding the adapter
    (fleet_adapter_routed_total pinned)."""
    import time

    import httpx

    from prime_tpu.loadgen.backends import NumericTokenizer
    from prime_tpu.serve.engine import EngineBackend
    from prime_tpu.serve.fleet import serve_fleet
    from prime_tpu.serve.server import InferenceServer

    base_engine = make_engine(prefix_cache_mb=8)
    lora_engine = make_engine(
        prefix_cache_mb=8,
        adapters={"tenant-a": artifacts["tenant-a"][0]},
    )
    for e in (base_engine, lora_engine):
        e.start()
    s0 = InferenceServer(
        "m", EngineBackend(base_engine, NumericTokenizer()), port=0
    ).start()
    s1 = InferenceServer(
        "m", EngineBackend(lora_engine, NumericTokenizer()), port=0
    ).start()
    router = serve_fleet([s0.url, s1.url], poll_interval=0.2, model_id="m")
    try:
        assert httpx.get(f"{s1.url}/healthz").json().get("adapters") == ["tenant-a"]
        assert "adapters" not in httpx.get(f"{s0.url}/healthz").json()
        models = [m["id"] for m in httpx.get(f"{s1.url}/v1/models").json()["data"]]
        assert models == ["m", "tenant-a"]
        r = httpx.post(
            f"{s1.url}/v1/chat/completions",
            json={"model": "nope", "messages": [{"role": "user", "content": "5"}]},
        )
        assert r.status_code == 404 and "tenant-a" in r.json()["error"]["message"]
        # wait for the poller to learn the advertisement
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline:
            if any(
                replica.adapters
                for replica in router.membership.replicas.values()
            ):
                break
            time.sleep(0.05)
        prompt = " ".join(str(i) for i in range(5, 41))
        for _ in range(3):
            r = httpx.post(
                f"{router.url}/v1/chat/completions",
                json={
                    "model": "tenant-a",
                    "messages": [{"role": "user", "content": prompt}],
                    "max_tokens": 4,
                },
                timeout=120,
            )
            assert r.status_code == 200, r.text
            assert r.json()["model"] == "tenant-a"
        assert router.stats()["adapter_routed"] == {"tenant-a": 3}
        # every adapter request landed on the adapter-holding replica (the
        # router counts an outcome after the body went out: the client can
        # be back here before the third is counted)
        lora_id = [
            rid for rid, rep in router.membership.replicas.items()
            if rep.adapters
        ][0]
        deadline = time.monotonic() + 5
        while True:
            served = router.stats()["requests_by_replica"].get(lora_id, {})
            if sum(served.values()) == 3 or time.monotonic() > deadline:
                break
            time.sleep(0.02)
        assert served == {"ok": 3}
    finally:
        router.stop()
        s0.stop()
        s1.stop()


def test_membership_parses_adapter_advertisement_tolerantly():
    from prime_tpu.serve.digest import parse_adapters
    from prime_tpu.serve.fleet.membership import FleetMembership, Replica

    membership = FleetMembership()
    replica = Replica("http://127.0.0.1:1")
    for junk in (None, 7, "x", {"a": 1}, [1, 2], ["", "x" * 1000]):
        membership.apply_health(replica, {"adapters": junk}, 200)
        assert replica.adapters == frozenset()
    membership.apply_health(replica, {"adapters": ["a", "b", 3, "a"]}, 200)
    assert replica.adapters == frozenset({"a", "b"})
    assert parse_adapters(["ok"] * 5000) == frozenset({"ok"})


def test_balancer_adapter_affinity_unit():
    from prime_tpu.serve.fleet.balancer import PrefixAffinityBalancer
    from prime_tpu.serve.fleet.membership import FleetMembership

    membership = FleetMembership()
    r1 = membership.add("http://127.0.0.1:1")
    r2 = membership.add("http://127.0.0.1:2")
    for r in (r1, r2):
        r.state = "ready"
    r2.adapters = frozenset({"tenant-a"})
    balancer = PrefixAffinityBalancer(membership)
    prompt = "x" * 256
    # adapter traffic narrows to the holder, whatever the ring says
    for _ in range(4):
        pick = balancer.pick(prompt, adapter="tenant-a")
        assert pick is not None and pick.replica.id == r2.id
        assert pick.adapter_routed
    # base traffic is unaffected; unknown adapters degrade to the full pool
    pick = balancer.pick(prompt)
    assert pick is not None and not pick.adapter_routed
    pick = balancer.pick(prompt, adapter="unknown")
    assert pick is not None and not pick.adapter_routed
    # the holder excluded: adapter affinity cannot resurrect it
    pick = balancer.pick(prompt, {r2.id}, adapter="tenant-a")
    assert pick is not None and pick.replica.id == r1.id


# ---- loadgen integration -----------------------------------------------------


def test_scenario_row_adapter_split(artifacts):
    """EngineTarget honors PlannedRequest.adapter and scenario_row splits
    tokens/TTFT per adapter from the labeled families."""
    from prime_tpu.loadgen.backends import EngineTarget
    from prime_tpu.loadgen.report import scenario_row
    from prime_tpu.loadgen.runner import run_schedule
    from prime_tpu.loadgen.scenario import Phase, Scenario, build_schedule

    scenario = Scenario(
        "mix", 3,
        (
            Phase(
                kind="mixed", n=4, tenants=2, prompt_tokens=20,
                max_new_tokens=4, adapters=("base", "tenant-a"),
            ),
        ),
        vocab=CONFIG.vocab_size,
    )
    schedule = build_schedule(scenario)
    engine = make_engine(
        adapters={"tenant-a": artifacts["tenant-a"][0]},
    )
    try:
        result = run_schedule(
            schedule, EngineTarget(engine), scenario="mix", seed=3,
            time_scale=0.0,
        )
        row = scenario_row(result)
    finally:
        engine.shutdown()
    split = row["adapters"]
    assert set(split) == {"base", "tenant-a"}
    assert split["base"]["tokens"] == split["tenant-a"]["tokens"] == 8
    assert split["tenant-a"]["ttft_s"]["p50"] is not None
    assert json.dumps(row)  # the row stays JSON-serializable


def test_router_model_alias_rewrites_forwarded_body(artifacts):
    """--model-alias placement must also REWRITE the forwarded body: the
    replica resolves the model field against its own adapter ids, not the
    router-side alias — and a base alias must serve the base model."""
    import httpx

    from prime_tpu.loadgen.backends import NumericTokenizer
    from prime_tpu.serve.engine import EngineBackend
    from prime_tpu.serve.fleet.router import FleetRouter
    from prime_tpu.serve.server import InferenceServer

    engine = make_engine(
        prefix_cache_mb=8,
        adapters={"tenant-a": artifacts["tenant-a"][0]},
    )
    engine.start()
    srv = InferenceServer(
        "m", EngineBackend(engine, NumericTokenizer()), port=0
    ).start()
    router = FleetRouter(
        [srv.url], poll_interval=0.2, model_id="m",
        model_registry={"fancy": "tenant-a", "plain": None},
    ).start()
    try:
        for alias, served in (("fancy", "tenant-a"), ("plain", "m")):
            r = httpx.post(
                f"{router.url}/v1/chat/completions",
                json={
                    "model": alias,
                    "messages": [{"role": "user", "content": "5 6 7 8"}],
                    "max_tokens": 4,
                },
                timeout=120,
            )
            assert r.status_code == 200, (alias, r.text)
            assert r.json()["model"] == served
        # the adapter really served the aliased request
        tokens = {
            s["labels"]["adapter"]: s["value"]
            for s in engine.registry.snapshot()["serve_adapter_tokens_total"]["series"]
        }
        assert tokens.get("tenant-a", 0) > 0
    finally:
        router.stop()
        srv.stop()


def test_serve_model_rejects_adapters_with_weight_quant():
    from prime_tpu.serve.server import serve_model

    with pytest.raises(ValueError, match="weight-quant"):
        serve_model(
            "tiny-test", continuous=True, weight_quant=True,
            adapters={"a": "/nonexistent"}, port=0,
        )
