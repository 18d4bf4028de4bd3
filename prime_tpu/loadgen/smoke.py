"""The loadgen CPU smoke: an in-process 2-replica fleet, one tiny scenario,
one nonzero headline.

This is the run that ends the era of empty trajectories: no TPU, no
checkpoint — two tiny continuous-batching engines behind real
``InferenceServer`` processes-worth of HTTP and a real ``FleetRouter``,
driven by the deterministic ``smoke`` scenario over the wire. It produces:

- ``slo_report.json`` — the versioned SLO report (registry-derived tok/s,
  TTFT/TPOT percentiles, hit/overlap/affinity ratios);
- ``bench_record.json`` — the same headline in BENCH record schema 2, so
  ``perf_delta.py`` folds CI smokes into the same trajectory as TPU rounds;
- an exposition lint verdict over every surface's ``/metrics`` text
  (checked against the docs/observability.md catalog);
- the router's flight-recorder scrape (the replay seed).

Shared by ``scripts/loadgen_smoke.py`` (CI job ``loadgen-smoke``) and
``prime bench smoke``. Import cost: jax and the serve stack load inside
:func:`run_smoke`, not at module import — the CLI stays light.
"""

from __future__ import annotations

import json
import os
from typing import Any

from prime_tpu.loadgen.scenario import SCENARIOS, loadgen_seed_default


def _spec_section(
    config, params_fn, *, seed: int, mesh: str | None, log
) -> tuple[dict[str, Any], list]:
    """The speculative on/off comparison: drive the ``spec_friendly``
    scenario (repetitive completions where n-gram drafts accept) through
    one in-process engine with speculation off, then on — same schedule,
    same registry-windowed measurement as every other section. Returns the
    BENCH-record keys (spec on/off tok/s, TPOT deltas, accept ratio,
    speedup) plus the two SLO scenario rows. With ``mesh`` set the engines
    are SHARDED, so the committed MULTICHIP round carries the
    spec × mesh evidence."""
    from prime_tpu.loadgen.backends import EngineTarget
    from prime_tpu.loadgen.report import scenario_row, spec_comparison_record
    from prime_tpu.loadgen.runner import run_schedule
    from prime_tpu.loadgen.scenario import SCENARIOS, build_schedule
    from prime_tpu.serve.engine import ContinuousBatchingEngine

    schedule = build_schedule(SCENARIOS["spec_friendly"](seed), vocab=config.vocab_size)
    rows = []
    for speculative in (False, True):
        name = "spec_friendly" if speculative else "spec_friendly_off"
        engine = ContinuousBatchingEngine(
            params_fn(), config, pad_id=0, max_slots=4, capacity=256, chunk=4,
            prefix_cache_mb=8, speculative=speculative, mesh_config=mesh or None,
        )
        try:
            # warm the shapes in play (incl. the second-admission prefix
            # hit), then measure through the registry-windowed runner —
            # time_scale=0 drives the whole burst immediately
            for _ in range(2):
                warm = engine.submit(
                    list(schedule[0].prompt_ids),
                    max_new_tokens=schedule[0].max_new_tokens,
                )
                while not warm.done:
                    engine.tick()
            engine.tick()
            result = run_schedule(
                schedule, EngineTarget(engine), scenario=name, seed=seed,
                time_scale=0.0,
            )
            rows.append(scenario_row(result))
        finally:
            engine.shutdown()
    off_row, on_row = rows
    record = spec_comparison_record(off_row, on_row)
    log(
        f"# loadgen-smoke: spec_friendly spec-on {record['serve_spec_tok_s']} "
        f"vs spec-off {record['serve_spec_off_tok_s']} tok/s "
        f"(accept ratio {record.get('serve_spec_accept_ratio')})"
    )
    return record, rows


def _longprefix_section(
    config, params_fn, *, seed: int, mesh: str | None, log
) -> tuple[dict[str, Any], list]:
    """The paged-vs-copy seeding comparison (docs/kernels.md
    "paged_gather"): the ``longprefix`` scenario — long shared preambles
    where hit seeding dominates — through one engine seeding hits from the
    page pool, then the SAME schedule through one seeding via the
    contiguous copy path. Record keys: tok/s both ways plus the mean
    hit-seed wall time per path straight from the
    ``serve_prefix_seed_seconds{path=...}`` histogram — the paging win's
    direct evidence (on CPU the gather runs the XLA fallback; the numbers
    prove the path and its accounting, the TPU round proves the speed)."""
    from prime_tpu.loadgen.backends import EngineTarget
    from prime_tpu.loadgen.report import scenario_row
    from prime_tpu.loadgen.runner import run_schedule
    from prime_tpu.loadgen.scenario import SCENARIOS, build_schedule
    from prime_tpu.serve.engine import ContinuousBatchingEngine

    schedule = build_schedule(
        SCENARIOS["longprefix"](seed), vocab=config.vocab_size
    )
    rows = []
    record: dict[str, Any] = {}
    for paged in (False, True):
        name = "longprefix" if paged else "longprefix_copy"
        engine = ContinuousBatchingEngine(
            params_fn(), config, pad_id=0, max_slots=4, capacity=256, chunk=4,
            prefix_cache_mb=8, paged_prefix=paged, mesh_config=mesh or None,
        )
        try:
            # warm the shapes in play (incl. the second-admission hit-seed),
            # then measure through the registry-windowed runner
            for _ in range(2):
                warm = engine.submit(
                    list(schedule[0].prompt_ids),
                    max_new_tokens=schedule[0].max_new_tokens,
                )
                while not warm.done:
                    engine.tick()
            engine.tick()
            result = run_schedule(
                schedule, EngineTarget(engine), scenario=name, seed=seed,
                time_scale=0.0,
            )
            rows.append(scenario_row(result))
            key = "serve_longprefix" if paged else "serve_longprefix_copy"
            record[f"{key}_tok_s"] = rows[-1]["tok_s"]
            path = "paged" if paged else "copy"
            hist = engine.registry.get(
                "serve_prefix_seed_seconds"
            ).series_snapshot(path=path)
            if hist and hist.get("count"):
                record[f"{key}_seed_ms"] = round(
                    hist["sum"] / hist["count"] * 1e3, 3
                )
            if paged:
                record["serve_longprefix_paged_seeds"] = engine.stats()[
                    "prefix_paged_seeds"
                ]
        finally:
            engine.shutdown()
    log(
        f"# loadgen-smoke: longprefix paged {record.get('serve_longprefix_tok_s')} "
        f"vs copy {record.get('serve_longprefix_copy_tok_s')} tok/s "
        f"(seed-ms {record.get('serve_longprefix_seed_ms')} vs "
        f"{record.get('serve_longprefix_copy_seed_ms')}, "
        f"{record.get('serve_longprefix_paged_seeds')} paged seeds)"
    )
    return record, rows


def _autotune_section(*, log) -> dict[str, Any]:
    """The autotune round-trip leg (docs/kernels.md "Kernel campaign &
    autotune"): a dry-run sweep over every kernel's trimmed candidate grid,
    winners saved to a throwaway artifact dir and loaded back through the
    production resolution path. Record keys ``autotune_kernels`` (kernels
    that produced a winner) and ``autotune_sweep_s`` (sweep wall time) —
    trajectory evidence that the sweep → artifact → resolve loop stays
    alive on every push."""
    import tempfile
    import time

    from prime_tpu.ops import kernel_configs
    from prime_tpu.ops.autotune import run_autotune

    t0 = time.perf_counter()
    winners = run_autotune(dry_run=True, log=None)
    sweep_s = time.perf_counter() - t0
    with tempfile.TemporaryDirectory(prefix="prime-autotune-") as tmp:
        kind = kernel_configs.device_kind()
        kernel_configs.save_artifact(winners, directory=tmp, kind=kind)
        # save/restore of the raw env var, not a config read: the section
        # must leave the process knob exactly as it found it
        saved = os.environ.get("PRIME_TPU_KERNEL_CONFIG_DIR")  # prime-lint: ignore[knob-direct-read] env save/restore, not a config read
        os.environ["PRIME_TPU_KERNEL_CONFIG_DIR"] = tmp
        kernel_configs.invalidate_cache()
        try:
            loaded = kernel_configs.load_tuned(kind)
            source = kernel_configs.source()
        finally:
            if saved is None:
                os.environ.pop("PRIME_TPU_KERNEL_CONFIG_DIR", None)
            else:
                os.environ["PRIME_TPU_KERNEL_CONFIG_DIR"] = saved
            kernel_configs.invalidate_cache()
    record: dict[str, Any] = {
        "autotune_kernels": len(winners),
        "autotune_sweep_s": round(sweep_s, 3),
    }
    if loaded is None or source != "tuned":
        record["autotune_error"] = (
            f"artifact failed to round-trip: loaded={loaded is not None} "
            f"source={source}"
        )
    log(
        f"# loadgen-smoke: autotune dry-run swept {record['autotune_kernels']} "
        f"kernels in {record['autotune_sweep_s']}s (source after load: {source})"
    )
    return record


def _multilora_section(
    config, params_fn, *, seed: int, mesh: str | None, log
) -> tuple[dict[str, Any], list]:
    """The batched multi-LoRA comparison (docs/architecture.md "Multi-LoRA
    serving"): the ``mixed_tenants`` scenario — tenants pinned to two LoRA
    adapters plus base via the OpenAI ``model`` field — through ONE engine
    holding the unmerged adapter bank, against the SAME schedule stripped to
    base-only on a bankless engine (the single-checkpoint headline config).
    Two throwaway adapter artifacts are trained-shaped (random factors,
    base-fingerprinted) and saved through ``train/lora.save_adapters`` so
    the load path exercised is the production one. Record keys:
    ``serve_multilora_tok_s`` / ``serve_multilora_base_tok_s`` / their
    ratio (the ≥0.8x acceptance gate reads it) and the per-adapter fairness
    ratio (min/max delivered tokens across base + adapters — 1.0 = perfectly
    even under the equal-demand mixed schedule).

    Scale note: run this at debug-128m (like the disagg section), not
    tiny-test — the gathered delta adds a fixed handful of small einsums
    per projection, and against a tiny model's near-zero matmuls that
    handful IS the runtime (the measured ratio would be an op-count
    artifact); at 128m the base matmuls are real work and the measured
    ratio reflects the architecture's actual multi-tenant cost."""
    import contextlib
    import dataclasses
    import tempfile

    import jax

    from prime_tpu.loadgen.backends import EngineTarget
    from prime_tpu.loadgen.report import scenario_row
    from prime_tpu.loadgen.runner import run_schedule
    from prime_tpu.loadgen.scenario import SCENARIOS, build_schedule
    from prime_tpu.serve.engine import ContinuousBatchingEngine
    from prime_tpu.train.lora import LoraConfig, init_lora_params, save_adapters

    schedule = build_schedule(
        SCENARIOS["mixed_tenants"](seed), vocab=config.vocab_size
    )
    base_schedule = [dataclasses.replace(r, adapter=None) for r in schedule]
    params = params_fn()
    lora = LoraConfig(r=8, alpha=16)
    stack = contextlib.ExitStack()
    tmp = stack.enter_context(
        tempfile.TemporaryDirectory(prefix="prime-multilora-")
    )
    paths: dict[str, str] = {}
    for i, name in enumerate(("adapter-a", "adapter-b")):
        factors = init_lora_params(jax.random.PRNGKey(10 + i), config, lora)
        # B is zero at init (a no-op adapter); give it small random values so
        # the gathered matmuls measure real distinct fine-tunes, not zeros
        factors["layers"] = {
            t: {
                "a": ab["a"],
                "b": (
                    jax.random.normal(jax.random.PRNGKey(20 + i), ab["b"].shape)
                    * 0.02
                ).astype(ab["b"].dtype),
            }
            for t, ab in factors["layers"].items()
        }
        path = os.path.join(tmp, name)
        save_adapters(path, factors, lora, config, base_params=params)
        paths[name] = path

    rows = []
    try:
        for label, adapters, sched in (
            ("multilora_base", None, base_schedule),
            ("multilora", paths, schedule),
        ):
            engine = ContinuousBatchingEngine(
                params, config, pad_id=0, max_slots=4, capacity=256, chunk=4,
                prefix_cache_mb=8, adapters=adapters, mesh_config=mesh or None,
            )
            try:
                # warm the shapes in play, then measure registry-windowed
                for _ in range(2):
                    warm = engine.submit(
                        list(sched[0].prompt_ids),
                        max_new_tokens=sched[0].max_new_tokens,
                    )
                    while not warm.done:
                        engine.tick()
                engine.tick()
                result = run_schedule(
                    sched, EngineTarget(engine), scenario=label, seed=seed,
                    time_scale=0.0,
                )
                rows.append(scenario_row(result))
            finally:
                engine.shutdown()
    finally:
        stack.close()  # the artifact dir is throwaway — never leak it
    base_row, mixed_row = rows
    record: dict[str, Any] = {
        "serve_multilora_base_tok_s": base_row["tok_s"],
        "serve_multilora_tok_s": mixed_row["tok_s"],
    }
    if base_row["tok_s"]:
        record["serve_multilora_ratio"] = round(
            mixed_row["tok_s"] / base_row["tok_s"], 3
        )
    split = mixed_row.get("adapters") or {}
    per_adapter = [entry["tokens"] for entry in split.values()]
    if per_adapter and max(per_adapter) > 0:
        record["serve_multilora_fairness"] = round(
            min(per_adapter) / max(per_adapter), 3
        )
    record["serve_multilora_adapters"] = {
        name: entry["tokens"] for name, entry in split.items()
    }
    log(
        f"# loadgen-smoke: multilora mixed {record['serve_multilora_tok_s']} "
        f"vs base-only {record['serve_multilora_base_tok_s']} tok/s "
        f"(ratio {record.get('serve_multilora_ratio')}, fairness "
        f"{record.get('serve_multilora_fairness')}, per-adapter "
        f"{record['serve_multilora_adapters']})"
    )
    return record, rows


def _elastic_section(
    config, params_fn, *, seed: int, log
) -> tuple[dict[str, Any], list]:
    """The live elastic leg (docs/architecture.md "Elastic fleet"): ONE
    tiny-engine replica behind a router whose autoscaler is armed with
    smoke-scale windows/cooldowns, driven by repeated ``rate_storm`` bursts
    over real HTTP. The storm must breach the tightened SLO policies, the
    actuator must spawn real engine replicas (in-process launcher — the
    same ``ReplicaLauncher`` seam the subprocess launcher plugs), and once
    the storm ends the fleet must drain back down: replica count 1→N→1
    with zero failed requests (429s are shed load, not failures) and every
    drain completing in-flight work. Record keys ``serve_elastic_*``."""
    import time

    from prime_tpu.loadgen.backends import HTTPTarget, NumericTokenizer
    from prime_tpu.loadgen.report import scenario_row
    from prime_tpu.loadgen.runner import run_schedule
    from prime_tpu.loadgen.scenario import SCENARIOS, build_schedule
    from prime_tpu.obs.slo import SloEvaluator, SloPolicy
    from prime_tpu.serve.engine import ContinuousBatchingEngine, EngineBackend
    from prime_tpu.serve.fleet import (
        AutoscalerConfig,
        FleetAutoscaler,
        ReplicaSupervisor,
        serve_fleet,
    )
    from prime_tpu.serve.server import InferenceServer

    class _ServerHandle:
        def __init__(self, srv) -> None:
            self.srv = srv
            self.url = srv.url
            self._alive = True

        def alive(self) -> bool:
            return self._alive

        def terminate(self) -> None:
            if self._alive:
                self._alive = False
                self.srv.stop()  # shuts the backing engine down too

    class _EngineLauncher:
        """In-process ReplicaLauncher: each spawn is a REAL engine behind a
        REAL InferenceServer on a fresh port — the launch seam exercised
        end to end without subprocess checkpoint loads."""

        def __init__(self) -> None:
            self.handles: list[_ServerHandle] = []

        def spawn(self) -> _ServerHandle:
            engine = ContinuousBatchingEngine(
                params_fn(), config, pad_id=0, max_slots=4, capacity=128,
                chunk=4, prefix_cache_mb=8, max_queue=16,
            )
            engine.start()
            srv = InferenceServer(
                "loadgen-smoke", EngineBackend(engine, NumericTokenizer()), port=0
            ).start()
            handle = _ServerHandle(srv)
            self.handles.append(handle)
            return handle

    # smaller bursts than the CI rate_storm default: each round must finish
    # in seconds on one tiny CPU engine so several rounds fit the smoke
    schedule = build_schedule(
        SCENARIOS["rate_storm"](seed, n=12, prompt_tokens=16, max_new_tokens=8),
        vocab=config.vocab_size,
    )
    launcher = _EngineLauncher()
    seed_handle = launcher.spawn()  # replica #1 (managed, so 1→N→1 can reap N-1)
    router = serve_fleet(
        [seed_handle.url], poll_interval=0.2, model_id="loadgen-smoke",
    )
    rows: list = []
    record: dict[str, Any] = {}
    try:
        # smoke-scale observatory: tight windows + thresholds a tiny-engine
        # storm actually breaches within seconds (the production defaults
        # would need minutes of sustained burn — right for a fleet, wrong
        # for a CI leg)
        router.slo = SloEvaluator(
            (
                SloPolicy(name="ttft_p95", kind="latency",
                          metric="serve_ttft_seconds", threshold=0.3),
                SloPolicy(name="queue_wait_p95", kind="latency",
                          metric="serve_queue_wait_seconds", threshold=0.2),
                SloPolicy(
                    name="reject_rate", kind="error_rate", source="router",
                    numerator=("fleet_admission_rejected_total",),
                    denominator=(
                        "fleet_admission_rejected_total", "fleet_requests_total",
                    ),
                    threshold=0.05,
                ),
                SloPolicy(name="utilization_floor", kind="utilization_floor",
                          metric="serve_active_slots", threshold=0.1),
            ),
            fast_s=1.5, slow_s=4.0,
        )
        supervisor = ReplicaSupervisor(launcher, membership=router.membership)
        router.attach_autoscaler(
            FleetAutoscaler(
                supervisor,
                AutoscalerConfig(
                    min_replicas=1, max_replicas=3,
                    up_cooldown_s=2.0, down_cooldown_s=3.0,
                ),
            )
        )
        # the seed replica is the one engine guaranteed alive all run
        # (LIFO retirement keeps the oldest), so the registry-windowed
        # tok_s scrapes it — spawned replicas' tokens are NOT counted
        # (they come and go mid-run; the replica-count trajectory, not
        # throughput, is this leg's headline)
        target = HTTPTarget(
            router.url,
            scrape_urls={"router": router.url, "replica0": seed_handle.url},
            timeout_s=120.0,
        )
        # warm the seed replica's shapes off the measured storm
        import httpx

        for n in sorted({len(r.prompt_ids) for r in schedule}):
            httpx.post(
                f"{seed_handle.url}/v1/chat/completions",
                json={"messages": [{"role": "user", "content": " ".join(["7"] * n)}],
                      "max_tokens": 4, "temperature": 0.0},
                timeout=120.0,
            ).raise_for_status()
        peak = 1
        failed = 0
        rounds = 6
        for i in range(rounds):
            result = run_schedule(
                schedule, target, scenario="elastic", seed=seed, time_scale=0.0,
            )
            failed += result.outcomes.get("error", 0) + result.outcomes.get(
                "timeout", 0
            )
            with router.membership._lock:
                peak = max(peak, len(router.membership.replicas))
            if i == rounds - 1:
                rows.append(scenario_row(result))
        # storm over: the idle fleet must shrink back to min (drains
        # complete in-flight work first; the poll loop keeps actuating)
        deadline = time.monotonic() + 60.0
        final = peak
        while time.monotonic() < deadline:
            with router.membership._lock:
                final = len(router.membership.replicas)
            if final <= 1 and not supervisor.pending():
                break
            time.sleep(0.25)
        # actuation counts come from the actions COUNTER, not the bounded
        # journal tail (a long run's early spawns scroll out of the tail)
        actions = {
            (s["labels"]["direction"], s["labels"]["outcome"]): int(s["value"])
            for s in router.registry.snapshot()["fleet_autoscale_actions_total"][
                "series"
            ]
        }
        ups = actions.get(("up", "spawned"), 0)
        downs = actions.get(("down", "retired"), 0)
        record = {
            "serve_elastic_tok_s": rows[0]["tok_s"] if rows else 0.0,
            "serve_elastic_peak_replicas": peak,
            "serve_elastic_final_replicas": final,
            "serve_elastic_scale_ups": ups,
            "serve_elastic_scale_downs": downs,
            "serve_elastic_failed_requests": failed,
        }
        if failed or peak < 2 or final > 1:
            record["serve_elastic_error"] = (
                f"elastic leg did not complete 1→N→1 cleanly: peak={peak} "
                f"final={final} failed={failed}"
            )
        log(
            f"# loadgen-smoke: elastic 1→{peak}→{final} "
            f"({ups} scale-ups, {downs} scale-downs, {failed} failed requests, "
            f"{record['serve_elastic_tok_s']} tok/s final round)"
        )
        return record, rows
    finally:
        router.stop()  # reaps the managed replicas through the supervisor
        for handle in launcher.handles:
            handle.terminate()


def _sentinel_section(config, params_fn, *, seed: int, log) -> dict[str, Any]:
    """The sentinel's false-positive AND true-positive gate in one leg
    (docs/observability.md "Sentinel & incidents"): a clean in-process
    server driven with steady traffic must raise ZERO incidents, then the
    same traffic against an engine with an env-injected dispatch delay
    (``PRIME_SENTINEL_INJECT_MS``, armed to activate only after the clean
    run's measured dispatch count — a genuine mid-run change-point) must
    raise EXACTLY ONE incident whose bundle carries flight timelines and
    registry deltas, fetchable over ``GET /admin/incidents``. Record keys
    ``serve_sentinel_*``."""
    import time

    import httpx

    from prime_tpu.loadgen.backends import NumericTokenizer
    from prime_tpu.obs.sentinel import Sentinel, SentinelRule
    from prime_tpu.serve.engine import ContinuousBatchingEngine, EngineBackend
    from prime_tpu.serve.server import InferenceServer

    # smoke-scale rule: tiny windows a seconds-long leg actually covers
    # (production defaults need minutes of history), fast p95 vs slow
    # MEDIAN so the slow window absorbing the regression's own samples
    # doesn't erase the change-point, and a 20 ms absolute deadband so
    # clean-run CPU timing jitter can't fire it (steps run ~1-5 ms/token;
    # the planted 120 ms/dispatch delay lands ~30 ms/token)
    rule = SentinelRule(
        name="step_clock_regression", kind="quantile_regression",
        metric="serve_decode_step_seconds", severity="critical",
        q=0.95, baseline_q=0.5, ratio=3.0, min_value=0.02,
    )
    prompt = " ".join(["7"] * 12)

    def _launch(inject: str | None):
        saved = os.environ.pop("PRIME_SENTINEL_INJECT_MS", None)
        if inject is not None:
            os.environ["PRIME_SENTINEL_INJECT_MS"] = inject
        try:
            engine = ContinuousBatchingEngine(
                params_fn(), config, pad_id=0, max_slots=4, capacity=128,
                chunk=4, prefix_cache_mb=8, max_queue=16,
            )
        finally:
            if saved is None:
                os.environ.pop("PRIME_SENTINEL_INJECT_MS", None)
            else:
                os.environ["PRIME_SENTINEL_INJECT_MS"] = saved
        engine.start()
        srv = InferenceServer(
            "loadgen-smoke", EngineBackend(engine, NumericTokenizer()), port=0
        ).start()
        srv.sentinel = Sentinel((rule,), fast_s=1.0, slow_s=3.2, min_samples=3)
        return engine, srv

    def _drive(srv, n: int, *, pause_s: float, stop_on_incident: bool) -> None:
        for _ in range(n):
            httpx.post(
                f"{srv.url}/v1/chat/completions",
                json={"messages": [{"role": "user", "content": prompt}],
                      "max_tokens": 8, "temperature": 0.0},
                timeout=120.0,
            ).raise_for_status()
            srv.observatory_sample()
            if stop_on_incident and len(srv.incidents):
                return
            if pause_s:
                time.sleep(pause_s)

    # ---- clean phase: steady traffic, zero incidents ----------------------
    engine, srv = _launch(None)
    try:
        _drive(srv, 14, pause_s=0.14, stop_on_incident=False)
        clean_incidents = len(srv.incidents)
        # the planted run replays this exact request sequence, so this
        # engine's dispatch count is where its delay should switch on
        clean_dispatches = int(getattr(engine, "_dispatch_count", 0))
    finally:
        srv.stop()

    # ---- planted phase: same traffic, delay arms mid-run ------------------
    engine, srv = _launch(f"120@{max(1, clean_dispatches)}")
    bundle: dict[str, Any] = {}
    listing: dict[str, Any] = {}
    try:
        _drive(srv, 14, pause_s=0.14, stop_on_incident=True)  # clean baseline
        deadline = time.monotonic() + 20.0
        while not len(srv.incidents) and time.monotonic() < deadline:
            _drive(srv, 4, pause_s=0.0, stop_on_incident=True)
        planted_incidents = len(srv.incidents)
        if planted_incidents:
            # the bundle must round-trip over the admin surface, not just
            # the in-process store
            listing = httpx.get(f"{srv.url}/admin/incidents", timeout=10).json()
            first = (listing.get("incidents") or [{}])[0]
            bundle = httpx.get(
                f"{srv.url}/admin/incidents/{first.get('id')}", timeout=10
            ).json()
    finally:
        srv.stop()

    bundle_ok = bool(bundle.get("flights")) and bool(bundle.get("metrics"))
    record: dict[str, Any] = {
        "serve_sentinel_clean_incidents": clean_incidents,
        "serve_sentinel_planted_incidents": planted_incidents,
        "serve_sentinel_bundle_flights": len(bundle.get("flights") or ()),
        "serve_sentinel_bundle_metrics": len(bundle.get("metrics") or ()),
    }
    if clean_incidents != 0 or planted_incidents != 1 or not bundle_ok:
        record["serve_sentinel_error"] = (
            f"sentinel leg off-contract: clean={clean_incidents} (want 0) "
            f"planted={planted_incidents} (want 1) bundle_ok={bundle_ok}"
        )
    log(
        f"# loadgen-smoke: sentinel clean={clean_incidents} incidents, "
        f"planted={planted_incidents} (rule={bundle.get('rule')}, "
        f"{record['serve_sentinel_bundle_flights']} flight timelines, "
        f"{record['serve_sentinel_bundle_metrics']} registry deltas)"
    )
    return record


def disagg_comparison(
    config,
    params_fn,
    *,
    seed: int,
    model_id: str = "disagg",
    max_slots: int = 8,
    capacity: int = 1024,
    chunk: int = 4,
    decode_chunk: int | None = None,
    prefix_cache_mb: float = 256,
    max_queue: int = 64,
    time_scale: float = 1.0,
    warmup: bool = False,
    mesh_roles: bool = False,
    log=print,
) -> tuple[dict[str, Any], list]:
    """Phase-split vs colocated, same device budget, same schedule.

    ``mesh_roles=True`` is the MULTICHIP variant: every replica becomes a
    SHARDED engine over a disjoint half of the available devices, laid out
    by its role preset (``role:prefill`` = tp-absorbing, ``role:decode`` =
    dp-absorbing, serve/mesh_config.ROLE_MESH_PRESETS; colocated ``any``
    replicas take the prefill-shaped tp layout so both cells span identical
    hardware). Run under ``XLA_FLAGS=--xla_force_host_platform_device_count=4``
    this measures the role-preset disaggregation on a forced CPU mesh — the
    open measurement from the PR 11 round.

    The long-prompt-heavy ``disagg`` scenario runs over real HTTP through a
    FleetRouter against (a) two colocated ``any``-role replicas on the
    balanced serving config, and (b) 1 prefill + 1 decode replica with KV
    migration over the prefix-cache wire format. The phase replicas run
    ROLE-TUNED engine policies — the point of disaggregation (PAPERS'
    per-topology Gemma serving tables): the prefill replica stores every
    batched-wave member's KV (``prefix_store_all``, so its exports cover
    batched admissions), and ``decode_chunk`` (None = same as ``chunk``)
    can deepen the decode replica's chunk to amortize per-dispatch
    overhead. When a deep chunk is asked for, a third cell — colocated on
    the SAME deep chunk — is also measured (``serve_disagg_colo_deep_*``):
    a both-phases replica pays for that setting in cold-admission latency
    and retirement waste (up to a whole chunk per retirement), and the
    cell shows the compromise is real rather than assumed. Returns the
    ``serve_disagg_*`` BENCH-record keys plus the SLO scenario rows.

    Honesty note: every migrated request makes its prefill replica emit ONE
    throwaway token (``max_tokens=1`` pins the KV store). The registry-
    derived row counts it; ``serve_disagg_tok_s`` subtracts those tokens so
    the committed headline counts only client-delivered tokens."""
    import concurrent.futures

    import httpx
    import jax

    from prime_tpu.loadgen.backends import HTTPTarget, NumericTokenizer
    from prime_tpu.loadgen.report import scenario_row
    from prime_tpu.loadgen.runner import run_schedule
    from prime_tpu.loadgen.scenario import SCENARIOS, build_schedule
    from prime_tpu.serve.engine import ContinuousBatchingEngine, EngineBackend
    from prime_tpu.serve.fleet import serve_fleet
    from prime_tpu.serve.server import InferenceServer

    schedule = build_schedule(SCENARIOS["disagg"](seed), vocab=config.vocab_size)
    prompt_len = len(schedule[0].prompt_ids)
    rows: dict[str, dict] = {}
    record: dict[str, Any] = {}
    decode_chunk = chunk if decode_chunk is None else decode_chunk
    cells: list[tuple[str, tuple[str, str], tuple[int, int]]] = [
        ("colocated", ("any", "any"), (chunk, chunk)),
        ("disagg", ("prefill", "decode"), (chunk, decode_chunk)),
    ]
    if decode_chunk != chunk:
        # the compromise cell: colocated on the decode role's deep chunk —
        # evidence that the role-tuned setting is NOT free for a replica
        # that must also admit cold interactive prefills
        cells.insert(1, ("colocated_deep", ("any", "any"), (decode_chunk, decode_chunk)))
    # ONE parameter set for every replica in every cell: a fleet serves one
    # checkpoint, and above all the migrated KV is only meaningful when the
    # decode replica resumes under the SAME weights that computed it —
    # per-replica params would silently benchmark an incoherent fleet
    params = params_fn(0)
    per_replica_devices = jax.device_count() // 2 if mesh_roles else 0
    if mesh_roles and per_replica_devices < 2:
        raise ValueError(
            f"mesh_roles needs >= 4 devices (2 per replica); have "
            f"{jax.device_count()} — force more with "
            "XLA_FLAGS=--xla_force_host_platform_device_count=4"
        )
    for mode, roles, chunks in cells:
        engines: list = []
        servers: list = []
        router = None
        try:
            for i, role in enumerate(roles):
                mesh_kw: dict = {"mesh_config": ""}
                engine_params = params
                if mesh_roles:
                    # role-preset layout over this replica's DISJOINT device
                    # slice (same disjointness contract as run_smoke --mesh:
                    # overlapping meshes would measure contention, not
                    # disaggregation). "any" replicas take the prefill
                    # (tp-absorbing) shape so the colocated cell spans the
                    # same hardware as the phase-split one.
                    from prime_tpu.parallel.sharding import (
                        serving_cache_spec,
                        shard_params,
                    )
                    from prime_tpu.serve.mesh_config import parse_mesh_spec

                    spec = "role:prefill" if role in ("any", "prefill") else "role:decode"
                    cfg = parse_mesh_spec(spec, per_replica_devices)
                    replica_mesh = cfg.build(
                        jax.devices()[
                            i * per_replica_devices : (i + 1) * per_replica_devices
                        ]
                    )
                    engine_params = shard_params(params, replica_mesh, config)
                    mesh_kw = {
                        "mesh": replica_mesh,
                        "cache_spec": serving_cache_spec(config, replica_mesh),
                    }
                engine = ContinuousBatchingEngine(
                    engine_params, config, pad_id=0, max_slots=max_slots,
                    capacity=capacity, chunk=chunks[i],
                    prefix_cache_mb=prefix_cache_mb, max_queue=max_queue,
                    warmup=warmup, **mesh_kw,
                    # role-tuned store policy: the prefill replica's batched
                    # waves must leave every member exportable
                    prefix_store_all=role == "prefill",
                )
                engine.start()
                engines.append(engine)
                servers.append(
                    InferenceServer(
                        model_id, EngineBackend(engine, NumericTokenizer()),
                        port=0, role=role,
                    ).start()
                )
            router = serve_fleet(
                [srv.url for srv in servers], poll_interval=0.2, model_id=model_id,
            )
            target = HTTPTarget(
                router.url,
                scrape_urls={
                    "router": router.url,
                    **{f"replica{i}": srv.url for i, srv in enumerate(servers)},
                },
                timeout_s=240.0,
            )
            # warm OFF the measured window. Direct per-replica warms compile
            # the cold prefill/decode shapes on both engines; router-path
            # warm bursts (4 concurrent, distinct non-schedule prefixes)
            # compile the batched admission widths AND — in disagg mode —
            # the migration-only shapes (the mid-length assemble_row and the
            # suffix chunk on the decode replica, the export/import path on
            # both). Warm prompts lead with reserved ids so they can never
            # prefix-hit a schedule prompt.
            def warm_ids(k: int) -> str:
                return " ".join(["2"] + [str(k)] * (prompt_len - 1))

            for srv in servers:
                httpx.post(
                    f"{srv.url}/v1/chat/completions",
                    json={
                        "messages": [{"role": "user", "content": warm_ids(0)}],
                        "max_tokens": 4, "temperature": 0.0,
                    },
                    timeout=240.0,
                ).raise_for_status()

            def warm_router(k: int) -> None:
                httpx.post(
                    f"{router.url}/v1/chat/completions",
                    json={
                        "messages": [{"role": "user", "content": warm_ids(k)}],
                        "max_tokens": 4, "temperature": 0.0,
                    },
                    timeout=240.0,
                ).raise_for_status()

            with concurrent.futures.ThreadPoolExecutor(max_workers=4) as pool:
                for _round in range(2):
                    list(
                        pool.map(warm_router, range(1 + _round * 4, 5 + _round * 4))
                    )

            result = run_schedule(
                schedule, target,
                scenario="disagg" if mode == "disagg" else f"disagg_{mode}",
                seed=seed, time_scale=time_scale, max_workers=8,
            )
            rows[mode] = scenario_row(result)
        finally:
            if router is not None:
                router.stop()
            for srv in servers:
                srv.stop()  # also shuts down the backing engine
            for engine in engines[len(servers):]:
                engine.shutdown()
    colo, split = rows["colocated"], rows["disagg"]
    fleet = split.get("fleet") or {}
    migrations = fleet.get("migrations") or {}
    # every migration whose prefill leg answered 200 emitted one throwaway
    # token — ok, cold, AND the decode-side failures; only prefill_failed
    # never got that far
    migrated = sum(
        int(v) for k, v in migrations.items() if k != "prefill_failed"
    )
    split_duration = split.get("duration_s") or 0.0
    # delivered-token throughput: drop the 1 throwaway prefill-replica
    # token per migrated request (docstring)
    split_tok_s = (
        round(max(0, split["tokens"] - migrated) / split_duration, 2)
        if split_duration
        else 0.0
    )
    record["serve_disagg_tok_s"] = split_tok_s
    record["serve_disagg_colo_tok_s"] = colo["tok_s"]
    if colo["tok_s"]:
        record["serve_disagg_speedup"] = round(split_tok_s / colo["tok_s"], 3)
    for key, row in (("serve_disagg", split), ("serve_disagg_colo", colo)):
        for q in ("p50", "p95"):
            value = (row.get("ttft_s") or {}).get(q)
            if isinstance(value, (int, float)):
                record[f"{key}_ttft_{q}_ms"] = round(value * 1e3, 3)
    deep = rows.get("colocated_deep")
    if deep is not None:
        record["serve_disagg_colo_deep_tok_s"] = deep["tok_s"]
        deep_p95 = (deep.get("ttft_s") or {}).get("p95")
        if isinstance(deep_p95, (int, float)):
            record["serve_disagg_colo_deep_ttft_p95_ms"] = round(deep_p95 * 1e3, 3)
    record["serve_disagg_migrations"] = {k: int(v) for k, v in migrations.items()}
    record["serve_disagg_migrate_bytes"] = int(fleet.get("migrate_bytes") or 0)
    record["serve_disagg_model"] = getattr(config, "name", "?")
    record["serve_disagg_chunks"] = {"colocated": chunk, "decode_role": decode_chunk}
    if mesh_roles:
        from prime_tpu.serve.mesh_config import ROLE_MESH_PRESETS

        record["serve_disagg_mesh_roles"] = dict(ROLE_MESH_PRESETS)
        record["serve_disagg_mesh_devices"] = per_replica_devices * 2
    if not int(migrations.get("ok", 0)):
        record["serve_disagg_error"] = (
            "no successful KV migration in the measured window — the "
            "phase split never engaged; both numbers are colocated"
        )
    log(
        f"# disagg: phase-split {record['serve_disagg_tok_s']} vs colocated "
        f"{record['serve_disagg_colo_tok_s']} tok/s "
        f"(migrations {record['serve_disagg_migrations']}, "
        f"{record['serve_disagg_migrate_bytes']} KV bytes shipped; TTFT p95 "
        f"{record.get('serve_disagg_ttft_p95_ms')} vs "
        f"{record.get('serve_disagg_colo_ttft_p95_ms')} ms)"
    )
    return record, [row for row in (colo, deep, split) if row is not None]


def run_smoke(
    output_dir: str,
    *,
    scenario: str = "smoke",
    seed: int | None = None,
    replicas: int = 2,
    mesh: str | None = None,
    time_scale: float = 1.0,
    log=print,
) -> dict[str, Any]:
    """Run the CPU fleet smoke end to end; returns ``{"ok", "report",
    "record", "lint"}`` and writes the artifacts into ``output_dir``.
    ``ok`` is False when the headline is zero or any exposition fails lint —
    the CI job exits nonzero on it.

    ``mesh`` (a ``"dp=1,fsdp=2,tp=2"``-style spec) builds each replica as a
    SHARDED engine spanning that mesh (serve/mesh_config.py) — under
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` this measures the
    multi-chip serving path on CPU and stamps the record with the mesh, the
    shape a committed ``MULTICHIP_*.json`` round wants (docs/benchmarking.md)."""
    # CPU pin before jax initializes: the smoke must never touch (or wait
    # for) an accelerator backend
    os.environ.setdefault("JAX_PLATFORMS", "cpu")

    import jax
    import jax.numpy as jnp

    from prime_tpu.analysis.obs_contract import load_metrics_catalog
    from prime_tpu.loadgen.backends import HTTPTarget, NumericTokenizer
    from prime_tpu.loadgen.report import build_report
    from prime_tpu.loadgen.runner import run_schedule
    from prime_tpu.loadgen.scenario import build_schedule
    from prime_tpu.models import get_config
    from prime_tpu.models.llama import init_params
    from prime_tpu.obs.metrics import lint_prometheus_text
    from prime_tpu.serve.engine import ContinuousBatchingEngine, EngineBackend
    from prime_tpu.serve.fleet import serve_fleet
    from prime_tpu.serve.server import InferenceServer

    seed = loadgen_seed_default() if seed is None else seed
    os.makedirs(output_dir, exist_ok=True)
    config = get_config("tiny-test")
    scenario_obj = SCENARIOS[scenario](seed)
    schedule = build_schedule(scenario_obj, vocab=config.vocab_size)
    log(
        f"# loadgen-smoke: scenario {scenario!r} seed {seed} -> "
        f"{len(schedule)} requests, {replicas} replicas"
        + (f", mesh {mesh}" if mesh else "")
    )

    engines: list = []
    servers: list = []
    router = None
    try:
        # sharded replicas get DISJOINT device slices: N engines all built
        # over jax.devices()[:k] would measure device contention (double-
        # subscribed HBM on real chips) and stamp it into the committed
        # MULTICHIP trajectory as a clean multichip number
        mesh_cfg = None
        if mesh:
            from prime_tpu.serve.mesh_config import parse_mesh_spec

            mesh_cfg = parse_mesh_spec(mesh, jax.device_count())
        if mesh_cfg is not None and replicas * mesh_cfg.total_devices > jax.device_count():
            raise ValueError(
                f"mesh {mesh!r} x {replicas} replicas needs "
                f"{replicas * mesh_cfg.total_devices} devices; only "
                f"{jax.device_count()} are available (drop --replicas or "
                "force more with --xla_force_host_platform_device_count)"
            )
        for i in range(replicas):
            params = init_params(jax.random.PRNGKey(i), config, dtype=jnp.float32)
            kw: dict = {"mesh_config": mesh}
            if mesh_cfg is not None and replicas > 1:
                # explicit surface: replica i's mesh over its own device
                # slice, params/cache placed by the same one-owner specs the
                # declarative path uses
                from prime_tpu.parallel.sharding import serving_cache_spec, shard_params

                need = mesh_cfg.total_devices
                replica_mesh = mesh_cfg.build(jax.devices()[i * need : (i + 1) * need])
                params = shard_params(params, replica_mesh, config)
                kw = {
                    "mesh": replica_mesh,
                    "cache_spec": serving_cache_spec(config, replica_mesh),
                }
            engine = ContinuousBatchingEngine(
                params, config, pad_id=0, max_slots=4, capacity=128, chunk=4,
                prefix_cache_mb=8, max_queue=16, **kw,
            )
            if mesh_cfg is None and replicas > 1 and engine.mesh_devices > 1:
                # PRIME_SERVE_MESH reached the engines without --mesh: every
                # replica built over the SAME first-k devices — contention,
                # not multichip serving. The explicit flag places disjointly.
                engine.shutdown()
                raise ValueError(
                    "PRIME_SERVE_MESH sharded every replica over the same "
                    "devices; pass --mesh explicitly (or --replicas 1) so "
                    "replicas get disjoint device slices"
                )
            engine.start()
            engines.append(engine)
            servers.append(
                InferenceServer(
                    "loadgen-smoke", EngineBackend(engine, NumericTokenizer()), port=0
                ).start()
            )
        router = serve_fleet(
            [srv.url for srv in servers], poll_interval=0.2, model_id="loadgen-smoke",
        )
        target = HTTPTarget(
            router.url,
            scrape_urls={
                "router": router.url,
                **{f"replica{i}": srv.url for i, srv in enumerate(servers)},
            },
            timeout_s=120.0,
        )
        # warm every prompt-length bucket the schedule will hit, per
        # replica: first-compile time belongs to startup, not to the
        # measured window's TTFT histogram bracket — warming one token
        # count would leave the other buckets' compiles inside the window
        # and the percentiles would measure XLA, not serving
        import httpx

        warm_lens = sorted({len(r.prompt_ids) for r in schedule})
        for srv in servers:
            for n in warm_lens:
                httpx.post(
                    f"{srv.url}/v1/chat/completions",
                    json={
                        "messages": [{"role": "user",
                                      "content": " ".join(["7"] * n)}],
                        "max_tokens": 4, "temperature": 0.0,
                    },
                    timeout=120.0,
                ).raise_for_status()

        result = run_schedule(
            schedule, target, scenario=scenario_obj.name, seed=seed,
            time_scale=time_scale, max_workers=8,
        )
        # stamp from the engines' ACTUAL mesh state, not the `mesh` argument:
        # PRIME_SERVE_MESH can shard the engines with mesh=None here, and a
        # sharded run labeled as single-chip would land in the wrong
        # perf-delta trajectory row (the mc-prefix design exists to prevent
        # exactly that cross-backend contamination)
        mesh_axes = engines[0].mesh_axes if engines else {}
        mesh_devices = engines[0].mesh_devices if engines else 1
        sharded = mesh_devices > 1
        mesh_desc = ",".join(f"{k}={v}" for k, v in mesh_axes.items())
        report = build_report(
            [result],
            meta={
                "backend": jax.default_backend(),
                "mode": "cpu-mesh-smoke" if sharded else "cpu-smoke",
                **({"mesh": mesh_axes, "mesh_devices": mesh_devices} if sharded else {}),
            },
        )
        headline = report["headline"]
        log(
            f"# loadgen-smoke: {headline['tok_s']} tok/s over "
            f"{headline['requests']} requests "
            f"(outcomes {dict(result.outcomes)})"
        )

        # observatory leg (docs/observability.md "Observatory"), captured
        # RIGHT after the fleet run while the fast window still covers it:
        # the live view must be well-formed — a valid scale signal, one row
        # per replica — with a NONZERO windowed token rate for the run just
        # driven (the observatory and the SLO report window the same
        # counters; a zero here while the report is nonzero means the
        # sensor layer is blind). The view JSON lands in the artifacts
        # either way, so a CI failure uploads the evidence.
        observatory: dict[str, Any] = {}
        obs_ok = False
        try:
            router.membership.poll_all()  # trailing sample closes the run window
            observatory = httpx.get(
                f"{router.url}/admin/observatory", timeout=10
            ).json()
            fleet_fast = (observatory.get("fleet") or {}).get("fast") or {}
            obs_ok = (
                observatory.get("signal", {}).get("direction")
                in ("up", "down", "hold")
                and isinstance(observatory.get("replicas"), list)
                and len(observatory["replicas"]) == replicas
                and (fleet_fast.get("tok_s") or 0) > 0
            )
            if obs_ok:
                log(
                    f"# loadgen-smoke: observatory signal "
                    f"{observatory['signal']['direction']} — fast-window "
                    f"{fleet_fast.get('tok_s')} tok/s over "
                    f"{fleet_fast.get('span_s')} s"
                )
            else:
                log(
                    "# loadgen-smoke: observatory view malformed or blind: "
                    f"signal={observatory.get('signal')} fast={fleet_fast}"
                )
        except Exception as e:  # noqa: BLE001 — the artifact write below must run
            log(f"# loadgen-smoke: observatory leg failed: {e}")
        with open(os.path.join(output_dir, "observatory.json"), "w") as f:
            json.dump(observatory, f, indent=2)

        # speculative on/off section (spec_friendly scenario, in-process
        # engines — sharded when --mesh is set). Appended to the report's
        # scenario rows WITHOUT touching the headline: the headline gate
        # stays the fleet scenario's, exactly as before.
        spec_record: dict[str, Any] = {}
        try:
            spec_record, spec_rows = _spec_section(
                config,
                lambda: init_params(jax.random.PRNGKey(0), config, dtype=jnp.float32),
                seed=seed, mesh=mesh, log=log,
            )
            report["scenarios"].extend(spec_rows)
        except Exception as e:  # noqa: BLE001 — the headline gate must survive
            spec_record = {"serve_spec_error": f"{type(e).__name__}: {e}"[:200]}
            log(f"# loadgen-smoke: spec section failed: {e}")

        # paged-vs-copy seeding section (longprefix scenario, in-process
        # tiny-test engines): record keys serve_longprefix_* — tok/s and
        # mean hit-seed ms per seeding path. Skipped under --mesh: paged
        # seeding is gated off on sharded engines (the comparison would be
        # copy vs copy).
        longprefix_record: dict[str, Any] = {}
        if not mesh:
            try:
                longprefix_record, longprefix_rows = _longprefix_section(
                    config,
                    lambda: init_params(
                        jax.random.PRNGKey(0), config, dtype=jnp.float32
                    ),
                    seed=seed, mesh=None, log=log,
                )
                report["scenarios"].extend(longprefix_rows)
            except Exception as e:  # noqa: BLE001 — the headline gate must survive
                longprefix_record = {
                    "serve_longprefix_error": f"{type(e).__name__}: {e}"[:200]
                }
                log(f"# loadgen-smoke: longprefix section failed: {e}")

        # autotune round-trip leg: dry-run sweep + artifact save/load
        # through the production resolution path (record keys autotune_*)
        autotune_record: dict[str, Any] = {}
        if not mesh:
            try:
                autotune_record = _autotune_section(log=log)
            except Exception as e:  # noqa: BLE001 — the headline gate must survive
                autotune_record = {
                    "autotune_error": f"{type(e).__name__}: {e}"[:200]
                }
                log(f"# loadgen-smoke: autotune section failed: {e}")

        # batched multi-LoRA section (mixed 3-adapter traffic through one
        # engine vs the same schedule base-only): record keys
        # serve_multilora_tok_s / _base_tok_s / _ratio / _fairness, rows
        # appended WITHOUT touching the headline gate — like the spec
        # section. Runs at debug-128m scale (see _multilora_section's scale
        # note: at tiny-test the gathered-delta op count, not the
        # architecture, is what a CPU ratio measures) and skips under --mesh
        # like the disagg section (its extra engines would contend for the
        # forced device set).
        multilora_record: dict[str, Any] = {}
        if not mesh:
            try:
                ml_config = get_config("debug-128m")
                multilora_record, multilora_rows = _multilora_section(
                    ml_config,
                    lambda: init_params(
                        jax.random.PRNGKey(0), ml_config, dtype=jnp.float32
                    ),
                    seed=seed, mesh=None, log=log,
                )
                report["scenarios"].extend(multilora_rows)
            except Exception as e:  # noqa: BLE001 — the headline gate must survive
                multilora_record = {
                    "serve_multilora_error": f"{type(e).__name__}: {e}"[:200]
                }
                log(f"# loadgen-smoke: multilora section failed: {e}")

        # elastic fleet section (the autoscaler's live 1→N→1 proof: real
        # engines spawned and drained by the actuator under a sustained
        # rate_storm; record keys serve_elastic_*). Runs at tiny-test scale
        # — the leg measures the control loop, not matmuls — and skips
        # under --mesh like the sections below (spawned replicas would
        # contend for the forced device set).
        elastic_record: dict[str, Any] = {}
        if not mesh:
            try:
                elastic_record, elastic_rows = _elastic_section(
                    config,
                    lambda: init_params(
                        jax.random.PRNGKey(3), config, dtype=jnp.float32
                    ),
                    seed=seed, log=log,
                )
                report["scenarios"].extend(elastic_rows)
            except Exception as e:  # noqa: BLE001 — the headline gate must survive
                elastic_record = {
                    "serve_elastic_error": f"{type(e).__name__}: {e}"[:200]
                }
                log(f"# loadgen-smoke: elastic section failed: {e}")

        # disaggregated prefill/decode section (phase-split vs colocated on
        # the long-prompt-heavy `disagg` scenario, real HTTP fleets both
        # ways). Runs at debug-128m scale, not tiny-test: the migration's
        # fixed per-request cost (three extra HTTP exchanges + the KV ship)
        # amortizes against real prefill compute — at tiny-test scale the
        # overhead is bigger than the prefill it offloads and the comparison
        # measures the harness, not the architecture. Rows append to the
        # report; the headline gate stays the fleet scenario's. Skipped
        # under --mesh: the section's four extra engines would contend for
        # the forced device set.
        disagg_record: dict[str, Any] = {}
        if not mesh:
            try:
                disagg_config = get_config("debug-128m")
                disagg_record, disagg_rows = disagg_comparison(
                    disagg_config,
                    lambda i: init_params(
                        jax.random.PRNGKey(i), disagg_config, dtype=jnp.float32
                    ),
                    seed=seed, model_id="loadgen-smoke", log=log,
                )
                report["scenarios"].extend(disagg_rows)
            except Exception as e:  # noqa: BLE001 — the headline gate must survive
                disagg_record = {
                    "serve_disagg_error": f"{type(e).__name__}: {e}"[:200]
                }
                log(f"# loadgen-smoke: disagg section failed: {e}")

        # sentinel section (clean run quiet / planted env-injected dispatch
        # delay raises exactly one incident with a complete bundle): record
        # keys serve_sentinel_*. Skipped under --mesh like the sections
        # above — its two extra engines would contend for the forced
        # device set.
        sentinel_record: dict[str, Any] = {}
        if not mesh:
            try:
                sentinel_record = _sentinel_section(
                    config,
                    lambda: init_params(
                        jax.random.PRNGKey(0), config, dtype=jnp.float32
                    ),
                    seed=seed, log=log,
                )
            except Exception as e:  # noqa: BLE001 — the headline gate must survive
                sentinel_record = {
                    "serve_sentinel_error": f"{type(e).__name__}: {e}"[:200]
                }
                log(f"# loadgen-smoke: sentinel section failed: {e}")

        # exposition lint, pinned to the documented catalog: every /metrics
        # surface the smoke stood up must be well-formed AND in-contract
        doc_path = os.path.join(
            os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
            "docs", "observability.md",
        )
        catalog = None
        if os.path.exists(doc_path):
            with open(doc_path) as f:
                catalog = load_metrics_catalog(f.read())
        lint: dict[str, list[str]] = {}
        for label, text in target.expositions().items():
            problems = lint_prometheus_text(text, catalog=catalog)
            if problems:
                lint[label] = problems
                log(f"# loadgen-smoke: exposition lint FAILED for {label}:")
                for p in problems:
                    log(f"#   {p}")

        metric = (
            f"serve_sharded_tok_s (tiny-test, {replicas}x sharded replica "
            f"over mesh {mesh_desc}, scenario {scenario_obj.name})"
            if sharded
            else f"loadgen_smoke_tok_s (tiny-test, {replicas}-replica fleet, "
                 f"scenario {scenario_obj.name})"
        )
        record = {
            "schema": 2,
            "metric": metric,
            "value": headline["tok_s"],
            "unit": "tokens/s",
            "vs_baseline": 0.0,
            "backend": jax.default_backend(),
            **({"mesh": mesh_axes, "mesh_devices": mesh_devices} if sharded else {}),
            **spec_record,
            **longprefix_record,
            **autotune_record,
            **multilora_record,
            **elastic_record,
            **disagg_record,
            **sentinel_record,
            "loadgen": report,
        }
        with open(os.path.join(output_dir, "slo_report.json"), "w") as f:
            json.dump(report, f, indent=2)
        with open(os.path.join(output_dir, "bench_record.json"), "w") as f:
            json.dump(record, f, indent=2)
        with open(os.path.join(output_dir, "flight.json"), "w") as f:
            json.dump(result.flight, f, indent=2)
        ok = headline["tok_s"] > 0 and not lint and obs_ok
        log(
            f"# loadgen-smoke: {'OK' if ok else 'FAILED'} — artifacts in "
            f"{output_dir}"
        )
        return {
            "ok": ok,
            "report": report,
            "record": record,
            "lint": lint,
            "observatory": observatory,
        }
    finally:
        if router is not None:
            router.stop()
        for srv in servers:
            srv.stop()  # also shuts down the backing engine
        for engine in engines[len(servers):]:
            engine.shutdown()


def run_disagg_mesh_round(
    output_dir: str,
    *,
    seed: int | None = None,
    log=print,
) -> dict[str, Any]:
    """The MULTICHIP disaggregation round (the open measurement from the
    disagg PR): :func:`disagg_comparison` with ``mesh_roles=True`` — every
    replica a sharded engine over a disjoint half of the forced CPU device
    set, laid out by its ``role:prefill`` / ``role:decode`` preset — at
    debug-128m scale. Writes ``bench_record.json`` in the MULTICHIP record
    shape (mesh-stamped schema 2, ``serve_disagg_*`` keys plus the SLO
    scenario rows under ``loadgen``) for committing as
    ``MULTICHIP_loadgen_cpu_rNN.json``. Run under
    ``XLA_FLAGS=--xla_force_host_platform_device_count=4``."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")

    import jax
    import jax.numpy as jnp

    from prime_tpu.models import get_config
    from prime_tpu.models.llama import init_params

    seed = loadgen_seed_default() if seed is None else seed
    os.makedirs(output_dir, exist_ok=True)
    config = get_config("debug-128m")
    record, rows = disagg_comparison(
        config,
        lambda i: init_params(jax.random.PRNGKey(i), config, dtype=jnp.float32),
        seed=seed, model_id="disagg-mesh", mesh_roles=True, log=log,
    )
    total = sum(r.get("tokens", 0) for r in rows)
    duration = sum(r.get("duration_s") or 0.0 for r in rows)
    report = {
        "slo_schema": 1,
        "scenarios": rows,
        "headline": {
            "tok_s": round(total / duration, 2) if duration else 0.0,
            "tokens": int(total),
            "duration_s": round(duration, 6),
            "requests": sum(r.get("requests", 0) for r in rows),
            "rejected_429": sum(r.get("rejected_429", 0) for r in rows),
        },
    }
    out = {
        "schema": 2,
        "metric": (
            "serve_disagg_mesh_tok_s (debug-128m, role-preset meshes — "
            "prefill tp-absorbing / decode dp-absorbing — over "
            f"{record.get('serve_disagg_mesh_devices')} forced CPU devices)"
        ),
        "value": record.get("serve_disagg_tok_s", 0.0),
        "unit": "tokens/s",
        "vs_baseline": 0.0,
        "backend": jax.default_backend(),
        "mesh": record.get("serve_disagg_mesh_roles", {}),
        "mesh_devices": record.get("serve_disagg_mesh_devices", 0),
        **record,
        "loadgen": report,
    }
    with open(os.path.join(output_dir, "bench_record.json"), "w") as f:
        json.dump(out, f, indent=2)
    ok = bool(record.get("serve_disagg_tok_s", 0.0)) and not record.get(
        "serve_disagg_error"
    )
    log(f"# disagg-mesh round: {'OK' if ok else 'FAILED'} — record in {output_dir}")
    return {"ok": ok, "record": out}
