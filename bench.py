"""Benchmark: TPU throughput on the north-star paths (BASELINE.md).

Sections (each prints a `# bench:` progress line; ONE final JSON line):
  headline   decode tokens/sec — llama3.2-1b bf16, batch 8, 128+128 greedy
  eval       eval samples/sec THROUGH EvalRunner (tokenize → batch → sharded
             generate → score → results.jsonl) — the BASELINE.json metric
  serve      continuous-batching engine tokens/sec under concurrent load
  quant      int8 weights / int8 KV variants of the headline
  longctx    flash-decode pallas kernel vs XLA at C=4096 (the regime the
             kernel was built for; short-context already dispatches to XLA)

The record is last-JSON-line-wins: the headline prints as soon as it is
measured and each later section re-prints the enriched record, so a run
killed part-way still leaves a parseable line.

This file measures a TPU v5e and nothing else: a run that is not the CPU
smoke (PRIME_BENCH_SMOKE=1) exits with an error when JAX's first device is
not a TPU of a kind in V5E_DEVICE_KINDS, and exits non-zero when any section
stored an ``*_error`` key.

The reference publishes no numbers (BASELINE.json "published": {}), so
vs_baseline is the ratio against PREV_DECODE_TOK_S — this repo's round-1
measured anchor.
"""

import functools
import json
import os
import sys
import time

# Round-1 anchor (v5e-1, this repo @ first bench). vs_baseline = value / this.
PREV_DECODE_TOK_S = 1396.6

# Record schema version. Schema 1: rounds 1-5 (implicit — no "schema" key;
# scripts/perf_delta.py labels them on load). Schema 2: serve/fleet sections
# are measured through prime_tpu.loadgen (registry-snapshot-derived numbers,
# "loadgen" SLO report key).
SCHEMA_VERSION = 2

# TPU v5e single-chip peaks for the roofline fields: decode
# is HBM-bound, so each section reports achieved GB/s and % of peak from a
# bytes-moved model (weights + KV + scales); prefill is MXU-bound, so the
# headline also reports prefill MFU against the bf16 peak. The peaks are only
# true of these device kinds, so main() refuses any other.
V5E_HBM_GBS = 819.0
V5E_BF16_FLOPS = 1.97e14
V5E_DEVICE_KINDS = ("TPU v5 lite", "TPU v5e")

# PRIME_BENCH_SMOKE=1 shrinks every section to tiny-model/tiny-shape so the
# full main() path (all sections, all record fields) can be validated on CPU
# in ~a minute before a bench.py change lands.
def _env_flag(name: str) -> bool:
    return os.environ.get(name, "").lower() not in ("", "0", "false", "no")


SMOKE = _env_flag("PRIME_BENCH_SMOKE")
if SMOKE and __name__ == "__main__":
    # smoke validates bench.py's code paths on the CPU (jax is imported in
    # main(), after this), with the pallas sections (longctx/winctx variants)
    # in interpret mode so the kernel dispatch paths run end to end. Only for
    # `python bench.py`: an importer (scripts/serve_profile.py borrowing the
    # serve scenario) keeps its own backend.
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ.setdefault("PRIME_TPU_PALLAS_INTERPRET", "1")
BATCH = 2 if SMOKE else 8
PROMPT_LEN = 16 if SMOKE else 128
NEW_TOKENS = 8 if SMOKE else 128
MODEL = "tiny-test" if SMOKE else "llama3.2-1b"

# serve-section scenario, module-level so scripts/serve_profile.py profiles
# EXACTLY the workload the bench measures (tuning one tunes both)
SERVE_N_REQ, SERVE_NEW = (4, 8) if SMOKE else (16, 64)
SERVE_PROMPT_LEN = 24 if SMOKE else 96
SERVE_SLOTS = 8
SERVE_CHUNK = 8
SERVE_CAPACITY = 1024


def serve_prompts_for(config) -> list[list[int]]:
    """The serve scenario's deterministic prompt set (no shared prefixes
    between requests, so admissions exercise cold prefill)."""
    return [
        [1]
        + [(7 * (i + j)) % (config.vocab_size - 3) + 3 for j in range(SERVE_PROMPT_LEN)]
        for i in range(SERVE_N_REQ)
    ]


def _tree_bytes(params) -> int:
    """Total bytes of a parameter pytree as stored on device (bf16 weights
    2 bytes, int8 1 byte + fp scales; int4 weights are nibble-packed into
    uint8 carriers with half the elements, so itemsize covers them too)."""
    import jax

    return int(
        sum(x.size * x.dtype.itemsize for x in jax.tree_util.tree_leaves(params))
    )


def _kv_bytes_per_slot(config, kv_bytes: float) -> float:
    """Bytes one cache slot (one token position, all layers, K+V) occupies.
    kv_bytes=2 for bf16 caches; int8 caches store 1 byte + a per-(token,head)
    fp32 scale amortized over head_dim (quantize_kv in models/llama.py:95)."""
    return config.n_layers * 2 * config.n_kv_heads * config.head_dim * kv_bytes


def _decode_roofline(
    param_bytes: int, config, batch: int, ctx_avg: float, steps: int,
    seconds: float, kv_bytes: float = 2.0, prefix: str = "",
) -> dict:
    """HBM roofline for a batched decode phase: every step streams the full
    weight set once (batch shares it) and each sequence reads its KV cache at
    the running context and writes one slot. Returns achieved GB/s and % of
    the v5e peak, keyed with `prefix` so sections can carry their own."""
    slot = _kv_bytes_per_slot(config, kv_bytes)
    per_step = param_bytes + batch * slot * (ctx_avg + 1)
    gbs = per_step * steps / seconds / 1e9
    return {
        f"{prefix}hbm_model_gb_per_step": round(per_step / 1e9, 4),
        f"{prefix}hbm_gbs": round(gbs, 1),
        f"{prefix}hbm_pct_peak": round(100.0 * gbs / V5E_HBM_GBS, 1),
    }


def main() -> None:
    import jax
    import jax.numpy as jnp

    if not SMOKE:
        device = jax.devices()[0]
        if device.platform != "tpu" or device.device_kind not in V5E_DEVICE_KINDS:
            sys.exit(
                f"bench.py measures a TPU v5e ({' / '.join(V5E_DEVICE_KINDS)}); "
                f"found {device.platform} {device.device_kind!r}. "
                "PRIME_BENCH_SMOKE=1 validates the code paths on the CPU."
            )

    from prime_tpu.models import get_config
    from prime_tpu.models.llama import init_params
    from prime_tpu.models.sampler import generate

    config = get_config(MODEL)
    rng = jax.random.PRNGKey(0)
    params = init_params(rng, config, dtype=jnp.bfloat16)
    prompts = jax.random.randint(jax.random.PRNGKey(1), (BATCH, PROMPT_LEN), 1, config.vocab_size)
    lengths = jnp.full((BATCH,), PROMPT_LEN, dtype=jnp.int32)

    def time_fn(fn, iterations: int = 3) -> float:
        """Best wall-clock seconds over `iterations` (after one warmup/compile
        call). fn must end with a scalar host fetch, so the timed region
        contains the computation and not only its dispatch."""
        fn()  # warmup + compile
        best_s = float("inf")
        for _ in range(iterations):
            t0 = time.perf_counter()
            fn()
            best_s = min(best_s, time.perf_counter() - t0)
        return best_s

    def time_op(op, q0, operands, iters=None) -> float:
        """Per-invocation seconds for a decode-shaped op, with dispatch
        overhead cancelled out: jit a fori_loop that feeds the op's output
        back into the query (the data dependency serializes the chain), time
        a short and a long chain, and attribute the difference to the extra
        iterations. Operands ride as jit ARGUMENTS, not closure constants
        baked into the program. ``op(q, *operands)`` must return an array of
        q's shape."""
        if iters is None:
            # smoke runs pallas in interpret mode where every chained
            # iteration costs milliseconds — keep the chains token-length
            iters = (2, 12) if SMOKE else (10, 510)

        @functools.partial(jax.jit, static_argnames=("n",))
        def chain(q, ops, n):
            def body(_, q_cur):
                # the tiny scaled add keeps values bounded across 500 hops
                # while making every iteration depend on the previous one
                return q_cur + op(q_cur, *ops) * 1e-6
            return jax.lax.fori_loop(0, n, body, q)

        short, long_ = iters
        t_short = time_fn(
            lambda: float(jnp.sum(chain(q0, operands, short))), iterations=3
        )
        t_long = time_fn(
            lambda: float(jnp.sum(chain(q0, operands, long_))), iterations=3
        )
        if t_long <= t_short:
            # timing noise inverted the chains: a silently-floored difference
            # would record a ~10^6x phantom speedup as if it were real
            raise RuntimeError(
                f"timing inversion (t_long {t_long:.4f}s <= t_short "
                f"{t_short:.4f}s): backend too noisy for this microbench"
            )
        return (t_long - t_short) / (long_ - short)

    def run_generate(**kw):
        result = generate(
            params, prompts, lengths, config, jax.random.PRNGKey(2),
            max_new_tokens=NEW_TOKENS, temperature=0.0, **kw,
        )
        float(jnp.sum(result.tokens))

    # ---- headline ------------------------------------------------------------
    best = time_fn(run_generate)
    decode_tok_s = BATCH * NEW_TOKENS / best
    param_bytes = _tree_bytes(params)
    record = {
        "schema": SCHEMA_VERSION,
        "metric": f"decode_tokens_per_sec ({MODEL} bf16, b{BATCH}, p{PROMPT_LEN}+{NEW_TOKENS})",
        "value": round(decode_tok_s, 1),
        "unit": "tokens/s",
        "vs_baseline": round(decode_tok_s / PREV_DECODE_TOK_S, 3),
        "gen_time_s": round(best, 3),
        "backend": jax.default_backend(),
        "device": str(jax.devices()[0]),
        "param_gb": round(param_bytes / 1e9, 3),
    }
    # early print: an external kill mid-extras still leaves a nonzero record
    print(json.dumps(record), flush=True)

    # roofline: time the prefill alone (MXU-bound → MFU), then attribute the
    # remaining gen time to the decode loop (HBM-bound → achieved GB/s). The
    # FLOP model is the standard causal count: 2·N_params per token plus
    # 2·layers·heads·S²·head_dim for the score/value matmuls.
    try:
        from prime_tpu.models.llama import forward, init_cache

        prefill_cache = init_cache(config, BATCH, PROMPT_LEN + NEW_TOKENS)
        # params/cache as ARGUMENTS, not closure captures: a captured tree is
        # serialized into the program as 2.47 GB of constants
        prefill_fn = jax.jit(
            lambda p, c: forward(p, prompts, config, cache=c)[0]
        )
        prefill_s = time_fn(
            lambda: float(jnp.sum(prefill_fn(params, prefill_cache))), iterations=3
        )
        n_params = param_bytes / 2  # bf16 storage
        prefill_flops = (
            2.0 * n_params * BATCH * PROMPT_LEN
            + 2.0 * config.n_layers * config.n_heads
            * BATCH * PROMPT_LEN**2 * config.head_dim
        )
        record["prefill_time_ms"] = round(prefill_s * 1e3, 2)
        record["prefill_mfu_pct"] = round(
            100.0 * prefill_flops / prefill_s / V5E_BF16_FLOPS, 1
        )
        # only attribute decode time when the residual is clearly above
        # measurement noise — prefill_s comes from a different jitted call,
        # and a clamped near-zero residual would commit absurd GB/s numbers
        decode_s = best - prefill_s
        if decode_s > 0.2 * best:
            record.update(
                _decode_roofline(
                    param_bytes, config, BATCH, PROMPT_LEN + NEW_TOKENS / 2,
                    NEW_TOKENS, decode_s,
                )
            )
            record["decode_only_tok_s"] = round(BATCH * NEW_TOKENS / decode_s, 1)
            print(
                f"# bench: roofline prefill mfu {record['prefill_mfu_pct']}% | "
                f"decode {record['hbm_gbs']} GB/s ({record['hbm_pct_peak']}% of "
                f"v5e HBM peak)",
                flush=True,
            )
        else:
            record["roofline_note"] = (
                "decode residual below noise (prefill ~ gen time); "
                "decode-only attribution skipped"
            )
    except Exception as e:  # noqa: BLE001 — roofline must not zero the headline
        record["roofline_error"] = f"{type(e).__name__}: {e}"[:200]
    print(json.dumps(record), flush=True)

    # ---- eval: the north-star metric through the REAL runner ----------------
    # EvalRunner end to end: tokenizer encode, batch assembly (+ SPMD padding),
    # sharded generate on a 1-device mesh, scoring, results.jsonl writes —
    # the BASELINE.json "verifiers eval samples/sec" definition, not a proxy.
    try:
        import tempfile

        from prime_tpu.evals.runner import EvalRunSpec, JaxGenerator, run_eval

        eval_gen = JaxGenerator(MODEL, slice_name="v5e-1")
        with tempfile.TemporaryDirectory() as td:
            spec = EvalRunSpec(
                env="synthetic-arith",
                model=MODEL,
                limit=8 if SMOKE else 32,
                batch_size=4 if SMOKE else 8,
                max_new_tokens=16 if SMOKE else 64,
                output_dir=td,
            )
            run_eval(spec, generator=eval_gen)  # warmup: compile + first batch shapes
            result = run_eval(spec, generator=eval_gen)
        record["eval_samples_per_sec"] = round(result.metrics["samples_per_sec"], 2)
        record["eval_wall_time_s"] = round(result.metrics["wall_time_s"], 2)
        print(f"# bench: eval {record['eval_samples_per_sec']} samples/s", flush=True)
        del eval_gen
    except Exception as e:  # noqa: BLE001 — a failed extra must not zero the headline
        record["eval_error"] = f"{type(e).__name__}: {e}"[:200]
        print(f"# bench: eval section failed: {e}", flush=True)
    print(json.dumps(record), flush=True)  # checkpoint: last JSON line wins

    # ---- serve: continuous-batching engine under concurrent load ------------
    # Measured THROUGH prime_tpu.loadgen (schema 2): the prompt sets and
    # engine configs are unchanged from schema 1, but the measured window is
    # driven by the loadgen runner and every number comes from registry
    # snapshot deltas (captured_at-bracketed) instead of a client stopwatch.
    # Each section's RunResult also lands in the record's "loadgen" SLO
    # report — the same artifact the CI smoke publishes.
    from prime_tpu.loadgen import (
        EngineTarget,
        build_report,
        run_schedule,
        scenario_row,
        schedule_from_prompts,
    )

    n_req, req_new = SERVE_N_REQ, SERVE_NEW
    serve_prompt_len = SERVE_PROMPT_LEN
    serve_slots = SERVE_SLOTS
    serve_prompts = serve_prompts_for(config)
    loadgen_results: list = []
    # scenario rows produced by self-contained comparisons (the disagg
    # section builds its own HTTP fleets and returns finished rows): appended
    # to the SLO report's scenarios without touching its headline
    loadgen_rows_extra: list = []

    def run_serve(
        kv_quant: bool = False, speculative: bool = False, prompts=None,
        record_counters: bool = False, obs_key: str | None = None,
        scenario: str = "serve", mesh_config="", max_new: int | None = None,
    ) -> dict:
        """Drive one engine configuration through the loadgen runner and
        return the registry-windowed SLO row (tok/s, TPOT quantiles,
        accept ratio, ...)."""
        from prime_tpu.serve.engine import ContinuousBatchingEngine

        prompts = prompts or serve_prompts
        new_tokens = max_new if max_new is not None else req_new
        engine = ContinuousBatchingEngine(
            params, config, pad_id=0, max_slots=serve_slots,
            capacity=SERVE_CAPACITY, chunk=SERVE_CHUNK,
            kv_quant=kv_quant, speculative=speculative, mesh_config=mesh_config,
        )
        try:
            # warmup: compile prefill/decode/finalize for the buckets in play.
            # TWO passes over the same prompt: the second admission hits the
            # prompt-prefix KV cache and prefills only the suffix — a
            # DIFFERENT chunk shape whose first compile would otherwise land
            # mid-measurement (remote TPU compiles cost seconds each)
            for _ in range(2):
                warm = engine.submit(prompts[0], max_new_tokens=new_tokens)
                while not warm.done:
                    engine.tick()
            # burst warmup: distinct cold prompts (lead token 2+ so they
            # never prefix-hit the measured [1]-led set) at every
            # power-of-two wave size the engine's batched admission can
            # decompose a wave into — measurement then never compiles
            size = min(serve_slots, len(prompts))
            lead = 2
            while size >= 2:
                warm_burst = [
                    [lead] + [(11 * (lead * 31 + i + j)) % (config.vocab_size - 3) + 3
                              for j in range(len(prompts[0]) - 1)]
                    for i in range(size)
                ]
                burst_reqs = [
                    engine.submit(ids, max_new_tokens=4) for ids in warm_burst
                ]
                while not all(r.done for r in burst_reqs):
                    engine.tick()
                size //= 2
                lead += 1
            # drain warmup's lookahead chunk: its retirement waste and its
            # warmup-boundary window must not leak into the measured deltas
            engine.tick()
            waves_before = engine.batched_waves
            hits_before = engine.prefix_hits
            stats_before = engine.stats()
            # the measured window: loadgen drives the burst (time_scale=0 —
            # every arrival immediate, exactly the old submit-all loop) and
            # brackets it with registry snapshots; tok/s comes from the
            # token-counter delta over the captured_at window
            schedule = schedule_from_prompts(scenario, prompts, new_tokens)
            result = run_schedule(
                schedule, EngineTarget(engine), scenario=scenario, time_scale=0.0,
            )
            loadgen_results.append(result)
            row = scenario_row(result)
            if record_counters:
                # evidence the batched-admission path carried the MEASURED
                # window (deltas, not engine-lifetime totals — warmup hits
                # prompts[0]'s prefix by construction), and only from the
                # headline bf16 run so a failed run can't be papered over
                # by a later variant's counters
                record["serve_batched_waves"] = engine.batched_waves - waves_before
                record["serve_prefix_hits"] = engine.prefix_hits - hits_before
                # pipeline evidence from the same run: how much of the decode
                # window the host overlapped, what it blocked for, and the
                # decode the one-chunk retirement lag threw away — deltas
                # over the measured window, like the wave/hit counters above
                # (warmup's retirement waste and cold-compile windows must
                # not pollute the measured numbers)
                stats = engine.stats()
                stall = stats["host_stall_s"] - stats_before["host_stall_s"]
                window = stats["chunk_window_s"] - stats_before["chunk_window_s"]
                record["serve_overlap"] = stats["overlap"]
                record["serve_overlap_ratio"] = (
                    round(max(0.0, min(1.0, 1.0 - stall / window)), 4) if window > 0 else 0.0
                )
                record["serve_host_stall_s"] = round(stall, 6)
                record["serve_wasted_decode_tokens"] = (
                    stats["wasted_decode_tokens"] - stats_before["wasted_decode_tokens"]
                )
            if record_counters:
                # device-time capture AFTER the measured window (a capture
                # fences every dispatch, which would perturb the headline
                # tok/s): a short driven burst under an open capture yields
                # the per-phase step clock + compile/MFU summary the record
                # embeds as "device_profile" — perf_delta diffs it, and
                # rounds without it stay comparable
                try:
                    engine.profiler.start_capture()
                    profile_reqs = [
                        engine.submit(p, max_new_tokens=min(8, new_tokens))
                        for p in prompts[: min(4, len(prompts))]
                    ]
                    while not all(r.done for r in profile_reqs):
                        engine.tick()
                    engine.tick()  # retire the overlap lookahead chunk
                    capture = engine.profiler.stop_capture()
                    if capture:
                        record["device_profile"] = capture["summary"]
                except Exception as e:  # noqa: BLE001 — profiling is evidence, not the benchmark
                    print(f"# bench: device-profile capture failed: {e}", flush=True)
            if obs_key:
                # full metrics-registry snapshot (TTFT / queue-wait /
                # prefill / decode-step histograms over the warmup+measured
                # window) so BENCH_*.json carries distributions, not just
                # the headline mean
                engine.stats()  # refresh point-in-time gauges
                record[obs_key] = engine.registry.snapshot()
            return row
        finally:
            del engine

    # separate guards: an int8 failure must not mark the bf16 number failed
    try:
        record["serve_tok_s"] = round(
            run_serve(kv_quant=False, record_counters=True, obs_key="serve_obs")["tok_s"], 1
        )
        record["serve_requests"] = n_req
        # roofline approximation: with the queue longer than the slot count
        # the slots stay full, so each decode step streams the weights once
        # for `occupied` tokens plus that many caches at the mean context;
        # prefill ticks are inside the elapsed time → lower bound
        occupied = min(n_req, serve_slots)
        serve_bpt = param_bytes / occupied + _kv_bytes_per_slot(config, 2) * (
            serve_prompt_len + req_new / 2
        )
        serve_gbs = record["serve_tok_s"] * serve_bpt / 1e9
        record["serve_hbm_gbs"] = round(serve_gbs, 1)
        record["serve_hbm_pct_peak"] = round(100.0 * serve_gbs / V5E_HBM_GBS, 1)
        print(
            f"# bench: serve {record['serve_tok_s']} tok/s ({n_req} reqs, "
            f"~{record['serve_hbm_pct_peak']}% HBM peak)",
            flush=True,
        )
    except Exception as e:  # noqa: BLE001
        record["serve_error"] = f"{type(e).__name__}: {e}"[:200]
        print(f"# bench: serve section failed: {e}", flush=True)
    print(json.dumps(record), flush=True)  # checkpoint: last JSON line wins
    try:
        # int8-cache engine: same load, half the KV HBM traffic per step
        record["serve_int8_tok_s"] = round(
            run_serve(kv_quant=True, obs_key="serve_int8_obs", scenario="serve_int8")["tok_s"], 1
        )
        print(f"# bench: serve int8 {record['serve_int8_tok_s']} tok/s", flush=True)
    except Exception as e:  # noqa: BLE001
        record["serve_int8_error"] = f"{type(e).__name__}: {e}"[:200]
        print(f"# bench: serve int8 section failed: {e}", flush=True)
    print(json.dumps(record), flush=True)  # checkpoint: last JSON line wins
    try:
        # speculative on/off over the loadgen DSL's spec_friendly scenario
        # (repetitive/templated completions — the favorable regime:
        # continuations settle into loops, n-gram drafts land, and each
        # fused propose+verify dispatch emits several tokens). BOTH legs run
        # the same schedule through the registry-windowed runner, so the
        # record carries the spec-on/off tok/s + TPOT delta and the accept
        # ratio as SLO-report evidence, not stopwatch numbers. Speculation
        # now rides the overlap pipeline and (in the sharded section's mesh
        # runs) the multi-chip path — docs/architecture.md "Speculative
        # decoding".
        from prime_tpu.loadgen.report import spec_comparison_record
        from prime_tpu.loadgen.scenario import SCENARIOS, build_schedule

        spec_schedule = build_schedule(
            SCENARIOS["spec_friendly"](0), vocab=config.vocab_size
        )
        spec_prompts = [list(r.prompt_ids) for r in spec_schedule]
        spec_new = max(r.max_new_tokens for r in spec_schedule)
        off_row = run_serve(
            prompts=spec_prompts, max_new=spec_new, scenario="serve_spec_off",
        )
        on_row = run_serve(
            speculative=True, prompts=spec_prompts, max_new=spec_new,
            obs_key="serve_spec_obs", scenario="serve_spec",
        )
        record.update(spec_comparison_record(off_row, on_row, digits=1))
        print(
            f"# bench: serve speculative {record['serve_spec_tok_s']} tok/s "
            f"(spec off {record['serve_spec_off_tok_s']}, accept ratio "
            f"{record.get('serve_spec_accept_ratio')})",
            flush=True,
        )
    except Exception as e:  # noqa: BLE001
        record["serve_spec_error"] = f"{type(e).__name__}: {e}"[:200]
        print(f"# bench: serve speculative section failed: {e}", flush=True)
    print(json.dumps(record), flush=True)  # checkpoint: last JSON line wins

    # ---- serve: shared-prefix burst (radix prefix-KV cache) -----------------
    # the multi-tenant prompt shape the block cache targets: every request
    # opens with the same system preamble and diverges after it. Reports the
    # prefix-hit ratio over the measured admissions (partial hits count),
    # mean admit (prefill) latency, and one assemble dispatch per hit.
    # built OUTSIDE the try: the host-spill section below reuses these
    # prompts and must not inherit a NameError from an unrelated failure here
    pre_len = 16 if SMOKE else 64
    preamble = [1] + [(5 * j) % (config.vocab_size - 3) + 3 for j in range(pre_len - 1)]
    burst_prompts = [
        preamble
        + [
            (13 * (i * 7 + j)) % (config.vocab_size - 3) + 3
            for j in range(serve_prompt_len - pre_len)
        ]
        for i in range(n_req)
    ]
    try:
        from prime_tpu.serve.engine import ContinuousBatchingEngine

        engine = ContinuousBatchingEngine(
            params, config, pad_id=0, max_slots=serve_slots,
            capacity=SERVE_CAPACITY, chunk=SERVE_CHUNK, prefix_cache_mb=256,
            mesh_config="",  # pin single-chip: an ambient PRIME_SERVE_MESH
            # must not shard the single-chip trajectory sections
        )
        try:
            # warm twice: the first pass compiles the cold plan and stores
            # the preamble blocks; the second compiles the suffix-chunk and
            # assemble shapes the measured burst admissions will hit
            for _ in range(2):
                warm = engine.submit(list(burst_prompts[0]), max_new_tokens=req_new)
                while not warm.done:
                    engine.tick()
            engine.tick()  # drain the lookahead chunk
            before = engine.stats()
            prefill_before = (
                engine.registry.get("serve_prefill_seconds").series_snapshot()
                or {"count": 0, "sum": 0.0}
            )
            # measured burst through loadgen (registry-windowed tok/s)
            burst_schedule = schedule_from_prompts(
                "serve_prefixburst", [list(ids) for ids in burst_prompts], req_new
            )
            burst_result = run_schedule(
                burst_schedule, EngineTarget(engine),
                scenario="serve_prefixburst", time_scale=0.0,
            )
            loadgen_results.append(burst_result)
            burst_row = scenario_row(burst_result)
            after = engine.stats()
            prefill_after = engine.registry.get("serve_prefill_seconds").series_snapshot()
            hits = after["prefix_hits"] - before["prefix_hits"]
            admitted = after["requests_admitted"] - before["requests_admitted"]
            d_count = prefill_after["count"] - prefill_before["count"]
            d_sum = prefill_after["sum"] - prefill_before["sum"]
            record["serve_prefixburst_tok_s"] = burst_row["tok_s"]
            record["serve_prefixburst_hit_ratio"] = (
                round(hits / admitted, 3) if admitted else 0.0
            )
            record["serve_prefixburst_hit_tokens"] = pre_len
            record["serve_prefixburst_admit_ms_mean"] = (
                round(d_sum / d_count * 1e3, 2) if d_count else 0.0
            )
            record["serve_prefixburst_assembles"] = (
                after["prefix_assembles"] - before["prefix_assembles"]
            )
            record["serve_prefixburst_cache_bytes"] = after["prefix_cache_bytes"]
            # per-tier hit tokens (serve_prefix_hit_tokens{tier=...}): the
            # 256 MiB device budget never pressures this burst, so host
            # stays 0 here — the spill-tier section below applies pressure
            hit_hist = engine.registry.get("serve_prefix_hit_tokens")
            for tier in ("device", "host"):
                snap = hit_hist.series_snapshot(tier=tier) or {"sum": 0.0}
                record[f"serve_prefixburst_hit_tokens_{tier}"] = int(snap["sum"])
            record["serve_prefixburst_spills"] = after["prefix_spills"]
            record["serve_prefixburst_reuploads"] = after["prefix_reuploads"]
            engine.stats()  # refresh gauges for the snapshot
            record["serve_prefixburst_obs"] = engine.registry.snapshot()
            print(
                f"# bench: serve shared-prefix burst "
                f"{record['serve_prefixburst_tok_s']} tok/s, hit ratio "
                f"{record['serve_prefixburst_hit_ratio']}, admit "
                f"{record['serve_prefixburst_admit_ms_mean']} ms mean, "
                f"{record['serve_prefixburst_assembles']} assembles",
                flush=True,
            )
        finally:
            del engine
    except Exception as e:  # noqa: BLE001
        record["serve_prefixburst_error"] = f"{type(e).__name__}: {e}"[:200]
        print(f"# bench: serve shared-prefix section failed: {e}", flush=True)
    print(json.dumps(record), flush=True)  # checkpoint: last JSON line wins

    # ---- serve: host spill tier under device-budget pressure ----------------
    # the two-tier prefix cache's reason to exist: a device budget too small
    # for even one prompt's KV (1 KiB here — deliberate, deterministic
    # pressure) forces every stored segment to demote to the host tier, so
    # each later shared-preamble admission hits HOST-resident blocks and
    # pays a re-upload instead of a recompute. Proves hit_tokens{tier=host}
    # > 0 and the spill/re-upload counters move (ROADMAP Open item 3).
    try:
        from prime_tpu.serve.engine import ContinuousBatchingEngine

        engine = ContinuousBatchingEngine(
            params, config, pad_id=0, max_slots=serve_slots,
            capacity=SERVE_CAPACITY, chunk=SERVE_CHUNK,
            prefix_cache_mb=1 / 1024, prefix_cache_host_mb=64,
            mesh_config="",  # pin single-chip (see the prefix section above)
        )
        try:
            for ids in burst_prompts[:3]:
                req = engine.submit(list(ids), max_new_tokens=req_new)
                while not req.done:
                    engine.tick()
            engine.tick()  # drain the lookahead chunk
            tier_stats = engine.stats()
            host_snap = engine.registry.get("serve_prefix_hit_tokens").series_snapshot(
                tier="host"
            ) or {"count": 0, "sum": 0.0}
            record["serve_prefixhost_hit_tokens"] = int(host_snap["sum"])
            record["serve_prefixhost_hits"] = int(host_snap["count"])
            record["serve_prefixhost_spills"] = tier_stats["prefix_spills"]
            record["serve_prefixhost_reuploads"] = tier_stats["prefix_reuploads"]
            record["serve_prefixhost_host_bytes"] = tier_stats["prefix_cache_host_bytes"]
            record["serve_prefixhost_obs"] = engine.registry.snapshot()
            print(
                f"# bench: serve host spill tier "
                f"{record['serve_prefixhost_hit_tokens']} host-tier hit tokens "
                f"over {record['serve_prefixhost_hits']} hits, "
                f"{record['serve_prefixhost_spills']} spills, "
                f"{record['serve_prefixhost_reuploads']} re-uploads, "
                f"{record['serve_prefixhost_host_bytes']} host bytes",
                flush=True,
            )
        finally:
            del engine
    except Exception as e:  # noqa: BLE001
        record["serve_prefixhost_error"] = f"{type(e).__name__}: {e}"[:200]
        print(f"# bench: serve host spill tier section failed: {e}", flush=True)
    print(json.dumps(record), flush=True)  # checkpoint: last JSON line wins

    # ---- serve fleet: 2-replica router, shared-prefix burst -----------------
    # the multi-replica control plane (docs/architecture.md "Serve fleet"):
    # two in-process engines behind a FleetRouter, driven over real HTTP with
    # the same shared-preamble burst as the prefix section. Reports aggregate
    # tok/s and the affinity hit ratio — the fraction of keyed requests the
    # consistent-hash scheduler landed on their prefix-cache-warm replica.
    try:
        import httpx

        from prime_tpu.loadgen import HTTPTarget, NumericTokenizer
        from prime_tpu.serve.engine import ContinuousBatchingEngine, EngineBackend
        from prime_tpu.serve.fleet import serve_fleet
        from prime_tpu.serve.server import InferenceServer

        fleet_slots = max(2, serve_slots // 2)
        # construct INSIDE the guarded block: a failed second server or
        # router must not leak running engine threads (and their KV device
        # allocations) into the later bench sections
        engines: list = []
        servers: list = []
        router = None
        try:
            for _ in range(2):
                engine = ContinuousBatchingEngine(
                    params, config, pad_id=0, max_slots=fleet_slots,
                    capacity=SERVE_CAPACITY, chunk=SERVE_CHUNK, prefix_cache_mb=256,
                    mesh_config="",  # pin single-chip fleet replicas
                )
                engine.start()
                engines.append(engine)
                servers.append(
                    InferenceServer(
                        "bench-fleet", EngineBackend(engine, NumericTokenizer()), port=0
                    ).start()
                )
            router = serve_fleet(
                [srv.url for srv in servers], poll_interval=0.2, model_id="bench-fleet",
            )
            pre_len = 16 if SMOKE else 64
            fleet_prompts = [
                [(5 * j) % (config.vocab_size - 3) + 3 for j in range(pre_len)]
                + [
                    (13 * (i * 7 + j)) % (config.vocab_size - 3) + 3
                    for j in range(serve_prompt_len - pre_len)
                ]
                for i in range(n_req)
            ]
            # the measured burst goes over real HTTP through the router; the
            # report scrapes BOTH replicas' engine registries plus the
            # router's, so fleet tok/s aggregates server-side token counters
            target = HTTPTarget(
                router.url,
                scrape_urls={
                    "router": router.url,
                    **{f"replica{i}": srv.url for i, srv in enumerate(servers)},
                },
                timeout_s=240.0,
            )

            # warm each replica directly (compile prefill/decode/assemble off
            # the measured clock), then let the router's poller observe them
            warm_body = {
                "messages": [{"role": "user",
                              "content": " ".join(str(t) for t in fleet_prompts[0])}],
                "max_tokens": req_new, "temperature": 0.0,
            }
            for srv in servers:
                for _ in range(2):
                    httpx.post(
                        f"{srv.url}/v1/chat/completions", json=warm_body, timeout=240.0,
                    ).raise_for_status()
            time.sleep(0.5)
            fleet_schedule = schedule_from_prompts(
                "serve_fleet", fleet_prompts, req_new
            )
            fleet_result = run_schedule(
                fleet_schedule, target, scenario="serve_fleet", time_scale=0.0,
                max_workers=8,
            )
            loadgen_results.append(fleet_result)
            fleet_row = scenario_row(fleet_result)
            stats = router.stats()
            record["serve_fleet_tok_s"] = fleet_row["tok_s"]
            # the old fleet_post raise_for_status aborted the section on any
            # failed request; loadgen folds failures into outcomes instead —
            # surface them at record level so a half-dead fleet's survivor
            # throughput can never read as a healthy number
            if fleet_result.outcomes.get("failed"):
                record["serve_fleet_error"] = (
                    f"{fleet_result.outcomes['failed']} of {len(fleet_schedule)} "
                    "requests failed; tok_s covers survivors only"
                )
            record["serve_fleet_affinity_ratio"] = stats["affinity_hit_ratio"]
            record["serve_fleet_reroutes"] = stats["reroutes"]
            # placement split: requests landed by advertised cached prefix
            # (digest-guided saturation fallback) vs by the consistent hash
            # (affinity target or blind least-loaded). Both terms are
            # per-PICK counters — requests_by_replica counts per forward
            # attempt, which double-counts failover retries
            record["serve_fleet_cache_routed"] = stats["cache_routed"]
            record["serve_fleet_hash_routed"] = (
                stats["affinity_requests"] - stats["cache_routed"]
            )
            record["serve_fleet_requests_by_replica"] = {
                rid: sum(outcomes.values())
                for rid, outcomes in stats["requests_by_replica"].items()
            }
            record["serve_fleet_obs"] = router.registry.snapshot()
            print(
                f"# bench: serve fleet (2 replicas) {record['serve_fleet_tok_s']} "
                f"tok/s aggregate, affinity hit ratio "
                f"{record['serve_fleet_affinity_ratio']}, per-replica "
                f"{record['serve_fleet_requests_by_replica']}",
                flush=True,
            )
        finally:
            if router is not None:
                router.stop()
            for srv in servers:
                srv.stop()  # also shuts down the backing engine
            for engine in engines[len(servers):]:
                engine.shutdown()  # engine started but its server never did
    except Exception as e:  # noqa: BLE001
        record["serve_fleet_error"] = f"{type(e).__name__}: {e}"[:200]
        print(f"# bench: serve fleet section failed: {e}", flush=True)
    print(json.dumps(record), flush=True)  # checkpoint: last JSON line wins

    # ---- serve: disaggregated prefill/decode (phase-split fleet) ------------
    # The long-prompt-heavy `disagg` scenario against the SAME two-engine
    # device budget twice: colocated (two any-role replicas) vs phase-split
    # (1 prefill + 1 decode replica, KV migrated over GET/PUT /admin/kv in
    # the prefix-cache wire format). Both runs are registry-windowed through
    # loadgen; the record carries both tok/s, both TTFT p95s, and the
    # migration outcome/byte evidence — docs/architecture.md "Disaggregated
    # serving". Engine warmup is off here (the direct + router warm passes
    # inside the comparison cover the shapes in play; AOT warmup on a remote
    # TPU costs minutes per engine).
    try:
        from prime_tpu.loadgen.scenario import loadgen_seed_default
        from prime_tpu.loadgen.smoke import disagg_comparison

        # smoke mode swaps the tiny bench model for debug-128m: at tiny-test
        # scale the migration's fixed per-request cost dwarfs the prefill it
        # offloads, so the comparison would measure the harness, not the
        # architecture (same rule the loadgen smoke's disagg section follows)
        # — a real-model bench round keeps the bench checkpoint
        if SMOKE:
            from prime_tpu.models import get_config as _get_config

            disagg_config = _get_config("debug-128m")
            disagg_params = init_params(
                jax.random.PRNGKey(0), disagg_config, dtype=jnp.float32
            )
        else:
            disagg_config, disagg_params = config, params
        disagg_record, disagg_rows = disagg_comparison(
            disagg_config, lambda i: disagg_params, seed=loadgen_seed_default(),
            model_id="bench-disagg", max_slots=max(2, serve_slots // 2),
            capacity=SERVE_CAPACITY, chunk=SERVE_CHUNK, warmup=False,
            log=lambda msg: print(f"# bench: {msg.lstrip('# ')}", flush=True),
        )
        record.update(disagg_record)
        # the comparison builds its own HTTP fleets, so its RunResults are
        # already folded into the rows it returns — append them to the SLO
        # report the same way the in-process sections' results are
        loadgen_rows_extra.extend(disagg_rows)
    except Exception as e:  # noqa: BLE001
        record["serve_disagg_error"] = f"{type(e).__name__}: {e}"[:200]
        print(f"# bench: serve disagg section failed: {e}", flush=True)
    print(json.dumps(record), flush=True)  # checkpoint: last JSON line wins

    # ---- sharded replica serve section (the MULTICHIP serving number) -------
    # ONE engine spanning every visible device (docs/architecture.md "Sharded
    # replica"): the engine builds the (dp, fsdp, tp) mesh from a declarative
    # spec, places params + paged KV as NamedSharding arrays, and is measured
    # through the same loadgen path as the single-chip sections — so the
    # tok/s is registry-windowed and the run lands in the SLO report as its
    # own scenario. On the virtual-device CPU smoke this is the committed
    # MULTICHIP trajectory's serving number; on a real slice it is the
    # per-topology throughput PAPERS' Gemma-on-TPU serving table reports.
    try:
        import math as _math

        n_dev = jax.device_count()
        if n_dev > 1:
            # tp over the kv heads it must divide; the rest of the slice
            # becomes the fsdp data axis (batch = slots shard over it)
            tp = _math.gcd(n_dev, config.n_kv_heads)
            mesh_spec = f"dp=1,fsdp={n_dev // tp},tp={tp}"
            record["serve_mesh"] = mesh_spec
            record["serve_mesh_devices"] = n_dev
            record["serve_sharded_tok_s"] = round(
                run_serve(
                    obs_key="serve_sharded_obs", scenario="serve_sharded",
                    mesh_config=mesh_spec,
                )["tok_s"],
                1,
            )
            print(
                f"# bench: serve sharded {record['serve_sharded_tok_s']} tok/s "
                f"(one replica over mesh {mesh_spec}, {n_dev} devices)",
                flush=True,
            )
        else:
            print("# bench: serve sharded section skipped (single device)", flush=True)
    except Exception as e:  # noqa: BLE001
        record["serve_sharded_error"] = f"{type(e).__name__}: {e}"[:200]
        print(f"# bench: serve sharded section failed: {e}", flush=True)
    print(json.dumps(record), flush=True)  # checkpoint: last JSON line wins

    # ---- loadgen SLO report over every serve section ------------------------
    # the schema-2 artifact: one row per driven scenario (serve, int8, spec,
    # prefixburst, fleet) with registry-derived tok/s, TTFT/TPOT p50/p95,
    # overlap and hit ratios — what scripts/perf_delta.py flattens into the
    # per-PR trajectory and scripts/serve_profile.py --slo merges with traces
    try:
        if loadgen_results or loadgen_rows_extra:
            # loadgen_rows_extra alone still produces a report: the disagg
            # comparison builds its own fleets, so its rows must survive
            # even a round where every in-process serve section failed
            record["loadgen"] = build_report(
                loadgen_results,
                meta={"backend": record.get("backend", "unknown")},
                device_profile=record.get("device_profile"),
            )
            # disagg-comparison rows ride along without joining the headline
            # (their fleets are separate stacks; the headline stays the
            # driven-engine sections' aggregate, exactly as before)
            record["loadgen"]["scenarios"].extend(loadgen_rows_extra)
            headline = record["loadgen"]["headline"]
            print(
                f"# bench: loadgen SLO report — {len(loadgen_results)} scenarios, "
                f"aggregate {headline['tok_s']} tok/s over "
                f"{headline['requests']} requests",
                flush=True,
            )
    except Exception as e:  # noqa: BLE001
        record["loadgen_error"] = f"{type(e).__name__}: {e}"[:200]
        print(f"# bench: loadgen report assembly failed: {e}", flush=True)
    print(json.dumps(record), flush=True)  # checkpoint: last JSON line wins

    # ---- serve fleet: cache-aware vs blind routing (deterministic sim) ------
    # pure balancer-level A/B — no sockets, no engines, no clocks — of the
    # tentpole routing upgrade: the same saturating shared-preamble workload
    # placed twice, once with replicas advertising their hot-prefix digests
    # (saturation fallback diverts to the longest advertised cached prefix)
    # and once blind (pre-digest least-loaded). Scores each placement by how
    # many leading blocks of the request the chosen replica's ACTUAL cache
    # held; the digest run must win the hit-token ratio.
    try:
        from collections import Counter as _Counter
        from collections import deque as _deque

        from prime_tpu.serve.digest import (
            HotPrefixDigest,
            longest_match_blocks,
            prefix_hashes,
        )
        from prime_tpu.serve.fleet.balancer import PrefixAffinityBalancer
        from prime_tpu.serve.fleet.membership import FleetMembership

        # 12 tenant groups over 4 replicas in deterministic-but-irregular
        # (LCG) arrival order: each preamble spans 3 digest blocks, each tail
        # is request-unique. Replica retention is the REAL bounded
        # HotPrefixDigest LRU (20 entries ~ a few groups' chains), so blind
        # scattering churns a replica's hot set while cache-aware placement
        # keeps re-landing a group where its preamble still survives.
        sim_prompts, lcg = [], 1
        for i in range(120):
            lcg = (lcg * 1103515245 + 12345) % (1 << 31)
            preamble = (f"tenant {lcg % 12} system preamble block " * 12)[:192]
            sim_prompts.append(preamble + f" user question {i} " * 8)

        def _route_sim(cache_aware: bool) -> tuple[float, int]:
            membership = FleetMembership(
                [f"http://10.0.0.{i}:9" for i in (1, 2, 3, 4)]
            )
            # saturation_depth=1: a backlog of one is tolerable, two diverts —
            # leaves multiple unsaturated candidates at UNEQUAL loads, the
            # regime where digest depth and least-loaded genuinely disagree
            balancer = PrefixAffinityBalancer(membership, saturation_depth=1)
            caches = {
                rid: HotPrefixDigest(max_entries=20) for rid in membership.replicas
            }
            recent: _deque = _deque(maxlen=6)  # each request occupies its
            # replica for the next 6 placements — emergent saturation
            hit_blocks = total_blocks = cache_routed = 0
            for prompt in sim_prompts:
                depths = _Counter(recent)
                for rid, replica in membership.replicas.items():
                    replica.queue_depth = depths.get(rid, 0)
                pick = balancer.pick(prompt)
                chain = prefix_hashes(prompt)
                hit_blocks += longest_match_blocks(
                    chain, set(caches[pick.replica.id].hashes())
                )
                total_blocks += len(chain)
                cache_routed += bool(pick.cache_routed)
                caches[pick.replica.id].observe(prompt)
                if cache_aware:
                    pick.replica.digest = frozenset(caches[pick.replica.id].hashes())
                recent.append(pick.replica.id)
            return hit_blocks / total_blocks, cache_routed

        aware_ratio, aware_cache_routed = _route_sim(cache_aware=True)
        blind_ratio, _ = _route_sim(cache_aware=False)
        record["serve_fleet_routesim_hit_ratio_cache_aware"] = round(aware_ratio, 4)
        record["serve_fleet_routesim_hit_ratio_blind"] = round(blind_ratio, 4)
        record["serve_fleet_routesim_cache_routed"] = aware_cache_routed
        record["serve_fleet_routesim_requests"] = len(sim_prompts)
        print(
            f"# bench: fleet routing sim prefix-hit-token ratio "
            f"{record['serve_fleet_routesim_hit_ratio_cache_aware']} cache-aware vs "
            f"{record['serve_fleet_routesim_hit_ratio_blind']} blind "
            f"({aware_cache_routed}/{len(sim_prompts)} cache-routed)",
            flush=True,
        )
    except Exception as e:  # noqa: BLE001
        record["serve_fleet_routesim_error"] = f"{type(e).__name__}: {e}"[:200]
        print(f"# bench: fleet routing sim failed: {e}", flush=True)
    print(json.dumps(record), flush=True)  # checkpoint: last JSON line wins

    # ---- quant: int8 weights / int8 KV --------------------------------------
    try:
        from prime_tpu.models.quantize import quantize_params_int8

        qparams = quantize_params_int8(params)

        def run_q(kv_quant=False):
            # auto dispatch: int8 caches ride the flash kernel at long
            # context now (round 4); short-context headline stays XLA
            result = generate(
                qparams, prompts, lengths, config, jax.random.PRNGKey(2),
                max_new_tokens=NEW_TOKENS, temperature=0.0,
                **({"kv_quant": True} if kv_quant else {}),
            )
            float(jnp.sum(result.tokens))

        q_s = time_fn(run_q)
        qkv_s = time_fn(lambda: run_q(kv_quant=True))
        record["int8_weights_tok_s"] = round(BATCH * NEW_TOKENS / q_s, 1)
        record["int8_weights_kv_tok_s"] = round(BATCH * NEW_TOKENS / qkv_s, 1)
        # roofline over the full gen time (prefill included → lower bound);
        # int8 caches move 1 byte/elem plus a 4-byte fp32 scale per slot-head
        qparam_bytes = _tree_bytes(qparams)
        record["int8_param_gb"] = round(qparam_bytes / 1e9, 3)
        ctx_avg = PROMPT_LEN + NEW_TOKENS / 2
        record.update(
            _decode_roofline(
                qparam_bytes, config, BATCH, ctx_avg, NEW_TOKENS, q_s,
                prefix="int8_",
            )
        )
        record.update(
            _decode_roofline(
                qparam_bytes, config, BATCH, ctx_avg, NEW_TOKENS, qkv_s,
                kv_bytes=1 + 4 / config.head_dim, prefix="int8_kv_",
            )
        )
        print(
            f"# bench: int8 weights {record['int8_weights_tok_s']} tok/s "
            f"({record['int8_hbm_pct_peak']}% HBM peak)",
            flush=True,
        )
        # int4 weights (W4A16 group-wise): half the int8 weight bytes again —
        # at decode the weights dominate HBM traffic, so this is the deepest
        # single-chip bandwidth lever in the stack. Nested guard: an int4
        # failure must not erase the int8 numbers already recorded above.
        try:
            from prime_tpu.models.quantize import quantize_params_int4

            q4params = quantize_params_int4(params)

            def run_q4(kv_quant=False):
                result = generate(
                    q4params, prompts, lengths, config, jax.random.PRNGKey(2),
                    max_new_tokens=NEW_TOKENS, temperature=0.0,
                    **({"kv_quant": True} if kv_quant else {}),
                )
                float(jnp.sum(result.tokens))

            q4_s = time_fn(run_q4)
            q4kv_s = time_fn(lambda: run_q4(kv_quant=True))
            record["int4_weights_tok_s"] = round(BATCH * NEW_TOKENS / q4_s, 1)
            record["int4_weights_kv_tok_s"] = round(BATCH * NEW_TOKENS / q4kv_s, 1)
            q4param_bytes = _tree_bytes(q4params)
            record["int4_param_gb"] = round(q4param_bytes / 1e9, 3)
            record.update(
                _decode_roofline(
                    q4param_bytes, config, BATCH, ctx_avg, NEW_TOKENS, q4_s,
                    prefix="int4_",
                )
            )
            record.update(
                _decode_roofline(
                    q4param_bytes, config, BATCH, ctx_avg, NEW_TOKENS, q4kv_s,
                    kv_bytes=1 + 4 / config.head_dim, prefix="int4_kv_",
                )
            )
            print(
                f"# bench: int4 weights {record['int4_weights_tok_s']} tok/s "
                f"({record['int4_hbm_pct_peak']}% HBM peak)",
                flush=True,
            )
        except Exception as e:  # noqa: BLE001
            record["int4_error"] = f"{type(e).__name__}: {e}"[:200]
            print(f"# bench: int4 subsection failed: {e}", flush=True)
    except Exception as e:  # noqa: BLE001
        record["quant_error"] = f"{type(e).__name__}: {e}"[:200]
        print(f"# bench: quant section failed: {e}", flush=True)
    print(json.dumps(record), flush=True)  # checkpoint: last JSON line wins

    # ---- longctx: flash-decode pallas kernel vs XLA at C=4096 ---------------
    # The regime the kernel exists for (short context dispatches to XLA via
    # PRIME_TPU_FLASH_DECODE_MIN_C): prove it or retire it.
    try:
        # prompt+new must be a multiple of the flash-decode kernel's 128-slot
        # block: generate() sizes the cache to exactly prompt+new, and the
        # forced impl="pallas" path skips the auto-dispatch alignment check —
        # an unaligned capacity makes the kernel's last block misread the tail
        lc_batch, lc_prompt, lc_new = (2, 120, 8) if SMOKE else (4, 4032, 64)
        lc_prompts = jax.random.randint(
            jax.random.PRNGKey(3), (lc_batch, lc_prompt), 1, config.vocab_size
        )

        def run_lc(impl):
            result = generate(
                params,
                lc_prompts,
                jnp.full((lc_batch,), lc_prompt, dtype=jnp.int32),
                config,
                jax.random.PRNGKey(2),
                max_new_tokens=lc_new,
                temperature=0.0,
                attn_impl=impl,
            )
            float(jnp.sum(result.tokens))

        xla_s = time_fn(lambda: run_lc("xla"), iterations=2)
        pallas_s = time_fn(lambda: run_lc("pallas"), iterations=2)
        record["longctx_xla_tok_s"] = round(lc_batch * lc_new / xla_s, 1)
        record["longctx_pallas_tok_s"] = round(lc_batch * lc_new / pallas_s, 1)
        record["longctx_pallas_speedup"] = round(xla_s / pallas_s, 3)
        print(
            f"# bench: longctx C={lc_prompt + lc_new} pallas {record['longctx_pallas_tok_s']} vs "
            f"xla {record['longctx_xla_tok_s']} tok/s",
            flush=True,
        )
        # int8-KV at long context: the round-4 kernel streams half the cache
        # bytes with scales folded — the regime the variant exists for
        def run_lc_q(impl):
            result = generate(
                params,
                lc_prompts,
                jnp.full((lc_batch,), lc_prompt, dtype=jnp.int32),
                config,
                jax.random.PRNGKey(2),
                max_new_tokens=lc_new,
                temperature=0.0,
                attn_impl=impl,
                kv_quant=True,
            )
            float(jnp.sum(result.tokens))

        q_xla_s = time_fn(lambda: run_lc_q("xla"), iterations=2)
        q_pallas_s = time_fn(lambda: run_lc_q("pallas"), iterations=2)
        record["longctx_int8kv_xla_tok_s"] = round(lc_batch * lc_new / q_xla_s, 1)
        record["longctx_int8kv_pallas_tok_s"] = round(lc_batch * lc_new / q_pallas_s, 1)
        record["longctx_int8kv_pallas_speedup"] = round(q_xla_s / q_pallas_s, 3)
        print(
            f"# bench: longctx int8-KV pallas {record['longctx_int8kv_pallas_tok_s']} vs "
            f"xla {record['longctx_int8kv_xla_tok_s']} tok/s",
            flush=True,
        )
        # rooflines LAST and exception-isolated: attribute decode-only time
        # by timing the long prefill once — at C≈4k the prefill dominates the
        # gen call, so the raw gen time would understate the decode kernel's
        # achieved bandwidth severalfold. A failure here (e.g. OOM from the
        # extra prefill cache) must not lose the tok/s comparisons above.
        try:
            from prime_tpu.models.llama import forward as _fwd, init_cache as _ic

            lc_cache = _ic(config, lc_batch, lc_prompt + lc_new)
            # args not closures — see the headline prefill_fn note
            lc_pre_fn = jax.jit(
                lambda p, c: _fwd(p, lc_prompts, config, cache=c)[0]
            )
            lc_pre_s = time_fn(
                lambda: float(jnp.sum(lc_pre_fn(params, lc_cache))), iterations=2
            )
            record["longctx_prefill_ms"] = round(lc_pre_s * 1e3, 1)
            # same noise guard as the headline: both operands are large and noisy
            if pallas_s - lc_pre_s > 0.2 * pallas_s:
                record.update(
                    _decode_roofline(
                        param_bytes, config, lc_batch, lc_prompt + lc_new / 2,
                        lc_new, pallas_s - lc_pre_s, prefix="longctx_",
                    )
                )
            if q_pallas_s - lc_pre_s > 0.2 * q_pallas_s:
                record.update(
                    _decode_roofline(
                        param_bytes, config, lc_batch, lc_prompt + lc_new / 2,
                        lc_new, q_pallas_s - lc_pre_s,
                        kv_bytes=1 + 4 / config.head_dim,
                        prefix="longctx_int8kv_",
                    )
                )
        except Exception as e:  # noqa: BLE001
            record["longctx_roofline_error"] = f"{type(e).__name__}: {e}"[:200]
    except Exception as e:  # noqa: BLE001
        record["longctx_error"] = f"{type(e).__name__}: {e}"[:200]
        print(f"# bench: longctx section failed: {e}", flush=True)
    print(json.dumps(record), flush=True)  # checkpoint: last JSON line wins

    # ---- mlactx: MLA latent-cache decode at long context --------------------
    # The round-5 architecture lever: a 1.16B MLA model (DeepSeek-V2 dims,
    # rank-512 latent) decodes at the same long context as the llama longctx
    # section. Per token per layer the latent streams 577*2 ≈ 1.1 KiB vs the
    # 8 KiB its own 16x(128+64) heads would need as full K/V (7x) and the
    # 2 KiB of llama3.2-1b's already-GQA-compressed 8x64 cache (1.7x) —
    # MLA reaches GQA-class cache size WITHOUT sharing heads, at 16 full-
    # width query heads.
    try:
        mla_cfg = get_config("tiny-mla" if SMOKE else "mla-1b")
        mla_params = init_params(jax.random.PRNGKey(30), mla_cfg, dtype=jnp.bfloat16)
        mb, mp, mn = (2, 120, 8) if SMOKE else (4, 4032, 64)
        mla_prompts = jax.random.randint(
            jax.random.PRNGKey(31), (mb, mp), 1, mla_cfg.vocab_size
        )

        def run_mla():
            result = generate(
                mla_params, mla_prompts,
                jnp.full((mb,), mp, dtype=jnp.int32), mla_cfg,
                jax.random.PRNGKey(32), max_new_tokens=mn, temperature=0.0,
            )
            float(jnp.sum(result.tokens))

        mla_s = time_fn(run_mla, iterations=2)
        record["mlactx_tok_s"] = round(mb * mn / mla_s, 1)
        mla_param_bytes = _tree_bytes(mla_params)
        record["mlactx_param_gb"] = round(mla_param_bytes / 1e9, 3)
        record["mlactx_cache_gb_per_4k_seq"] = round(
            mla_cfg.n_layers * (mla_cfg.mla_cache_dim + 1) * 2 * 4096 / 1e9, 4
        )
        # attribute the decode phase by timing the 4k prefill alone (same
        # scheme as longctx): at this prompt length the prefill dominates
        # the gen call, and a whole-call roofline would understate the
        # decode bandwidth severalfold. The latent cache streams twice per
        # step (K and V reads share the array) plus the 1-wide dummy.
        mla_slot = mla_cfg.n_layers * (2 * mla_cfg.mla_cache_dim + 1) * 2
        per_step = mla_param_bytes + mb * mla_slot * (mp + mn / 2)

        def emit_mla_roofline(seconds: float) -> None:
            gbs = per_step * mn / seconds / 1e9
            record["mlactx_hbm_gbs"] = round(gbs, 1)
            record["mlactx_hbm_pct_peak"] = round(100.0 * gbs / V5E_HBM_GBS, 1)

        try:
            from prime_tpu.models.llama import forward as _mla_fwd
            from prime_tpu.models.llama import init_cache as _mla_ic

            mla_cache = _mla_ic(mla_cfg, mb, mp + mn)
            mla_pre_fn = jax.jit(
                lambda p, c: _mla_fwd(
                    p, mla_prompts, mla_cfg, cache=c,
                    last_positions=jnp.full((mb,), mp - 1, dtype=jnp.int32),
                )[0]
            )
            mla_pre_s = time_fn(
                lambda: float(jnp.sum(mla_pre_fn(mla_params, mla_cache))),
                iterations=2,
            )
            record["mlactx_prefill_ms"] = round(mla_pre_s * 1e3, 1)
            decode_s = mla_s - mla_pre_s
            if decode_s > 0.2 * mla_s:
                record["mlactx_decode_tok_s"] = round(mb * mn / decode_s, 1)
                emit_mla_roofline(decode_s)
            else:
                # noisy subtraction: keep the whole-call lower bound so the
                # record never loses the mlactx_hbm_* keys
                emit_mla_roofline(mla_s)
        except Exception as e:  # noqa: BLE001
            record["mlactx_roofline_error"] = f"{type(e).__name__}: {e}"[:200]
            emit_mla_roofline(mla_s)  # whole-call lower bound
        print(
            f"# bench: mlactx C={mp + mn} {record['mlactx_tok_s']} tok/s "
            f"(latent cache, ~{record.get('mlactx_hbm_pct_peak', 0)}% HBM peak)",
            flush=True,
        )
        del mla_params
    except Exception as e:  # noqa: BLE001
        record["mlactx_error"] = f"{type(e).__name__}: {e}"[:200]
        print(f"# bench: mlactx section failed: {e}", flush=True)
    print(json.dumps(record), flush=True)  # checkpoint: last JSON line wins

    # ---- trainstep: full training-step throughput + MFU ---------------------
    # The other half of the framework: one adamw step (fwd + bwd + fp32
    # update) on a 0.5B dense model, remat="dots". MFU uses the standard
    # model-FLOP count (6*N per token + causal attention fwd*3), NOT the
    # rematerialized hardware FLOPs.
    try:
        from prime_tpu.train.trainer import (
            default_optimizer,
            init_train_state,
            make_train_step,
        )

        tr_model = "tiny-test" if SMOKE else "qwen2.5-0.5b"
        tr_cfg = get_config(tr_model)
        tr_b, tr_s = (2, 64) if SMOKE else (4, 1024)
        tr_params = init_params(jax.random.PRNGKey(40), tr_cfg, dtype=jnp.bfloat16)
        tr_opt = default_optimizer()
        holder = {"state": init_train_state(tr_params, tr_opt)}
        step_fn = make_train_step(tr_cfg, tr_opt, remat="dots")
        tr_tokens = jax.random.randint(
            jax.random.PRNGKey(41), (tr_b, tr_s + 1), 1, tr_cfg.vocab_size
        )
        tr_mask = jnp.ones((tr_b, tr_s), dtype=jnp.float32)

        def run_train_step():
            state, metrics = step_fn(
                holder["state"], tr_tokens[:, :-1], tr_tokens[:, 1:], tr_mask
            )
            holder["state"] = state
            float(metrics["loss"])  # host sync

        tr_step_s = time_fn(run_train_step, iterations=3)
        tr_param_count = _tree_bytes(tr_params) / 2  # bf16 storage
        tr_tokens_per_step = tr_b * tr_s
        tr_flops = (
            6.0 * tr_param_count * tr_tokens_per_step
            # causal fwd attention is 2*L*H*S^2*hd (same model as the
            # headline prefill MFU); fwd + 2x bwd = 3x that
            + 6.0 * tr_cfg.n_layers * tr_cfg.n_heads
            * tr_b * tr_s**2 * tr_cfg.head_dim
        )
        record["trainstep_tok_s"] = round(tr_tokens_per_step / tr_step_s, 1)
        record["trainstep_step_ms"] = round(tr_step_s * 1e3, 1)
        record["trainstep_mfu_pct"] = round(
            100.0 * tr_flops / tr_step_s / V5E_BF16_FLOPS, 1
        )
        record["trainstep_model"] = tr_model
        print(
            f"# bench: trainstep {record['trainstep_tok_s']} tok/s "
            f"({record['trainstep_mfu_pct']}% MFU, b{tr_b} s{tr_s})",
            flush=True,
        )
        del tr_params, holder
    except Exception as e:  # noqa: BLE001
        record["trainstep_error"] = f"{type(e).__name__}: {e}"[:200]
        print(f"# bench: trainstep section failed: {e}", flush=True)
    print(json.dumps(record), flush=True)  # checkpoint: last JSON line wins

    # ---- winctx: sliding-window flash decode at long context ----------------
    # The round-4 kernel variant: a sliding layer's decode step front-skips
    # cache blocks before the window, so it streams ~window slots instead of
    # the whole cache (Gemma2/3, Mistral, GPT-OSS layers). Microbench of the
    # decode step itself at C=4096 / window=1024: pallas-with-skip vs the
    # XLA path that reads everything and masks.
    try:
        from prime_tpu.ops.attention import decode_attention

        wb, wh, wkh, wd, wc, wwin = (
            (2, 4, 2, 64, 256, 128) if SMOKE else (8, 32, 8, 64, 4096, 1024)
        )
        wq = jax.random.normal(jax.random.PRNGKey(7), (wb, wh, 1, wd), dtype=jnp.bfloat16)
        wk = jax.random.normal(jax.random.PRNGKey(8), (wb, wkh, wd, wc), dtype=jnp.bfloat16)
        wv = jax.random.normal(jax.random.PRNGKey(9), (wb, wkh, wd, wc), dtype=jnp.bfloat16)
        wlens = jnp.full((wb,), wc, dtype=jnp.int32)

        # chained in-jit timing (time_op): a single dispatch per wall-clock
        # sample is mostly dispatch overhead at this microsecond scale — both
        # sides run a serialized chain of ops and the overhead cancels in the
        # long-minus-short difference
        def win_op(impl):
            return lambda qc, k, v, lens: decode_attention(
                qc, k, v, lens, wd**-0.5, impl=impl, window=wwin,
                sliding=jnp.asarray(True),
            )

        win_ops = (wk, wv, wlens)
        win_xla_s = time_op(win_op("xla"), wq, win_ops)
        win_pallas_s = time_op(win_op("pallas"), wq, win_ops)
        record["winctx_xla_us"] = round(win_xla_s * 1e6, 1)
        record["winctx_pallas_us"] = round(win_pallas_s * 1e6, 1)
        record["winctx_pallas_speedup"] = round(win_xla_s / win_pallas_s, 3)
        # single-op roofline: the band-skip kernel streams ~window KV slots
        # (2 bytes × K and V); the XLA path streams the whole cache
        win_kernel_bytes = wb * wkh * wd * wwin * 2 * 2
        win_gbs = win_kernel_bytes / win_pallas_s / 1e9
        record["winctx_hbm_gbs"] = round(win_gbs, 1)
        record["winctx_hbm_pct_peak"] = round(100.0 * win_gbs / V5E_HBM_GBS, 1)
        print(
            f"# bench: winctx C={wc} win={wwin} pallas {record['winctx_pallas_us']}us "
            f"vs xla {record['winctx_xla_us']}us",
            flush=True,
        )
    except Exception as e:  # noqa: BLE001
        record["winctx_error"] = f"{type(e).__name__}: {e}"[:200]
        print(f"# bench: winctx section failed: {e}", flush=True)
    print(json.dumps(record), flush=True)  # checkpoint: last JSON line wins

    # ---- spdecode: sequence-parallel decode step ----------------------------
    # The long-context decode path a v5e-8+ slice runs (cache slots sharded
    # over sp, two-phase softmax combine — parallel/long_context.py), timed
    # through the IDENTICAL shard_map code on the bench chip's sp=1 mesh.
    # What a single chip can measure is the sp machinery's overhead vs the
    # plain decode step (expect ~1.0x); cross-chip scaling needs a slice the
    # driver doesn't have. Parity at sp=8 is locked by
    # tests/test_parallel.py::test_sp_decode_parity_long_cache.
    try:
        from prime_tpu.ops.attention import decode_attention
        from prime_tpu.parallel.long_context import sp_decode_attention
        from prime_tpu.parallel.mesh import make_mesh

        sp_b, sp_h, sp_kh, sp_d, sp_c = (
            (2, 4, 2, 64, 256) if SMOKE else (8, 32, 8, 64, 4096)
        )
        sp_q = jax.random.normal(jax.random.PRNGKey(4), (sp_b, sp_h, 1, sp_d), dtype=jnp.bfloat16)
        sp_k = jax.random.normal(jax.random.PRNGKey(5), (sp_b, sp_kh, sp_d, sp_c), dtype=jnp.bfloat16)
        sp_v = jax.random.normal(jax.random.PRNGKey(6), (sp_b, sp_kh, sp_d, sp_c), dtype=jnp.bfloat16)
        sp_lens = jnp.full((sp_b,), sp_c, dtype=jnp.int32)
        mesh1 = make_mesh({"sp": 1})
        # chained in-jit timing (time_op) for the same reason as winctx: one
        # dispatch per sample measures the dispatch, not the op
        sp_operands = (sp_k, sp_v, sp_lens)
        plain_s = time_op(
            lambda qc, k, v, lens: decode_attention(
                qc, k, v, lens, sp_d**-0.5, impl="xla"
            ),
            sp_q, sp_operands,
        )
        sp_s = time_op(
            lambda qc, k, v, lens: sp_decode_attention(qc, k, v, lens, mesh1),
            sp_q, sp_operands,
        )
        record["spdecode_plain_us"] = round(plain_s * 1e6, 1)
        record["spdecode_sp_us"] = round(sp_s * 1e6, 1)
        record["spdecode_overhead"] = round(sp_s / plain_s, 3)
        # single-op roofline: one decode step streams the full K+V cache
        sp_kernel_bytes = sp_b * sp_kh * sp_d * sp_c * 2 * 2
        sp_gbs = sp_kernel_bytes / sp_s / 1e9
        record["spdecode_hbm_gbs"] = round(sp_gbs, 1)
        record["spdecode_hbm_pct_peak"] = round(100.0 * sp_gbs / V5E_HBM_GBS, 1)
        print(
            f"# bench: spdecode C={sp_c} sp-path {record['spdecode_sp_us']}us vs "
            f"plain {record['spdecode_plain_us']}us",
            flush=True,
        )
    except Exception as e:  # noqa: BLE001
        record["spdecode_error"] = f"{type(e).__name__}: {e}"[:200]
        print(f"# bench: spdecode section failed: {e}", flush=True)
    print(json.dumps(record), flush=True)  # checkpoint: last JSON line wins

    # final, enriched record — last JSON line on stdout wins
    print(json.dumps(record), flush=True)
    failed = sorted(key for key in record if key.endswith("_error"))
    if failed:
        sys.exit(f"bench.py: {len(failed)} section(s) failed: {', '.join(failed)}")


if __name__ == "__main__":
    main()
