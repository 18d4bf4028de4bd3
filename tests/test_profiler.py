"""Device-time observatory tests (CPU, tiny model).

The load-bearing property: with profiling off, the profiler adds ZERO
``jax.block_until_ready`` calls to the dispatch path — the engine's
one-chunk-deep overlap pipeline must be bit-identical to the pre-profiler
engine. Everything else (sampled step clock, compile accounting, capture window,
Chrome-trace export, trace-sink rotation, CLI/endpoint surfaces) is the
observatory built on top of that guarantee.
"""

import json
import sys
import threading
import time

import httpx
import jax
import jax.numpy as jnp
import pytest

from prime_tpu.models import get_config
from prime_tpu.models.llama import init_params
from prime_tpu.obs import DeviceProfiler, Registry, chrome_trace
from prime_tpu.obs import profiler as profiler_mod
from prime_tpu.obs.metrics import lint_prometheus_text
from prime_tpu.obs.trace import Tracer
from prime_tpu.serve.engine import ContinuousBatchingEngine

CONFIG = get_config("tiny-test")
PARAMS = init_params(jax.random.PRNGKey(0), CONFIG, dtype=jnp.float32)


@pytest.fixture(autouse=True)
def _default_profiler_env(monkeypatch):
    """Pin the env-driven defaults: ambient profiling/rotation knobs must not
    flip these tests onto another code path."""
    for knob in (
        "PRIME_SERVE_OVERLAP", "PRIME_SERVE_WARMUP", "PRIME_SERVE_MESH",
        "PRIME_SERVE_SPEC", "PRIME_SERVE_PROFILE", "PRIME_SERVE_PROFILE_SAMPLE",
        "PRIME_TRACE_MAX_MB", "PRIME_TRACE_KEEP", "PRIME_FLEET_ADMIN_TOKEN",
    ):
        monkeypatch.delenv(knob, raising=False)


def make_engine(**kw) -> ContinuousBatchingEngine:
    kw.setdefault("max_slots", 2)
    kw.setdefault("capacity", 64)
    kw.setdefault("chunk", 4)
    kw.setdefault("prefix_cache_mb", 0)
    return ContinuousBatchingEngine(PARAMS, CONFIG, **kw)


def drain(engine, *requests, max_ticks=200):
    for _ in range(max_ticks):
        engine.tick()
        if all(r.done for r in requests):
            return
    raise AssertionError("requests did not finish")


def _counting_block_until_ready(monkeypatch):
    """Wrap jax.block_until_ready, splitting calls by origin: the
    instrumentation's (frames inside obs/profiler.py or obs/phase.py) vs
    everyone else's."""
    counts = {"profiler": 0, "other": 0}
    real = jax.block_until_ready

    def spy(x):
        caller = sys._getframe(1).f_code.co_filename
        key = "profiler" if caller.endswith(("profiler.py", "phase.py")) else "other"
        counts[key] += 1
        return real(x)

    monkeypatch.setattr(jax, "block_until_ready", spy)
    return counts


# ---- the overhead guard ------------------------------------------------------


def test_profiling_off_adds_zero_syncs(monkeypatch):
    """Profiling off: every dispatch site gets the shared allocation-free
    no-op handle and neither the profiler nor the phase clock contributes a
    block_until_ready call to a full request lifecycle (prefill + decode +
    finish). With no capture and PRIME_TRACE unset the phase clock's scopes
    make no tracer Span either, while the clock itself still runs."""
    from prime_tpu.obs import trace as trace_mod

    engine = make_engine()
    assert engine.profiler.active is False
    assert engine.profiler.step("decode") is profiler_mod._NULL_STEP
    assert not trace_mod.TRACER.enabled

    spans_made = []

    class CountingSpan(trace_mod.Span):
        __slots__ = ()

        def __init__(self, *args, **kwargs):
            spans_made.append(args[0])
            super().__init__(*args, **kwargs)

    monkeypatch.setattr(trace_mod, "Span", CountingSpan)
    counts = _counting_block_until_ready(monkeypatch)
    req = engine.submit([5, 9, 301, 42], max_new_tokens=8)
    drain(engine, req)
    assert req.done
    assert counts["profiler"] == 0
    assert spans_made == []
    # always on all the same: the lifecycle's seconds were charged to phases
    loop_s = engine.registry.get("serve_loop_seconds_total")
    assert loop_s.value(phase="dispatch") > 0
    assert loop_s.value(phase="sync_wait") > 0
    assert loop_s.value(phase="prefill_sync") > 0
    # sanity that the spy itself works: a sampled step from an armed
    # profiler is attributed to profiler.py
    prof = DeviceProfiler(Registry(), enabled=True, sample_every=1)
    with prof.step("decode", pre=jnp.zeros(())) as handle:
        handle.fence(jnp.zeros(()))
    assert counts["profiler"] > 0


def test_profiling_on_fences_sampled_dispatches(monkeypatch):
    """PRIME_SERVE_PROFILE_SAMPLE=1 + profile=True: every dispatch is fenced
    by the profiler and the step clock fills per-phase."""
    monkeypatch.setenv("PRIME_SERVE_PROFILE_SAMPLE", "1")
    engine = make_engine(profile=True)
    assert engine.profile_enabled and engine.profiler.active
    counts = _counting_block_until_ready(monkeypatch)
    req = engine.submit([5, 9, 301, 42], max_new_tokens=8)
    drain(engine, req)
    assert counts["profiler"] > 0

    summary = engine.profiler.summary()
    assert summary["sample_every"] == 1
    phases = summary["phases"]
    assert phases["decode"]["samples"] > 0
    assert phases["decode"]["total_s"] > 0
    assert phases["prefill"]["samples"] >= 1
    # CPU backend: no roofline, so no MFU claims
    assert summary["peak_tflops"] is None
    assert "mfu" not in phases["decode"]
    # the phase clock's jax.monitoring listener attributed this engine's jit
    # cache misses to the loop phases that triggered them
    assert summary["compiles"]["total"] > 0
    assert summary["compiles"]["seconds"] > 0
    compiles = engine.registry.get("serve_compiles_total")
    assert compiles.value(phase="dispatch") >= 1  # the decode program
    assert compiles.value(phase="prefill") >= 2  # chunk prefill + finalize
    assert summary["compiles"]["total"] == sum(
        v[0] for v in compiles._series.values()
    )

    # the metric families made it into clean Prometheus exposition
    text = engine.registry.render_prometheus()
    assert 'serve_device_step_seconds_count{phase="decode"' in text
    assert "serve_compiles_total" in text
    assert lint_prometheus_text(text) == []


def test_sampling_rate_limits_fences():
    """N-of-M: with sample_every=4 only every 4th dispatch of a phase is
    fenced; the rest get the shared no-op (no fence, no record): the phase
    that a surprise recompile is billed to is the phase clock's now."""
    prof = DeviceProfiler(Registry(), enabled=True, sample_every=4)
    kinds = []
    for _ in range(8):
        handle = prof.step("decode")
        kinds.append(type(handle).__name__)
        with handle:
            handle.fence(jnp.zeros(()))
    assert kinds.count("_SampledStep") == 2
    assert kinds.count("_NullStep") == 6
    assert prof.summary()["phases"]["decode"]["samples"] == 2
    # a profiler built without an engine has no clock: compile totals read 0
    assert prof.summary()["compiles"] == {"total": 0, "seconds": 0.0, "loads": 0}


# ---- capture window + Chrome trace ------------------------------------------


def test_capture_window_fences_everything_and_exports_trace():
    """A capture window arms even a disabled profiler: every dispatch in the
    window is fenced and the stop payload carries a Perfetto-loadable
    Chrome trace merging device samples, compiles, and host spans."""
    engine = make_engine()
    assert engine.profiler.enabled is False
    assert engine.profiler.start_capture()
    assert not engine.profiler.start_capture()  # already open
    req = engine.submit([3, 1, 4, 1, 5], max_new_tokens=6)
    drain(engine, req)
    capture = engine.profiler.stop_capture()
    assert engine.profiler.stop_capture() is None  # window closed

    assert capture["samples"] > 0
    assert capture["duration_s"] > 0
    assert capture["summary"]["phases"]["decode"]["samples"] > 0
    _validate_chrome_trace(capture["trace"])
    # the engine's own serve.* spans from the window rode along on pid 1
    names = {e["name"] for e in capture["trace"]["traceEvents"]}
    assert any(n.startswith("device.") for n in names)
    # once the window closes, dispatches return to the free no-op path
    assert engine.profiler.step("decode") is profiler_mod._NULL_STEP


def _validate_chrome_trace(trace: dict) -> None:
    """Chrome-trace schema: X/M events only, int pid/tid, non-negative
    ts/dur microseconds, and per-(pid, tid) monotonic timestamps."""
    events = trace["traceEvents"]
    assert isinstance(events, list) and events
    last_ts: dict[tuple, float] = {}
    for event in events:
        assert event["ph"] in ("X", "M"), event
        assert isinstance(event["pid"], int)
        assert isinstance(event["tid"], int)
        assert isinstance(event["name"], str) and event["name"]
        if event["ph"] == "M":
            continue
        assert event["ts"] >= 0
        assert event["dur"] >= 0
        key = (event["pid"], event["tid"])
        assert event["ts"] >= last_ts.get(key, 0.0), "track not monotonic"
        last_ts[key] = event["ts"]
    assert trace["displayTimeUnit"] == "ms"


def test_chrome_trace_merges_three_sources():
    device = [
        {"phase": "decode", "start_s": 10.0, "duration_s": 0.002, "batch": 2, "steps": 1},
        {"phase": "decode", "start_s": 10.01, "duration_s": 0.001, "batch": 2, "steps": 1},
        {"phase": "prefill", "start_s": 10.005, "duration_s": 0.004, "batch": 1, "steps": 1},
    ]
    compiles = [{"phase": "decode", "start_s": 9.5, "duration_s": 0.4}]
    host = [
        {"name": "serve.request", "start_s": 9.9, "duration_s": 0.15,
         "attrs": {"tokens": 6}},
    ]
    trace = chrome_trace(device, compiles, host, base_s=9.0, base_unix_s=1234.5)
    _validate_chrome_trace(trace)
    events = [e for e in trace["traceEvents"] if e["ph"] == "X"]
    assert {e["pid"] for e in events} == {1, 2}
    by_name = {}
    for e in events:
        by_name.setdefault(e["name"], []).append(e)
    assert len(by_name["device.decode"]) == 2
    # both decode samples share one track; prefill and the compile get their own
    assert len({e["tid"] for e in by_name["device.decode"]}) == 1
    assert by_name["device.prefill"][0]["tid"] != by_name["device.decode"][0]["tid"]
    assert by_name["xla.compile"][0]["tid"] not in {
        by_name["device.prefill"][0]["tid"], by_name["device.decode"][0]["tid"],
    }
    # µs from base_s: serve.request starts 0.9s after the base
    assert by_name["serve.request"][0]["ts"] == pytest.approx(0.9e6)
    assert by_name["serve.request"][0]["dur"] == pytest.approx(0.15e6)
    assert trace["metadata"]["capture_start_unix_s"] == 1234.5
    # track-naming metadata exists for every device phase
    meta_names = {
        e["args"]["name"] for e in trace["traceEvents"] if e["ph"] == "M"
    }
    assert {"decode", "prefill", "xla compile"} <= meta_names


# ---- warmup breakdown --------------------------------------------------------


def test_warmup_program_family_breakdown():
    """warmup() splits its wall time into serve_warmup_program_seconds
    {program=...} — one observation per family block — alongside the
    existing end-to-end gauges."""
    engine = make_engine(warmup=True)
    programs = engine.warmup()
    assert programs > 0
    hist = engine._m_warmup_program_s
    decode = hist.series_snapshot(program="decode")
    chunk = hist.series_snapshot(program="chunk_prefill")
    finalize = hist.series_snapshot(program="finalize")
    assert decode["count"] >= 1
    assert chunk["count"] >= 1 and finalize["count"] >= 1
    # the family splits sum to (roughly, <= because gaps exist) the gauge;
    # families this config never runs (spec off, prefix cache off) have no
    # series at all
    snaps = [
        hist.series_snapshot(program=p)
        for p in ("decode", "spec", "hist_seed", "chunk_prefill", "finalize", "assemble")
    ]
    total = sum(s["sum"] for s in snaps if s is not None)
    assert 0 < total <= engine._m_warmup_s.value() * 1.05 + 0.05


# ---- trace-sink rotation -----------------------------------------------------


def test_trace_sink_rotation_caps_live_file(tmp_path):
    sink = tmp_path / "trace.jsonl"
    cap_bytes = 4096
    tracer = Tracer(sink_path=sink, max_mb=cap_bytes / (1024 * 1024), keep=2)
    for i in range(200):
        with tracer.span("serve.request", idx=i, pad="x" * 64):
            pass
    tracer.close()
    assert sink.exists()
    rotated = tmp_path / "trace.jsonl.1"
    assert rotated.exists(), "sink never rotated under a 4KiB cap"
    assert not (tmp_path / "trace.jsonl.3").exists()  # keep=2
    # the live file respects the cap (one line of slack for the overflow write)
    assert sink.stat().st_size <= cap_bytes + 512
    # every surviving file is intact JSONL
    for path in (sink, rotated):
        for line in path.read_text().splitlines():
            assert json.loads(line)["name"] == "serve.request"


def test_trace_sink_unlimited_by_default(tmp_path):
    sink = tmp_path / "trace.jsonl"
    tracer = Tracer(sink_path=sink)  # max_mb -> env default 0 = unlimited
    for _ in range(50):
        with tracer.span("s"):
            pass
    tracer.close()
    assert not (tmp_path / "trace.jsonl.1").exists()
    assert len(sink.read_text().splitlines()) == 50


def test_tracer_tail_is_non_destructive():
    tracer = Tracer()
    with tracer.span("a"):
        pass
    assert [s["name"] for s in tracer.tail()] == ["a"]
    assert [s["name"] for s in tracer.tail()] == ["a"]  # still there
    assert [s["name"] for s in tracer.drain()] == ["a"]  # drain still clears
    assert tracer.tail() == []


# ---- /admin/profile endpoint -------------------------------------------------


def _chat(url: str, text: str = "ab", tokens: int = 6) -> None:
    response = httpx.post(
        f"{url}/v1/chat/completions",
        json={"messages": [{"role": "user", "content": text}],
              "max_tokens": tokens},
        timeout=120,
    )
    assert response.status_code == 200


def _serving_engine():
    from prime_tpu.evals.tokenizer import ByteTokenizer
    from prime_tpu.serve.engine import EngineBackend

    engine = make_engine()
    return engine, EngineBackend(engine, ByteTokenizer())


def test_admin_profile_endpoint_capture_roundtrip(monkeypatch):
    from prime_tpu.obs.trace import TRACER
    from prime_tpu.serve import InferenceServer

    # ring-only tracing (no sink): the capture merges host spans from here
    monkeypatch.setattr(TRACER, "enabled", True)
    engine, backend = _serving_engine()
    with engine:
        with InferenceServer("tiny-test", backend, port=0) as srv:
            status = httpx.get(f"{srv.url}/admin/profile").json()
            assert status["enabled"] is False and status["capturing"] is False
            assert status["sample_every"] >= 1 and "summary" in status

            # stop without start -> 409
            response = httpx.post(
                f"{srv.url}/admin/profile", json={"action": "stop"}
            )
            assert response.status_code == 409
            # bad action -> 400
            response = httpx.post(
                f"{srv.url}/admin/profile", json={"action": "dance"}
            )
            assert response.status_code == 400

            started = httpx.post(
                f"{srv.url}/admin/profile", json={"action": "start"}
            ).json()
            assert started == {"capturing": True, "started": True}
            assert httpx.get(f"{srv.url}/admin/profile").json()["capturing"]

            _chat(srv.url)
            capture = httpx.post(
                f"{srv.url}/admin/profile", json={"action": "stop"}
            ).json()
            assert capture["samples"] > 0
            assert capture["summary"]["phases"]["decode"]["samples"] > 0
            _validate_chrome_trace(capture["trace"])
            # the HTTP hop's own host span landed in the merged timeline
            assert capture["host_spans"] > 0

            # new metric families expose cleanly after real traffic
            text = httpx.get(
                f"{srv.url}/metrics", params={"format": "prometheus"}
            ).text
            assert "serve_device_step_seconds" in text
            assert lint_prometheus_text(text) == []


def test_admin_profile_honors_admin_token():
    from prime_tpu.serve import InferenceServer

    engine, backend = _serving_engine()
    with engine:
        with InferenceServer(
            "tiny-test", backend, port=0, admin_token="sekrit"
        ) as srv:
            assert httpx.get(f"{srv.url}/admin/profile").status_code == 403
            assert (
                httpx.post(
                    f"{srv.url}/admin/profile", json={"action": "start"}
                ).status_code
                == 403
            )
            auth = {"Authorization": "Bearer sekrit"}
            assert (
                httpx.get(f"{srv.url}/admin/profile", headers=auth).status_code
                == 200
            )


def test_admin_profile_404_without_engine_profiler():
    """A non-engine generator has no profiler: the endpoint 404s instead of
    pretending a capture could work."""
    from prime_tpu.serve import InferenceServer

    class EchoGenerator:
        def generate(self, prompts, max_new_tokens, temperature, top_p=1.0):
            return ["ok"] * len(prompts)

    with InferenceServer("tiny-test", EchoGenerator(), port=0) as srv:
        assert httpx.get(f"{srv.url}/admin/profile").status_code == 404
        assert (
            httpx.post(
                f"{srv.url}/admin/profile", json={"action": "start"}
            ).status_code
            == 404
        )


# ---- prime serve profile (CLI) ----------------------------------------------


def test_serve_profile_cli_renders_breakdown_and_writes_trace(tmp_path):
    from click.testing import CliRunner

    from prime_tpu.commands.main import cli
    from prime_tpu.serve import InferenceServer

    engine, backend = _serving_engine()
    with engine:
        with InferenceServer("tiny-test", backend, port=0) as srv:
            # compile every program BEFORE the window: a cold tiny-test chat
            # spends ~1s in XLA compiles, which would swallow the whole
            # capture (the one in-flight sampled step then exits after stop)
            _chat(srv.url, tokens=4)
            _chat(srv.url, tokens=4)
            stop = threading.Event()

            def traffic():
                while not stop.is_set():
                    _chat(srv.url, tokens=4)
                    time.sleep(0.02)

            thread = threading.Thread(target=traffic, daemon=True)
            thread.start()
            try:
                trace_out = tmp_path / "trace.json"
                result = CliRunner().invoke(
                    cli,
                    [
                        "serve", "profile", "--url", srv.url,
                        "--seconds", "0.8", "--trace-out", str(trace_out),
                    ],
                )
            finally:
                stop.set()
                thread.join(timeout=30)
    assert result.exit_code == 0, result.output
    assert "Device time @" in result.output
    assert "decode" in result.output
    assert "no roofline for this backend" in result.output  # CPU: no MFU claim
    assert "Perfetto" in result.output
    trace = json.loads(trace_out.read_text())
    _validate_chrome_trace(trace)
    assert any(
        e["name"].startswith("device.") for e in trace["traceEvents"]
    )


def test_serve_profile_cli_unreachable_target_fails_cleanly():
    from click.testing import CliRunner

    from prime_tpu.commands.main import cli

    result = CliRunner().invoke(
        cli,
        ["serve", "profile", "--url", "http://127.0.0.1:9", "--seconds", "0.1"],
    )
    assert result.exit_code != 0
    assert "could not reach" in result.output


# ---- perf_delta integration --------------------------------------------------


def test_perf_delta_flattens_device_profile():
    from prime_tpu.loadgen.perf_delta import _device_profile_metrics

    profile = {
        "phases": {
            "decode": {"samples": 12, "total_s": 0.24, "mean_s": 0.02,
                       "mfu": 0.31, "achieved_tflops": 142.0,
                       "achieved_gbps": 88.5},
            "prefill": {"samples": 3, "total_s": 0.09, "mean_s": 0.03},
        },
        "compiles": {"total": 7, "seconds": 12.5},
    }
    metrics = _device_profile_metrics(profile)
    assert metrics["dp:decode step ms"] == 20.0
    assert metrics["dp:decode mfu"] == 0.31
    assert metrics["dp:decode tflops"] == 142.0
    assert metrics["dp:decode gb/s"] == 88.5
    assert metrics["dp:prefill step ms"] == 30.0
    assert metrics["dp:compiles"] == 7.0
    assert metrics["dp:compile s"] == 12.5
    # malformed sections flatten to nothing, never raise
    assert _device_profile_metrics({}) == {}
    assert _device_profile_metrics({"phases": {"x": "oops"}, "compiles": 3}) == {}


def test_perf_delta_tolerates_absent_device_profile():
    """A profiler-era round next to a pre-profiler baseline: the dp: rows
    render an em-dash for the baseline column, not an error."""
    from prime_tpu.loadgen.perf_delta import _round_from_record, delta_table

    old = _round_from_record(
        "BENCH_r01.json",
        {"schema": 2, "value": 10.0, "metric": "decode_tokens_per_sec"},
    )
    new = _round_from_record(
        "BENCH_r02.json",
        {
            "schema": 2, "value": 11.0, "metric": "decode_tokens_per_sec",
            "device_profile": {
                "phases": {"decode": {"samples": 5, "total_s": 0.1,
                                      "mean_s": 0.02}},
                "compiles": {"total": 3, "seconds": 4.0},
            },
        },
    )
    table = delta_table([old, new])
    dp_row = next(
        line for line in table.splitlines()
        if line.startswith("dp:decode step ms")
    )
    assert "—" in dp_row  # r01 never measured it
    assert "20" in dp_row  # r02 did
