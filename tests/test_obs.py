"""Observability layer: registry semantics, Prometheus exposition, span
tracing, and the serve wiring (JSON /metrics backward compatibility +
histograms populating through a real streamed completion).

The fast server-scrape tests double as the tier-1 smoke for exposition
regressions: they import prime_tpu.obs, stand up a live in-process
InferenceServer, and parse the actual Prometheus text a scraper would see.
"""

import json
import math

import httpx
import pytest

from prime_tpu.obs import (
    FlightRecorder,
    Registry,
    TraceContext,
    Tracer,
    lint_prometheus_text,
    new_traceparent,
    parse_traceparent,
    quantile_from_snapshot,
)

# ---- histogram semantics ----------------------------------------------------


def test_histogram_bucket_boundaries():
    """``le`` semantics: a value ON a bound lands in that bucket; past the
    last bound only +Inf counts it."""
    r = Registry()
    h = r.histogram("h_seconds", "x", buckets=(0.1, 1.0, 10.0))
    for v in (0.05, 0.1, 0.100001, 1.0, 9.9, 10.0, 11.0):
        h.observe(v)
    snap = h.series_snapshot()
    assert snap["counts"] == [2, 2, 2, 1]  # per-bucket (non-cumulative) + Inf
    assert snap["count"] == 7
    assert snap["sum"] == pytest.approx(sum((0.05, 0.1, 0.100001, 1.0, 9.9, 10.0, 11.0)))


def test_histogram_quantiles():
    r = Registry()
    h = r.histogram("h", "x", buckets=(1.0, 2.0, 4.0))
    for _ in range(50):
        h.observe(0.5)
    for _ in range(50):
        h.observe(3.0)
    # 50 obs in (0,1], 50 in (2,4]: the median sits exactly at bucket 1's
    # upper bound, p99 interpolates inside the (2,4] bucket
    assert h.quantile(0.5) == pytest.approx(1.0)
    assert 2.0 <= h.quantile(0.99) <= 4.0
    assert math.isnan(r.histogram("empty", "x", buckets=(1.0,)).quantile(0.5))
    with pytest.raises(ValueError):
        h.quantile(1.5)
    # the snapshot-based estimator is the same math
    snap = h.series_snapshot()
    assert quantile_from_snapshot(snap["buckets"], snap["counts"], 0.5) == pytest.approx(
        h.quantile(0.5)
    )


def test_histogram_bucket_validation():
    r = Registry()
    with pytest.raises(ValueError):
        r.histogram("bad", "x", buckets=())
    with pytest.raises(ValueError):
        r.histogram("bad2", "x", buckets=(2.0, 1.0))


# ---- registry semantics -----------------------------------------------------


def test_counter_and_gauge():
    r = Registry()
    c = r.counter("c_total", "x")
    c.inc()
    c.inc(2)
    assert c.value() == 3
    with pytest.raises(ValueError):
        c.inc(-1)
    g = r.gauge("g", "x")
    g.set(5)
    g.dec(2)
    assert g.value() == 3


def test_registry_get_or_create_and_conflicts():
    r = Registry()
    assert r.counter("c_total") is r.counter("c_total")
    with pytest.raises(ValueError):
        r.gauge("c_total")  # same name, different kind
    with pytest.raises(ValueError):
        r.counter("c_total", labelnames=("x",))  # same kind, different labels
    with pytest.raises(ValueError):
        r.counter("bad name")
    with pytest.raises(ValueError):
        r.counter("ok_total", labelnames=("bad-label",))


def test_labeled_series_and_values():
    r = Registry()
    c = r.counter("req_total", "x", labelnames=("method",))
    c.inc(method="GET")
    c.inc(3, method="POST")
    assert c.value(method="POST") == 3
    assert c.value(method="DELETE") == 0  # never observed
    with pytest.raises(ValueError):
        c.inc(verb="GET")  # wrong label name
    # values() is the unlabeled-only consistent read (engine stats source)
    plain = r.counter("plain_total")
    plain.inc(7)
    assert r.values() == {"plain_total": 7.0}


# ---- Prometheus exposition --------------------------------------------------


def test_prometheus_rendering_and_escaping():
    r = Registry()
    c = r.counter("reqs_total", 'help with \\ and\nnewline', labelnames=("path",))
    c.inc(2, path='a"b\\c\nd')
    text = r.render_prometheus()
    assert '# HELP reqs_total help with \\\\ and\\nnewline' in text
    assert "# TYPE reqs_total counter" in text
    assert 'reqs_total{path="a\\"b\\\\c\\nd"} 2' in text


def test_prometheus_histogram_rendering():
    r = Registry()
    h = r.histogram("lat_seconds", "latency", buckets=(0.5, 1.0))
    h.observe(0.2)
    h.observe(0.7)
    h.observe(5.0)
    lines = r.render_prometheus().splitlines()
    assert 'lat_seconds_bucket{le="0.5"} 1' in lines
    assert 'lat_seconds_bucket{le="1"} 2' in lines  # cumulative
    assert 'lat_seconds_bucket{le="+Inf"} 3' in lines
    assert "lat_seconds_sum 5.9" in lines
    assert "lat_seconds_count 3" in lines


def test_prometheus_unobserved_and_nonfinite_are_well_formed():
    """Satellite: a registered-but-never-observed label-less histogram must
    emit zero-count bucket series (not a bare HELP/TYPE header), and NaN/Inf
    gauges must use the text-format spellings — checked by the lint."""
    r = Registry()
    r.histogram("cold_seconds", "never observed", buckets=(0.5, 1.0))
    r.counter("cold_total", "never incremented")
    g = r.gauge("weird")
    g.set(float("nan"))
    g2 = r.gauge("hot")
    g2.set(float("inf"))
    text = r.render_prometheus()
    assert 'cold_seconds_bucket{le="+Inf"} 0' in text
    assert "cold_seconds_count 0" in text and "cold_seconds_sum 0" in text
    assert "cold_total 0" in text
    assert "weird NaN" in text and "nan" not in text
    assert "hot +Inf" in text
    assert lint_prometheus_text(text) == []


def test_exposition_lint_catches_violations():
    ok = (
        "# TYPE h_seconds histogram\n"
        'h_seconds_bucket{le="1"} 1\n'
        'h_seconds_bucket{le="+Inf"} 2\n'
        "h_seconds_sum 1.5\n"
        "h_seconds_count 2\n"
    )
    assert lint_prometheus_text(ok) == []
    # non-cumulative buckets
    assert lint_prometheus_text(
        '# TYPE h histogram\nh_bucket{le="1"} 5\nh_bucket{le="+Inf"} 2\nh_count 2\n'
    )
    # missing +Inf bucket
    assert lint_prometheus_text('# TYPE h histogram\nh_bucket{le="1"} 1\nh_count 1\n')
    # _count disagrees with the +Inf bucket
    assert lint_prometheus_text(
        '# TYPE h histogram\nh_bucket{le="+Inf"} 2\nh_count 3\n'
    )
    # duplicate series, bad value spelling, unparseable line
    assert lint_prometheus_text("# TYPE c counter\nc 1\nc 2\n")
    assert lint_prometheus_text("# TYPE g gauge\ng nan\n")
    assert lint_prometheus_text("just not exposition\n")
    # legal label values containing '}', ',' and escapes must NOT trip it
    assert lint_prometheus_text(
        '# TYPE c counter\nc{a="x,y",b="cl}osed",d="q\\"uo"} 1\n'
    ) == []
    assert lint_prometheus_text('# TYPE c counter\nc{a="trailing",} 1\n') == []
    assert lint_prometheus_text('# TYPE c counter\nc{a=unquoted} 1\n')


def test_snapshot_roundtrips_through_json():
    r = Registry()
    r.counter("c_total").inc()
    r.histogram("h_seconds", buckets=(1.0,)).observe(2.0)
    snap = json.loads(json.dumps(r.snapshot()))
    assert snap["c_total"]["type"] == "counter"
    assert snap["h_seconds"]["series"][0]["counts"] == [0, 1]


# ---- tracing ----------------------------------------------------------------


def test_span_nesting_and_jsonl_roundtrip(tmp_path):
    tracer = Tracer()
    with tracer.span("outer", kind="request") as outer:
        with tracer.span("inner") as inner:
            inner.set_attr("tokens", 3)
        assert inner.parent_id == outer.span_id
        assert inner.trace_id == outer.trace_id
    path = tmp_path / "trace.jsonl"
    assert tracer.export_jsonl(path) == 2
    rows = [json.loads(line) for line in path.read_text().splitlines()]
    by_name = {row["name"]: row for row in rows}
    assert by_name["inner"]["parent_id"] == by_name["outer"]["span_id"]
    assert by_name["outer"]["parent_id"] is None
    assert by_name["inner"]["attrs"] == {"tokens": 3}
    assert all(row["duration_s"] >= 0 for row in rows)
    # inner is fully contained in outer on the monotonic clock
    assert by_name["inner"]["start_s"] >= by_name["outer"]["start_s"]
    assert tracer.drain() == []  # export drained the buffer


def test_span_records_exceptions_and_sink(tmp_path):
    sink = tmp_path / "sink.jsonl"
    tracer = Tracer(sink_path=sink)
    with pytest.raises(RuntimeError):
        with tracer.span("boom"):
            raise RuntimeError("kaput")
    row = json.loads(sink.read_text().splitlines()[0])
    assert "kaput" in row["attrs"]["error"]
    tracer.close()


def test_disabled_tracer_is_noop():
    tracer = Tracer(enabled=False)
    with tracer.span("x", a=1) as s:
        s.set_attr("b", 2)  # must not raise
    assert s.traceparent() is None  # callers skip header injection
    assert tracer.drain() == []


# ---- trace context propagation ----------------------------------------------


def test_traceparent_roundtrip_valid():
    header = new_traceparent()
    ctx = parse_traceparent(header)
    assert ctx is not None
    assert ctx.to_header() == header
    assert len(ctx.trace_id) == 32 and len(ctx.span_id) == 16
    # whitespace/case tolerated (header values travel through proxies)
    assert parse_traceparent(f"  {header.upper()}  ") == ctx
    # future versions may carry extra fields — parse the known prefix
    assert parse_traceparent("cf-" + "a" * 32 + "-" + "b" * 16 + "-01-extra") is not None


@pytest.mark.parametrize(
    "header",
    [
        None,
        "",
        "garbage",
        "00-" + "a" * 31 + "-" + "b" * 16 + "-01",  # short trace id
        "00-" + "a" * 32 + "-" + "b" * 15 + "-01",  # short span id
        "ff-" + "a" * 32 + "-" + "b" * 16 + "-01",  # invalid version
        "00-" + "0" * 32 + "-" + "b" * 16 + "-01",  # all-zero trace id
        "00-" + "a" * 32 + "-" + "0" * 16 + "-01",  # all-zero span id
        "00-" + "a" * 32 + "-" + "b" * 16 + "-01-extra",  # v00 has no extras
        "00-" + "A" * 32 + "-" + "b" * 16,  # missing flags
    ],
)
def test_traceparent_malformed_or_absent(header):
    assert parse_traceparent(header) is None


def test_span_joins_inbound_context():
    tracer = Tracer()
    ctx = parse_traceparent(new_traceparent())
    with tracer.span("server.hop", context=ctx) as root:
        with tracer.span("inner") as inner:
            pass
    assert root.trace_id == ctx.trace_id
    assert root.parent_id == ctx.span_id
    assert inner.trace_id == ctx.trace_id and inner.parent_id == root.span_id
    # the span's own traceparent parses back to (trace_id, span_id)
    fwd = parse_traceparent(root.traceparent())
    assert fwd.trace_id == ctx.trace_id and fwd.span_id == root.span_id
    # explicit context beats the thread-local stack
    other = TraceContext.generate()
    with tracer.span("outer"):
        with tracer.span("rebased", context=other) as rebased:
            pass
    assert rebased.trace_id == other.trace_id


def test_tracer_emit_synthetic_span():
    tracer = Tracer()
    ctx = TraceContext.generate()
    tracer.emit("serve.queue_wait", 0.25, context=ctx, request=7)
    tracer.emit("rootless", 0.1)
    spans = tracer.drain()
    wait = next(s for s in spans if s["name"] == "serve.queue_wait")
    assert wait["trace_id"] == ctx.trace_id and wait["parent_id"] == ctx.span_id
    assert wait["duration_s"] == pytest.approx(0.25)
    assert wait["attrs"] == {"request": 7}
    # disabled tracer: emit is free
    off = Tracer(enabled=False)
    off.emit("x", 1.0)
    assert off.drain() == []


def test_tracer_reconfigure_roundtrip(tmp_path):
    tracer = Tracer(enabled=False)
    sink = tmp_path / "sink.jsonl"
    prev = tracer.reconfigure(enabled=True, sink_path=str(sink))
    with tracer.span("x"):
        pass
    tracer.reconfigure(**prev)
    assert not tracer.enabled
    assert len(sink.read_text().splitlines()) == 1


# ---- serve wiring -----------------------------------------------------------


class EchoGenerator:
    def generate(self, prompts, max_new_tokens, temperature, top_p=1.0):
        return [p.splitlines()[-2].split(":", 1)[1].strip().upper() for p in prompts]


@pytest.fixture
def server():
    from prime_tpu.serve import InferenceServer

    with InferenceServer("tiny-test", EchoGenerator(), port=0) as srv:
        yield srv


def test_metrics_json_shape_unchanged(server):
    """The default JSON /metrics response keeps the pre-obs shape for
    existing keys (wire compatibility for whatever already scrapes it)."""
    data = httpx.get(f"{server.url}/metrics").json()
    assert data["model"] == "tiny-test"
    assert data["loaded"] is True
    assert "engine" not in data  # EchoGenerator has no stats()


def test_healthz(server):
    data = httpx.get(f"{server.url}/healthz").json()
    assert data["status"] == "ok"
    assert data["loaded"] is True
    assert data["uptime_s"] >= 0

    from prime_tpu.serve import InferenceServer

    with InferenceServer("tiny-test", port=0) as unloaded:
        data = httpx.get(f"{unloaded.url}/healthz").json()
        assert data["status"] == "ok" and data["loaded"] is False


def test_prometheus_scrape_live_server(server):
    """Fast exposition smoke: a live in-process server must serve parseable
    Prometheus text with the http metrics populated."""
    httpx.post(
        f"{server.url}/v1/chat/completions",
        json={"messages": [{"role": "user", "content": "hi"}]},
        timeout=30,
    )
    response = httpx.get(f"{server.url}/metrics", params={"format": "prometheus"})
    assert response.status_code == 200
    assert response.headers["content-type"].startswith("text/plain")
    text = response.text
    assert "# TYPE http_requests_total counter" in text
    assert 'http_requests_total{route="/v1/chat/completions",method="POST",status="200"} 1' in text
    assert "# TYPE http_request_seconds histogram" in text
    # every non-comment line is `name{labels} value`
    for line in text.splitlines():
        if line.startswith("#") or not line:
            continue
        name_part, _, value = line.rpartition(" ")
        assert name_part and float(value) >= 0


def test_registry_json_view(server):
    payload = httpx.get(f"{server.url}/metrics", params={"format": "registry"}).json()
    assert "server" in payload
    assert payload["server"]["http_requests_total"]["type"] == "counter"


def test_engine_histograms_populate_through_streamed_completion():
    """Acceptance: one streamed chat completion through InferenceServer over
    the continuous-batching engine leaves serve_ttft_seconds and
    serve_queue_wait_seconds with non-zero counts in the Prometheus text,
    while the JSON /metrics engine keys stay the legacy shape."""
    import jax
    import jax.numpy as jnp

    from prime_tpu.evals.tokenizer import ByteTokenizer
    from prime_tpu.models import get_config
    from prime_tpu.models.llama import init_params
    from prime_tpu.serve import InferenceServer
    from prime_tpu.serve.engine import ContinuousBatchingEngine, EngineBackend

    config = get_config("tiny-test")
    params = init_params(jax.random.PRNGKey(0), config, dtype=jnp.float32)
    engine = ContinuousBatchingEngine(
        params, config, max_slots=2, capacity=128, chunk=4, prefix_cache_mb=0
    )
    with engine:
        backend = EngineBackend(engine, ByteTokenizer())
        with InferenceServer("tiny-test", backend, port=0) as srv:
            with httpx.stream(
                "POST",
                f"{srv.url}/v1/chat/completions",
                json={
                    "messages": [{"role": "user", "content": "ab"}],
                    "max_tokens": 6,
                    "stream": True,
                },
                timeout=120,
            ) as response:
                assert response.status_code == 200
                body = "".join(response.iter_lines())
                assert "[DONE]" in body

            # legacy JSON: the pre-registry counter keys, plus the decode
            # pipeline fields (PR 2), the radix prefix-cache fields (PR 3),
            # the fleet admission/drain fields (PR 4), the host spill
            # tier fields (PR 6), the sharded-replica mesh fields, the
            # speculative-decoding fields, the disaggregated-serving KV
            # export/import counters, the paged-seeding counter, and the
            # phase clock's list of the last program builds — additive only
            engine_stats = httpx.get(f"{srv.url}/metrics").json()["engine"]
            assert set(engine_stats) == {
                "requests_admitted", "requests_completed", "requests_cancelled",
                "requests_failed", "tokens_emitted", "prefix_hits",
                "batched_admission_waves", "active_slots", "queue_depth",
                "max_slots", "max_queue", "mesh_devices", "mesh_axes",
                "adapters_loaded", "adapters", "adapter_weights", "state",
                "overlap", "speculative", "draft_len", "spec_accept_ratio",
                "inflight_depth", "loop_s", "loop_starved_s",
                "loop_starved_unseen_s", "wasted_decode_tokens", "warmup_programs",
                "prefix_cache_bytes", "prefix_cache_host_bytes",
                "prefix_host_tier_disabled",
                "prefix_cache_nodes", "prefix_evictions", "prefix_spills",
                "prefix_reuploads", "prefix_assembles", "prefix_paged_seeds",
                "kv_exports", "kv_imports", "program_builds", "uptime_s",
            }
            assert engine_stats["requests_admitted"] == 1
            assert engine_stats["requests_completed"] == 1

            text = httpx.get(
                f"{srv.url}/metrics", params={"format": "prometheus"}
            ).text
    assert "serve_ttft_seconds_count 1" in text
    assert "serve_queue_wait_seconds_count 1" in text
    assert "serve_prefill_seconds_count 1" in text
    assert "serve_tokens_emitted_total 6" in text
    # decode ran at least one chunk past the prefill's first token
    assert "serve_decode_step_seconds_count 0" not in text
    # TTFT must be a real measurement, not a zero-fill
    for line in text.splitlines():
        if line.startswith("serve_ttft_seconds_sum"):
            assert float(line.split()[-1]) > 0


def test_engine_tpot_and_batch_size_histograms():
    """Direct engine drive: TPOT records per completed multi-token request,
    admission batch size records the wave width."""
    import jax
    import jax.numpy as jnp

    from prime_tpu.models import get_config
    from prime_tpu.models.llama import init_params
    from prime_tpu.serve.engine import ContinuousBatchingEngine

    config = get_config("tiny-test")
    params = init_params(jax.random.PRNGKey(0), config, dtype=jnp.float32)
    engine = ContinuousBatchingEngine(
        params, config, max_slots=4, capacity=128, chunk=4, prefix_cache_mb=0
    )
    reqs = [engine.submit([3, 1, 4, 1], max_new_tokens=5) for _ in range(2)]
    for _ in range(50):
        engine.tick()
        if all(r.done for r in reqs):
            break
    assert all(r.done for r in reqs)
    tpot = engine.registry.get("serve_tpot_seconds").series_snapshot()
    assert tpot["count"] == 2
    batch = engine.registry.get("serve_admission_batch_size").series_snapshot()
    assert batch["count"] >= 1 and batch["sum"] == 2  # one 2-wide wave


def test_client_http_metrics():
    """Every APIClient request records latency/status/retries into the
    process-wide registry, sync and async alike."""
    from prime_tpu.core.client import (
        _HTTP_LATENCY,
        _HTTP_REQUESTS,
        _HTTP_RETRIES,
        APIClient,
    )
    from prime_tpu.core.config import Config

    before_ok = _HTTP_REQUESTS.value(method="GET", status="200")
    before_404 = _HTTP_REQUESTS.value(method="GET", status="404")
    before_retry = _HTTP_RETRIES.value(method="GET")
    lat_before = _HTTP_LATENCY.series_snapshot(method="GET")
    lat_before_count = lat_before["count"] if lat_before else 0

    calls = {"n": 0}

    def handler(request):
        calls["n"] += 1
        if calls["n"] == 1:
            return httpx.Response(503, json={})  # retried (idempotent GET)
        if calls["n"] == 2:
            return httpx.Response(200, json={"ok": True})
        return httpx.Response(404, json={"detail": "nope"})

    cfg = Config()
    cfg.api_key = "k"
    client = APIClient(
        config=cfg, base_url="https://api.test",
        transport=httpx.MockTransport(handler),
    )
    import prime_tpu.core.client as client_mod

    # no real sleeps in tests: the 503→200 retry backoff would add seconds
    orig = client_mod._backoff
    client_mod._backoff = lambda attempt: 0.0
    try:
        assert client.get("/thing") == {"ok": True}
        with pytest.raises(Exception):
            client.get("/missing")
    finally:
        client_mod._backoff = orig
    assert _HTTP_REQUESTS.value(method="GET", status="200") == before_ok + 1
    assert _HTTP_REQUESTS.value(method="GET", status="404") == before_404 + 1
    assert _HTTP_RETRIES.value(method="GET") == before_retry + 1  # the 503 retry
    assert _HTTP_LATENCY.series_snapshot(method="GET")["count"] == lat_before_count + 2


def test_eval_runner_latency_metrics(tmp_path):
    from prime_tpu.evals.runner import EvalRunSpec, run_eval

    class Oracle:
        def generate(self, prompts, max_new_tokens, temperature, top_p=1.0):
            return ["42"] * len(prompts)

    spec = EvalRunSpec(limit=6, batch_size=2, output_dir=str(tmp_path))
    result = run_eval(spec, generator=Oracle())
    for key in (
        "sample_latency_mean_s", "sample_latency_p50_s",
        "sample_latency_p95_s", "sample_latency_max_s",
    ):
        assert key in result.metrics
        assert result.metrics[key] >= 0
    meta = json.loads((result.run_dir / "metadata.json").read_text())
    obs = meta["obs"]
    assert obs["eval_samples_total"]["series"][0]["value"] == 6
    assert obs["eval_batch_seconds"]["series"][0]["count"] == 3


def test_serve_metrics_cli(server):
    from click.testing import CliRunner

    from prime_tpu.commands.serve import serve_cmd

    httpx.get(f"{server.url}/v1/models")  # populate an http counter
    runner = CliRunner()
    out = runner.invoke(
        serve_cmd, ["metrics", "--url", server.url, "--plain"]
    )
    assert out.exit_code == 0, out.output
    assert "http_requests_total" in out.output
    as_json = runner.invoke(
        serve_cmd, ["metrics", "--url", server.url, "--output", "json"]
    )
    assert as_json.exit_code == 0
    assert json.loads(as_json.output)["server"]["http_requests_total"]["type"] == "counter"
    prom = runner.invoke(serve_cmd, ["metrics", "--url", server.url, "--prometheus"])
    assert prom.exit_code == 0
    assert "# TYPE http_requests_total counter" in prom.output
    dead = runner.invoke(serve_cmd, ["metrics", "--url", "http://127.0.0.1:9"])
    assert dead.exit_code != 0
    assert "could not scrape" in dead.output


def test_serve_cli_still_requires_model():
    """The group conversion must not silently accept a bare `prime serve`."""
    from click.testing import CliRunner

    from prime_tpu.commands.serve import serve_cmd

    result = CliRunner().invoke(serve_cmd, [])
    assert result.exit_code != 0
    assert "--model" in result.output


def test_int4_pallas_gate_under_mesh():
    """ADVICE r5: the fused int4 kernel must be ineligible under a
    multi-device mesh context, and the XLA fallback must match the
    ungated reference numerics."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from prime_tpu.models import quantize as qz

    w = jax.random.normal(jax.random.PRNGKey(0), (256, 128), jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 256), jnp.float32)
    qw = qz.quantize_weight_int4(w)
    assert qw[0].ndim == 2 and qw[0].dtype == jnp.uint8
    # outside any mesh: interpret mode keeps the kernel eligible (CPU tests)
    assert qz._int4_pallas_eligible(x, qw[0], True)
    ref = qz.matmul(x, qw)

    mesh = jax.make_mesh((2,), ("tp",), devices=jax.devices()[:2])
    with mesh:
        assert qz._mesh_context_active()
        assert not qz._int4_pallas_eligible(x, qw[0], True)
        out = qz.matmul(x, qw)
    assert not qz._mesh_context_active()
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5)


# ---- flight recorder --------------------------------------------------------


def test_flight_recorder_ring_bounded_under_churn():
    """Acceptance: the recorder's memory is strictly bounded no matter how
    many requests/events churn through it, and truncation is counted."""
    fr = FlightRecorder(capacity=8, max_events=4, max_inflight=3, slow_ms=0)
    for i in range(100):
        fr.begin(i, trace_id=f"{i:032x}", prompt_tokens=i)
        for j in range(10):
            fr.event(i, "chunk", seq=j)
        if i % 2 == 0:
            fr.end(i, "completed", tokens=3)
    s = fr.summaries()
    assert len(s["recent"]) <= 8
    assert len(s["inflight"]) <= 3
    full = fr.get(f"{98:032x}")  # lookup by trace id
    assert full is not None and full["id"] == "98"
    assert len(full["events"]) <= 4
    assert full["events_dropped"] > 0
    # evicted-over-inflight-bound timelines are retired, not leaked
    assert any(t["outcome"] == "evicted" for t in s["recent"])
    # unknown keys never raise (late events after retirement)
    fr.event("nope", "chunk")
    fr.end("nope", "completed")


def test_flight_recorder_summary_and_timeline_shape():
    fr = FlightRecorder(slow_ms=0)
    fr.begin("r1", trace_id="t" * 32, prompt_tokens=5)
    fr.event("r1", "admitted", slot=2)
    fr.annotate("r1", replica="10.0.0.1:8000")
    fr.end("r1", "completed", tokens=6)
    summary = fr.summaries()["recent"][0]
    assert summary["state"] == "done" and summary["outcome"] == "completed"
    assert summary["replica"] == "10.0.0.1:8000"
    timeline = fr.get("r1")
    events = [e["event"] for e in timeline["events"]]
    assert events == ["admitted", "completed"]
    assert timeline["events"][0]["slot"] == 2
    json.dumps(timeline)  # wire-able


def test_flight_recorder_slow_capture_persists_to_tracer(monkeypatch):
    from prime_tpu.obs import TRACER

    prev = TRACER.reconfigure(enabled=True, sink_path=None)
    try:
        fr = FlightRecorder(slow_ms=0.0001)
        fr.begin("slow", trace_id="a" * 32)
        fr.event("slow", "chunk")
        fr.end("slow", "completed")
        spans = [s for s in TRACER.drain() if s["name"] == "flight.slow_request"]
        assert spans and spans[-1]["trace_id"] == "a" * 32
        assert spans[-1]["attrs"]["timeline"][0]["event"] == "chunk"
    finally:
        TRACER.reconfigure(**prev)


def test_server_debug_requests_and_auth_parity():
    """/debug/requests on a plain (non-engine) server records the HTTP hop
    and honors the same admin-token gate as /admin/drain."""
    from prime_tpu.serve import InferenceServer

    with InferenceServer(
        "tiny-test", EchoGenerator(), port=0, admin_token="sekrit"
    ) as srv:
        tp = new_traceparent()
        ctx = parse_traceparent(tp)
        httpx.post(
            f"{srv.url}/v1/chat/completions",
            json={"messages": [{"role": "user", "content": "hi"}]},
            headers={"traceparent": tp},
            timeout=30,
        )
        assert httpx.get(f"{srv.url}/debug/requests").status_code == 403
        auth = {"Authorization": "Bearer sekrit"}
        listing = httpx.get(f"{srv.url}/debug/requests", headers=auth).json()
        assert listing["recent"][0]["trace_id"] == ctx.trace_id
        timeline = httpx.get(
            f"{srv.url}/debug/requests/{ctx.trace_id}", headers=auth
        ).json()
        assert timeline["outcome"] == "http_200"
        assert (
            httpx.get(f"{srv.url}/debug/requests/zzz", headers=auth).status_code
            == 404
        )
    # no admin token -> open, like the admin surface
    with InferenceServer("tiny-test", EchoGenerator(), port=0, admin_token="") as srv:
        assert httpx.get(f"{srv.url}/debug/requests").status_code == 200


def test_engine_trace_continuity_and_flight_timeline(tmp_path):
    """Tentpole acceptance (replica half): a traced streamed request through
    the engine leaves serve.queue_wait/serve.prefill/serve.request spans
    sharing the INBOUND trace id, and /debug/requests/{trace_id} returns the
    engine's per-chunk timeline."""
    import jax
    import jax.numpy as jnp

    from prime_tpu.evals.tokenizer import ByteTokenizer
    from prime_tpu.models import get_config
    from prime_tpu.models.llama import init_params
    from prime_tpu.obs import TRACER
    from prime_tpu.serve import InferenceServer
    from prime_tpu.serve.engine import ContinuousBatchingEngine, EngineBackend

    config = get_config("tiny-test")
    params = init_params(jax.random.PRNGKey(0), config, dtype=jnp.float32)
    engine = ContinuousBatchingEngine(
        params, config, max_slots=2, capacity=128, chunk=4, prefix_cache_mb=0
    )
    sink = tmp_path / "trace.jsonl"
    prev = TRACER.reconfigure(enabled=True, sink_path=str(sink))
    tp = new_traceparent()
    ctx = parse_traceparent(tp)
    try:
        with engine:
            backend = EngineBackend(engine, ByteTokenizer())
            with InferenceServer("tiny-test", backend, port=0) as srv:
                with httpx.stream(
                    "POST",
                    f"{srv.url}/v1/chat/completions",
                    json={
                        "messages": [{"role": "user", "content": "ab"}],
                        "max_tokens": 6,
                        "stream": True,
                    },
                    headers={"traceparent": tp},
                    timeout=120,
                ) as response:
                    assert response.status_code == 200
                    "".join(response.iter_lines())
                timeline = httpx.get(
                    f"{srv.url}/debug/requests/{ctx.trace_id}", timeout=5
                ).json()
    finally:
        TRACER.reconfigure(**prev)
    events = [e["event"] for e in timeline["events"]]
    assert events[0] == "admitted"
    for expected in ("prefill_done", "first_token", "chunk"):
        assert expected in events, events
    assert timeline["outcome"] == "completed"
    spans = [json.loads(line) for line in sink.read_text().splitlines()]
    mine = {s["name"] for s in spans if s["trace_id"] == ctx.trace_id}
    assert {"serve.queue_wait", "serve.prefill", "serve.request"} <= mine
    # batched device spans stay process-local (they cover many requests)
    dispatch = next(s for s in spans if s["name"] == "serve.dispatch")
    assert dispatch["trace_id"] != ctx.trace_id


def test_serve_profile_waterfall_stitches_cross_process(tmp_path):
    """serve_profile --trace A --trace B: spans sharing a W3C trace id merge
    into one per-request waterfall with cross-process gaps called out."""
    import pathlib
    import subprocess
    import sys

    trace_id = "ab" * 16
    router_spans = [
        {"name": "fleet.route", "trace_id": trace_id, "span_id": "r" * 16,
         "parent_id": None, "start_unix_s": 100.0, "start_s": 0.0,
         "duration_s": 0.5, "attrs": {}},
        {"name": "fleet.attempt", "trace_id": trace_id, "span_id": "a" * 16,
         "parent_id": "r" * 16, "start_unix_s": 100.01, "start_s": 0.01,
         "duration_s": 0.48, "attrs": {"replica": "rep-1"}},
    ]
    replica_spans = [
        {"name": "serve.chat", "trace_id": trace_id, "span_id": "c" * 16,
         "parent_id": "a" * 16, "start_unix_s": 100.06, "start_s": 7.0,
         "duration_s": 0.4, "attrs": {}},
        # an unrelated single-span trace: not stitched
        {"name": "serve.request", "trace_id": "cd" * 16, "span_id": "d" * 16,
         "parent_id": "e" * 16, "start_unix_s": 50.0, "start_s": 1.0,
         "duration_s": 0.1, "attrs": {}},
    ]
    a = tmp_path / "router.jsonl"
    b = tmp_path / "replica.jsonl"
    a.write_text("".join(json.dumps(s) + "\n" for s in router_spans))
    b.write_text("".join(json.dumps(s) + "\n" for s in replica_spans))
    script = str(
        pathlib.Path(__file__).resolve().parents[1] / "scripts" / "serve_profile.py"
    )
    out = subprocess.run(
        [sys.executable, script, "--trace", str(a), "--trace", str(b)],
        capture_output=True, text=True, timeout=60, check=True,
    ).stdout
    assert f"trace {trace_id}: 3 spans" in out
    assert "router.jsonl" in out and "replica.jsonl" in out
    # indentation encodes the parent chain; the replica hop calls out its gap
    assert "fleet.route" in out and "fleet.attempt" in out and "serve.chat" in out
    assert "[cross-process]" in out
    assert "+50.00 ms after parent" in out
    # --trace-id narrows to one request
    picked = subprocess.run(
        [sys.executable, script, "--trace", str(a), "--trace", str(b),
         "--trace-id", "cd" * 16],
        capture_output=True, text=True, timeout=60, check=True,
    ).stdout
    assert "serve.request" in picked and "fleet.route" not in picked


def test_serve_profile_overlap_report(tmp_path):
    """scripts/serve_profile.py --trace: pairs serve.dispatch/serve.sync
    spans by chunk seq from a PRIME_TRACE JSONL and reports the per-chunk
    host-stall fraction (the offline twin of the phase clock's sync_wait
    over a chunk's window)."""
    import json
    import pathlib
    import subprocess
    import sys

    spans = [
        # chunk 0: dispatched at t=0.00 (1ms enqueue), synced over [0.10, 0.101]
        {"name": "serve.dispatch", "start_s": 0.0, "duration_s": 0.001,
         "attrs": {"seq": 0, "steps": 8}},
        {"name": "serve.sync", "start_s": 0.10, "duration_s": 0.001,
         "attrs": {"seq": 0}},
        # chunk 1: fully stalled (sync spans the whole window)
        {"name": "serve.dispatch", "start_s": 0.2, "duration_s": 0.001,
         "attrs": {"seq": 1, "steps": 8}},
        {"name": "serve.sync", "start_s": 0.201, "duration_s": 0.099,
         "attrs": {"seq": 1}},
        # unrelated span: must be ignored
        {"name": "serve.prefill", "start_s": 0.0, "duration_s": 0.5, "attrs": {}},
        # a second engine's spans (seq restarts at 0): a new run, not an
        # overwrite of the first engine's chunk 0
        {"name": "serve.dispatch", "start_s": 1.0, "duration_s": 0.001,
         "attrs": {"seq": 0, "steps": 8}},
        {"name": "serve.sync", "start_s": 1.05, "duration_s": 0.002,
         "attrs": {"seq": 0}},
    ]
    trace = tmp_path / "trace.jsonl"
    trace.write_text("".join(json.dumps(s) + "\n" for s in spans))
    script = str(
        pathlib.Path(__file__).resolve().parents[1] / "scripts" / "serve_profile.py"
    )
    out = subprocess.run(
        [sys.executable, script, "--trace", str(trace)],
        capture_output=True, text=True, timeout=60, check=True,
    ).stdout
    assert "overlap report: 2 chunks" in out and "engine run 1/2" in out
    assert "overlap report: 1 chunks" in out and "engine run 2/2" in out
    assert "stall_frac" in out
    lines = [l for l in out.splitlines() if l.strip().startswith(("0 ", "1 "))]
    assert len(lines) == 3  # chunks 0+1 of run 1, chunk 0 of run 2
    # run 1: chunk 0 barely stalled, chunk 1 fully stalled
    assert float(lines[0].split()[-1]) < 0.05
    assert float(lines[1].split()[-1]) > 0.9
    assert "overlapped)" in out

    # an empty / span-free file degrades with a pointer, not a crash
    empty = tmp_path / "empty.jsonl"
    empty.write_text("")
    out2 = subprocess.run(
        [sys.executable, script, "--trace", str(empty)],
        capture_output=True, text=True, timeout=60, check=True,
    ).stdout
    assert "no paired" in out2
