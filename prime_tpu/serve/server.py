"""OpenAI-compatible HTTP server over a JaxGenerator.

Wire surface (subset the platform/inference clients use, reference
api/inference.py): GET /v1/models, POST /v1/chat/completions with optional
SSE streaming. Generation runs one request at a time behind a lock — the
jitted sampler is a single compiled program and XLA serializes the chip
anyway; continuous batching is a scheduler problem for a later round.
Streaming replays the finished completion as SSE deltas (the sampler decodes
a whole turn in one lax.scan; true token-level streaming would need a
step-callback decode loop).

Chat prompts use a minimal role-tagged template; pass a HF tokenizer with a
chat template upstream for model-faithful formatting.

Observability (docs/architecture.md "Observability"): ``GET /metrics``
returns the legacy JSON counters; ``GET /metrics?format=prometheus`` renders
the server's HTTP metrics plus the backing engine's registry (queue-wait,
TTFT, prefill/decode histograms) in Prometheus text format; ``GET /healthz``
is the liveness probe. `prime serve metrics` renders either from the CLI.
"""

from __future__ import annotations

import functools
import inspect
import json
import os
import threading
import time
import uuid
import warnings
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Any
from urllib.parse import parse_qs, urlsplit

from prime_tpu.core.config import env_flag, env_int, env_str
from prime_tpu.obs.flight import FlightRecorder, parse_summary_limit
from prime_tpu.obs.metrics import Registry
from prime_tpu.obs.sentinel import Sentinel
from prime_tpu.obs.slo import SloEvaluator
from prime_tpu.obs.timeseries import (
    RegistrySampler,
    SnapshotRing,
    merge_registry_payload,
    serving_window_view,
)
from prime_tpu.obs.trace import (
    TRACEPARENT_HEADER,
    TRACER,
    TraceContext,
    parse_traceparent,
)
from prime_tpu.serve.digest import REPLICA_ROLES, HotPrefixDigest
from prime_tpu.serve.errors import DrainingError, QueueFullError, backpressure_response

CHAT_TEMPLATE = "{role}: {content}\n"

# PUT /admin/kv body bound: a real migration payload is the KV of one
# prompt (hundreds of MB at 8B-model/long-context scale), but an unbounded
# Content-Length would let one request allocate arbitrary memory before
# validation runs — same cannot-balloon-memory contract as the digest
# retention cap (serve/digest.py RETAIN_MAX_ENTRIES)
MAX_KV_PAYLOAD_BYTES = 1 << 30


@functools.lru_cache(maxsize=64)
def _accepts_kwarg(fn, name: str) -> bool:
    try:
        params = inspect.signature(fn).parameters
    except (TypeError, ValueError):
        return True  # unintrospectable callables: assume the full protocol
    return name in params or any(
        p.kind is inspect.Parameter.VAR_KEYWORD for p in params.values()
    )


class _LiveStream:
    """Marker wrapper: _chat returns this when a continuous-batching backend
    is streaming deltas live (vs a finished completion to replay)."""

    def __init__(self, deltas, request=None) -> None:
        self.deltas = deltas
        self.request = request

    def cancel(self) -> None:
        if self.request is not None and hasattr(self.request, "cancel"):
            self.request.cancel()


def _route_label(path: str) -> str:
    """Collapse a request path to a bounded route label (metric cardinality
    must not scale with whatever paths clients probe)."""
    p = urlsplit(path).path.rstrip("/") or "/"
    if p.endswith("/chat/completions"):
        return "/v1/chat/completions"
    if p.endswith("/models") or "/models/" in p:
        return "/v1/models"
    if p.endswith("/metrics"):
        return "/metrics"
    if p in ("/healthz", "/livez"):
        return "/healthz"
    if p.startswith("/admin/"):
        return "/admin"
    if p.startswith("/debug/"):
        return "/debug"
    return "other"


def _as_nonneg_int(value: Any) -> int:
    """Defensive int for stats()-sourced fields in the observatory view —
    a backend's junk value must degrade to 0, not 500 the endpoint."""
    try:
        if isinstance(value, bool) or value is None:
            return int(bool(value))
        return max(0, int(value))
    except (TypeError, ValueError):
        return 0


def render_chat_prompt(messages: list[dict[str, str]]) -> str:
    parts = [
        CHAT_TEMPLATE.format(role=m.get("role", "user"), content=m.get("content", ""))
        for m in messages
    ]
    return "".join(parts) + "assistant:"


class InferenceServer:
    """Own a generator + a ThreadingHTTPServer bound to host:port."""

    def __init__(
        self,
        model_id: str,
        generator=None,
        host: str = "127.0.0.1",
        port: int = 0,
        admin_token: str | None = None,
        role: str | None = None,
    ) -> None:
        """``generator=None`` binds the socket immediately and answers 503
        until one is assigned — serve_model uses this so a port conflict fails
        in milliseconds, not after minutes of checkpoint loading.
        ``admin_token`` (None = PRIME_FLEET_ADMIN_TOKEN env, "" = open) gates
        POST /admin/drain with `Authorization: Bearer <token>` — drain is
        irreversible, so beyond loopback it must not be one anonymous packet.
        ``role`` (None = PRIME_SERVE_ROLE env, default "any") is the
        replica's phase role — ``prefill`` / ``decode`` / ``any`` — advertised
        in /healthz so a fleet router can phase-split admission and migrate
        requests over GET/PUT /admin/kv (docs/architecture.md "Disaggregated
        serving")."""
        self.model_id = model_id
        self._draining = False  # set by drain(): finish in-flight, refuse new
        self.generator = generator
        if admin_token is None:
            admin_token = env_str("PRIME_FLEET_ADMIN_TOKEN", "")
        self.admin_token = admin_token or None
        if role is None:
            role = env_str("PRIME_SERVE_ROLE", "any")
            if role not in REPLICA_ROLES:
                # env junk degrades to the every-phase role, loudly — the
                # constructor arg stays strict (a typo in code is a bug)
                warnings.warn(
                    f"PRIME_SERVE_ROLE={role!r} is not one of {REPLICA_ROLES}; "
                    "serving as 'any'",
                    stacklevel=2,
                )
                role = "any"
        elif role not in REPLICA_ROLES:
            raise ValueError(f"role must be one of {REPLICA_ROLES}, got {role!r}")
        self.role = role
        # chat requests currently generating/streaming in THIS server: the
        # drain-complete signal for backends without their own `drained`
        # (the one-shot generator path has no engine to ask)
        self._inflight_chats = 0
        self._inflight_lock = threading.Lock()
        self._lock = threading.Lock()  # one generation on the chip at a time
        # flight recorder for backends without their own (the continuous-
        # batching engine records richer timelines itself; the /debug
        # endpoints prefer generator.flight when it exists)
        self._own_flight = FlightRecorder()
        # hot-prefix digest (serve/digest.py): every admitted chat records
        # its ROUTER-RENDERED prompt text's block-hash chain here, and
        # /healthz advertises the bounded set (merged with the engine's
        # exact id-block export when the backend has one) so a cache-aware
        # fleet balancer can route saturation fallbacks to the replica
        # holding the longest cached prefix. Only backends that declare
        # prefix_cache_enabled (EngineBackend with a live cache) advertise.
        self.prefix_digest = HotPrefixDigest()
        # server-side HTTP metrics live in the server's own registry; the
        # backing engine's registry (generator.registry, when present) is
        # rendered alongside it by the Prometheus exposition
        self.registry = Registry()
        self._m_http_requests = self.registry.counter(
            "http_requests_total", "HTTP requests served",
            labelnames=("route", "method", "status"),
        )
        self._m_http_latency = self.registry.histogram(
            "http_request_seconds", "HTTP request wall time", labelnames=("route",)
        )
        # single-replica observatory (docs/observability.md "Observatory"):
        # a rolling ring of this process's merged server+engine snapshots,
        # fed by a periodic sampler (PRIME_OBS_SAMPLE_INTERVAL_S) so the
        # windowed view at GET /admin/observatory has history even before
        # anyone asks — the fleet router keeps its own per-replica rings
        # through the health poll instead of scraping this one
        self.obs_ring = SnapshotRing()
        self._sampler = RegistrySampler(
            self._observatory_snapshot,
            self.obs_ring,
            on_sample=self._on_observatory_sample,
        )
        self._slo = SloEvaluator()
        # regression sentinel (docs/observability.md "Sentinel & incidents"):
        # rides the sampler's on_sample hook so detection runs exactly once
        # per capture; new detections become incident bundles (flight
        # timelines + registry deltas) in the bounded store behind
        # GET /admin/incidents[/{id}]
        # local import: the fleet package pulls in router.py, which imports
        # render_chat_prompt back from this module — a top-level import
        # would be circular
        from prime_tpu.serve.fleet.incidents import IncidentStore

        self.sentinel = Sentinel()
        self.incidents = IncidentStore()
        self._m_incidents = self.registry.counter(
            "serve_incidents_total", "Sentinel incidents raised",
            labelnames=("rule", "severity"),
        )
        self._t0 = time.monotonic()
        outer = self

        class Handler(BaseHTTPRequestHandler):
            def log_message(self, *args: object) -> None:  # quiet
                pass

            def _json(
                self, status: int, payload: dict, headers: dict | None = None
            ) -> None:
                self._status_sent = status
                body = json.dumps(payload).encode()
                self.send_response(status)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                for name, value in (headers or {}).items():
                    self.send_header(name, str(value))
                self.end_headers()
                self.wfile.write(body)

            def _text(self, status: int, body: str, content_type: str) -> None:
                self._status_sent = status
                raw = body.encode()
                self.send_response(status)
                self.send_header("Content-Type", content_type)
                self.send_header("Content-Length", str(len(raw)))
                self.end_headers()
                self.wfile.write(raw)

            def _observe(self, t0: float) -> None:
                route = _route_label(self.path)
                status = getattr(self, "_status_sent", 0)
                outer._m_http_requests.inc(
                    route=route, method=self.command, status=str(status)
                )
                outer._m_http_latency.observe(time.monotonic() - t0, route=route)

            def do_GET(self) -> None:
                t0 = time.monotonic()
                try:
                    self._get()
                finally:
                    self._observe(t0)

            def _get(self) -> None:
                parts = urlsplit(self.path)
                path = parts.path
                if path in ("/v1/models", "/api/v1/models"):
                    self._json(
                        200,
                        {
                            "object": "list",
                            "data": [
                                {"id": model, "object": "model"}
                                for model in outer.model_ids()
                            ],
                        },
                    )
                elif path in ("/metrics", "/v1/metrics"):
                    fmt = parse_qs(parts.query).get("format", [""])[0]
                    if fmt == "prometheus":
                        self._text(
                            200,
                            outer.metrics_prometheus(),
                            "text/plain; version=0.0.4; charset=utf-8",
                        )
                    elif fmt == "registry":
                        self._json(200, outer.metrics_registry())
                    else:
                        self._json(200, outer.metrics())
                elif path == "/healthz":
                    payload = outer.healthz()
                    # routers and k8s readiness probes gate traffic on the
                    # status code: 200 only when ready to take new work
                    self._json(200 if payload["state"] == "ready" else 503, payload)
                elif path == "/livez":
                    # liveness (the old /healthz contract): always 200 while
                    # the listener is up — loading and draining are healthy
                    # states for a process, just not routable ones
                    self._json(200, {"status": "ok"})
                elif path.rstrip("/") == "/debug/requests" or path.startswith(
                    "/debug/requests/"
                ):
                    # flight-recorder view: timelines carry prompt sizes and
                    # error strings, so auth parity with the admin surface —
                    # when an admin token gates /admin/drain it gates this too
                    if not outer._admin_authorized(self.headers):
                        self._json(403, {"error": {"message": "admin token required"}})
                        return
                    request_id = path[len("/debug/requests/"):].strip("/") if (
                        path.startswith("/debug/requests/")
                    ) else ""
                    if request_id:
                        timeline = outer.flight_recorder().get(request_id)
                        if timeline is None:
                            self._json(
                                404,
                                {"error": {"message": f"no request {request_id!r}"}},
                            )
                        else:
                            self._json(200, timeline)
                    else:
                        # ?limit= raises the per-ring summary bound so a
                        # loadgen replay capture fetches a whole run in one
                        # scrape (parse_summary_limit is shared with the
                        # fleet router so the two windows cannot drift)
                        limit = parse_summary_limit(
                            parse_qs(parts.query).get("limit", [None])[0]
                        )
                        self._json(
                            200, outer.flight_recorder().summaries(limit=limit)
                        )
                elif path == "/admin/observatory":
                    # single-replica SLO view (windowed rates/percentiles +
                    # burn verdicts over this process's own ring); admin
                    # parity like the rest of /admin and /debug
                    if not outer._admin_authorized(self.headers):
                        self._json(403, {"error": {"message": "admin token required"}})
                        return
                    self._json(200, outer.observatory_view())
                elif path.rstrip("/") == "/admin/incidents" or path.startswith(
                    "/admin/incidents/"
                ):
                    # sentinel incident bundles (flight timelines + registry
                    # deltas carry prompt evidence): admin parity
                    if not outer._admin_authorized(self.headers):
                        self._json(403, {"error": {"message": "admin token required"}})
                        return
                    incident_id = path[len("/admin/incidents/"):].strip("/") if (
                        path.startswith("/admin/incidents/")
                    ) else ""
                    if incident_id:
                        bundle = outer.incidents.get(incident_id)
                        if bundle is None:
                            self._json(
                                404,
                                {"error": {"message": f"no incident {incident_id!r}"}},
                            )
                        else:
                            self._json(200, bundle)
                    else:
                        self._json(200, outer.incidents_view())
                elif path == "/admin/profile":
                    # device-time profiler status (enabled/capturing/summary);
                    # admin parity like the rest of /admin
                    if not outer._admin_authorized(self.headers):
                        self._json(403, {"error": {"message": "admin token required"}})
                        return
                    prof = outer.profiler()
                    if prof is None:
                        self._json(
                            404,
                            {"error": {"message": "no device profiler (continuous engine required)"}},
                        )
                        return
                    self._json(200, prof.status())
                elif path == "/admin/kv":
                    # prefix-KV wire export (disaggregated serving): admin-
                    # token parity with /admin/drain — a payload is raw KV
                    # bytes of served prompts, not less sensitive than drain.
                    # A JSON body (the router's migration path sends the
                    # chat messages) rides the GET so arbitrarily long
                    # prompts never hit the request-line length cap.
                    if not outer._admin_authorized(self.headers):
                        self._json(403, {"error": {"message": "admin token required"}})
                        return
                    try:
                        # clamp: read(-1) would block until the client
                        # closes, wedging the handler thread
                        length = max(0, int(self.headers.get("Content-Length", 0)))
                    except ValueError:
                        length = 0
                    raw = self.rfile.read(length) if length else b""
                    status, body = outer.kv_export(parse_qs(parts.query), raw)
                    if isinstance(body, bytes):
                        self._status_sent = status
                        self.send_response(status)
                        self.send_header("Content-Type", "application/octet-stream")
                        self.send_header("Content-Length", str(len(body)))
                        self.end_headers()
                        self.wfile.write(body)
                    elif body is None:  # 204: no cached prefix to ship
                        self._status_sent = status
                        self.send_response(status)
                        self.send_header("Content-Length", "0")
                        self.end_headers()
                    else:
                        self._json(status, body)
                elif (
                    matched := next(
                        (
                            model
                            for model in outer.model_ids()
                            if path.rstrip("/").endswith(f"/models/{model}")
                        ),
                        None,
                    )
                ) is not None:
                    # echo the FULL matched id: HF-style ids (and adapter
                    # names) may contain "/", so the last path segment alone
                    # would truncate them
                    self._json(200, {"id": matched, "object": "model"})
                else:
                    self._json(404, {"error": {"message": f"no route {self.path}"}})

            def do_PUT(self) -> None:
                t0 = time.monotonic()
                try:
                    self._put()
                finally:
                    self._observe(t0)

            def _put(self) -> None:
                # prefix-KV wire import: the decode half of a migration
                if urlsplit(self.path).path != "/admin/kv":
                    self._json(404, {"error": {"message": f"no route {self.path}"}})
                    return
                if not outer._admin_authorized(self.headers):
                    self._json(403, {"error": {"message": "admin token required"}})
                    return
                try:
                    # clamp negatives: read(-1) blocks until the peer
                    # closes, wedging the handler thread
                    length = max(0, int(self.headers.get("Content-Length", 0)))
                except ValueError:
                    self._json(400, {"error": {"message": "bad Content-Length"}})
                    return
                if length > MAX_KV_PAYLOAD_BYTES:
                    self._json(
                        413,
                        {"error": {"message": f"KV payload over {MAX_KV_PAYLOAD_BYTES} bytes"}},
                    )
                    return
                payload = self.rfile.read(length) if length else b""
                self._json(*outer.kv_import(payload))

            def do_POST(self) -> None:
                t0 = time.monotonic()
                try:
                    self._post()
                finally:
                    self._observe(t0)

            def _post(self) -> None:
                if urlsplit(self.path).path == "/admin/drain":
                    # graceful-drain hook (k8s preStop / fleet router): stop
                    # taking new work, finish in-flight, report progress
                    if not outer._admin_authorized(self.headers):
                        self._json(403, {"error": {"message": "admin token required"}})
                        return
                    outer.drain()
                    self._json(200, outer.healthz())
                    return
                if urlsplit(self.path).path == "/admin/profile":
                    # start/stop a device-time capture window (same admin
                    # parity as /admin/drain; the fleet router proxies this
                    # path to every routable replica)
                    if not outer._admin_authorized(self.headers):
                        self._json(403, {"error": {"message": "admin token required"}})
                        return
                    prof = outer.profiler()
                    if prof is None:
                        self._json(
                            404,
                            {"error": {"message": "no device profiler (continuous engine required)"}},
                        )
                        return
                    try:
                        length = max(0, int(self.headers.get("Content-Length", 0)))
                        body = json.loads(self.rfile.read(length) or b"{}")
                    except (ValueError, json.JSONDecodeError):
                        self._json(400, {"error": {"message": "invalid JSON body"}})
                        return
                    action = body.get("action") if isinstance(body, dict) else None
                    if action == "start":
                        started = prof.start_capture()
                        self._json(
                            200, {"capturing": True, "started": bool(started)}
                        )
                    elif action == "stop":
                        result = prof.stop_capture()
                        if result is None:
                            self._json(
                                409,
                                {"error": {"message": "no capture in progress"}},
                            )
                        else:
                            self._json(200, result)
                    else:
                        self._json(
                            400,
                            {"error": {"message": "action must be 'start' or 'stop'"}},
                        )
                    return
                if self.path not in ("/v1/chat/completions", "/api/v1/chat/completions"):
                    self._json(404, {"error": {"message": f"no route {self.path}"}})
                    return
                try:
                    length = int(self.headers.get("Content-Length", 0))
                    request = json.loads(self.rfile.read(length) or b"{}")
                except (ValueError, json.JSONDecodeError):
                    self._json(400, {"error": {"message": "invalid JSON body"}})
                    return
                if not isinstance(request, dict):
                    self._json(400, {"error": {"message": "request body must be an object"}})
                    return
                want_stream = bool(request.get("stream"))
                # one trace context per chat: extracted from the inbound
                # traceparent (SDK/router hop) or generated here, so the
                # flight recorder always has a cross-process correlation id
                # — even when tracing itself is off
                trace = parse_traceparent(self.headers.get(TRACEPARENT_HEADER))
                if trace is None:
                    trace = TraceContext.generate()
                # engine backends record their own (richer) timelines from
                # submit(); for everything else the server records the hop.
                # One trace id may cover several concurrent client calls, so
                # the timeline key qualifies it with the parent span id
                # (bare-trace-id lookups resolve via FlightRecorder.get)
                fkey = f"{trace.trace_id}.{trace.span_id}"
                own_flight = outer.flight_recorder() is outer._own_flight
                if own_flight:
                    outer._own_flight.begin(
                        fkey, trace_id=trace.trace_id, stream=want_stream
                    )
                # count the WHOLE chat lifetime (generation + streaming) so a
                # drain only reports complete once live responses finished
                with outer._inflight_lock:
                    outer._inflight_chats += 1
                try:
                    try:
                        response = outer._chat(request, stream=want_stream, trace=trace)
                    except Exception as e:  # noqa: BLE001 — a bad request must get a response
                        self._json(400, {"error": {"message": f"bad request: {e}"}})
                        return
                    if isinstance(response, tuple):  # (status, error payload)
                        self._json(*response)
                        return
                    if isinstance(response, _LiveStream):
                        self._stream_live(response)
                    elif want_stream:
                        self._stream_replay(response)
                    else:
                        self._json(200, response)
                finally:
                    if own_flight:
                        outer._own_flight.end(
                            fkey,
                            f"http_{getattr(self, '_status_sent', 0)}",
                        )
                    with outer._inflight_lock:
                        outer._inflight_chats -= 1

            def _sse_headers(self) -> None:
                self._status_sent = 200
                self.send_response(200)
                self.send_header("Content-Type", "text/event-stream")
                self.end_headers()

            def _sse_chunk(self, base: dict, delta: dict, finish: str | None = None) -> None:
                chunk = {
                    **base,
                    "choices": [{"index": 0, "delta": delta, "finish_reason": finish}],
                }
                self.wfile.write(f"data: {json.dumps(chunk)}\n\n".encode())

            def _stream_live(self, live: "_LiveStream") -> None:
                """True token-level streaming off a continuous-batching
                backend: deltas are written as the engine decodes them."""
                self._sse_headers()
                base = {
                    "id": f"chatcmpl-{uuid.uuid4().hex[:12]}",
                    "object": "chat.completion.chunk",
                    "model": outer.model_id,
                }
                try:
                    for delta in live.deltas:
                        self._sse_chunk(base, {"content": delta})
                    self._sse_chunk(base, {}, finish="stop")
                    self.wfile.write(b"data: [DONE]\n\n")
                except OSError:
                    # client went away mid-stream: stop decoding for nobody;
                    # writing a farewell chunk to the dead socket would raise
                    live.cancel()
                except Exception as e:  # noqa: BLE001 — generation failure
                    live.cancel()
                    try:
                        self._sse_chunk(base, {"content": f"\n[error: {e}]"})
                        self._sse_chunk(base, {}, finish="stop")
                        self.wfile.write(b"data: [DONE]\n\n")
                    except OSError:
                        pass

            def _stream_replay(self, completion: dict) -> None:
                """SSE replay of an already-finished completion (one-shot
                generator backends decode whole turns in one lax.scan)."""
                self._sse_headers()
                text = completion["choices"][0]["message"]["content"]
                base = {
                    "id": completion["id"],
                    "object": "chat.completion.chunk",
                    "model": completion["model"],
                }
                step = 16
                for start in range(0, max(len(text), 1), step):
                    self._sse_chunk(base, {"content": text[start : start + step]})
                self._sse_chunk(base, {}, finish="stop")
                self.wfile.write(b"data: [DONE]\n\n")

        self._server = ThreadingHTTPServer((host, port), Handler)
        self._thread: threading.Thread | None = None

    @property
    def generator(self):
        return self._generator

    @generator.setter
    def generator(self, generator) -> None:
        """serve_model assigns the generator AFTER the socket is bound (and
        minutes of checkpoint loading). If a drain arrived in that window,
        forward it now — otherwise the engine never learns it should refuse
        work and healthz `drained` could never flip true."""
        self._generator = generator
        if self._draining and generator is not None:
            drain_fn = getattr(generator, "drain", None)
            if callable(drain_fn):
                drain_fn()

    # -- observability --------------------------------------------------------

    def model_ids(self) -> list[str]:
        """Every model id this server answers to: the base model plus each
        loaded multi-LoRA adapter name (EngineBackend.adapter_names — the
        OpenAI ``model`` field selects the adapter). One owner for
        /v1/models, the per-model GET, and _chat's resolution."""
        return [self.model_id, *getattr(self.generator, "adapter_names", ())]

    def metrics(self) -> dict:
        """GET /metrics: server identity + the backing engine's counters
        (admissions, completions, tokens, prefix hits, batched waves, active
        slots, queue depth) when the generator exposes ``stats()`` — the
        continuous-batching EngineBackend forwards its engine's."""
        payload: dict = {
            "model": self.model_id,
            "loaded": self.generator is not None,
        }
        stats_fn = getattr(self.generator, "stats", None)
        if callable(stats_fn):
            try:
                payload["engine"] = stats_fn()
            except Exception as e:  # noqa: BLE001 — metrics must never 500
                payload["engine_error"] = str(e)[:200]
        return payload

    def metrics_prometheus(self) -> str:
        """GET /metrics?format=prometheus: text exposition of the server's
        HTTP metrics plus the backing engine's registry (queue-wait, TTFT,
        prefill/decode histograms — see docs/architecture.md
        "Observability"). Calling the generator's stats() first refreshes
        its point-in-time gauges so a scrape never reports stale slot/queue
        depths."""
        stats_fn = getattr(self.generator, "stats", None)
        if callable(stats_fn):
            try:
                stats_fn()
            except Exception:  # noqa: BLE001 — metrics must never 500
                pass
        text = self.registry.render_prometheus()
        engine_registry = getattr(self.generator, "registry", None)
        if isinstance(engine_registry, Registry) and engine_registry is not self.registry:
            text += engine_registry.render_prometheus()
        return text

    def metrics_registry(self) -> dict:
        """GET /metrics?format=registry: full JSON snapshots (histogram
        bucket data included) of the server and engine registries — the
        machine-readable twin of the Prometheus exposition, consumed by
        `prime serve metrics`."""
        stats_fn = getattr(self.generator, "stats", None)
        if callable(stats_fn):
            try:
                stats_fn()  # refresh point-in-time gauges
            except Exception:  # noqa: BLE001 — metrics must never 500
                pass
        payload = {"server": self.registry.snapshot()}
        engine_registry = getattr(self.generator, "registry", None)
        if isinstance(engine_registry, Registry) and engine_registry is not self.registry:
            payload["engine"] = engine_registry.snapshot()
        return payload

    def _observatory_snapshot(self) -> dict | None:
        """One merged server+engine snapshot for the observatory ring —
        the same payload shape ``/metrics?format=registry`` serves, flattened
        the same way the fleet poller flattens its scrapes."""
        return merge_registry_payload(self.metrics_registry())

    def observatory_sample(self) -> bool:
        """Capture one snapshot into the ring right now (the sampler thread
        does this periodically; tests and the observatory endpoint call it
        synchronously). Returns True when a counter reset was detected."""
        return self._sampler.sample_now()

    def _on_observatory_sample(self, reset: bool) -> None:
        """Sentinel pass over the freshly captured snapshot (fires once per
        sampler capture, whichever path triggered it). New detections become
        incident bundles — flight timelines + registry deltas + span tail —
        a ``serve_incidents_total`` bump, and a ``fleet.incident`` span."""
        del reset  # the ring already cleared itself; windows restart clean
        from prime_tpu.serve.fleet.incidents import build_bundle

        for det in self.sentinel.observe({"server": self.obs_ring}):
            bundle = build_bundle(
                det.to_dict(),
                ring=self.obs_ring,
                flight=self.flight_recorder(),
                spans=TRACER.tail,
            )
            self.incidents.add(bundle)
            self._m_incidents.inc(rule=det.rule, severity=det.severity)
            TRACER.emit(
                "fleet.incident",
                0.0,
                rule=det.rule,
                severity=det.severity,
                scope=det.scope,
                incident_id=det.id,
            )

    def incidents_view(self) -> dict:
        """GET /admin/incidents: bundle summaries (newest first) plus the
        currently latched rule+scope pairs."""
        return {
            "incidents": self.incidents.list(),
            "active": [list(pair) for pair in self.sentinel.active()],
        }

    def observatory_view(self) -> dict:
        """GET /admin/observatory: the single-replica twin of the fleet
        router's view — windowed token/admission rates and latency
        percentiles over this process's ring, the engine-side SLO verdicts,
        and the resulting signal. Router-sourced policies (the 429-rate
        objective reads the router registry) report no data here."""
        self.observatory_sample()
        stats: dict = {}
        stats_fn = getattr(self.generator, "stats", None)
        if callable(stats_fn):
            try:
                stats = stats_fn()
            except Exception:  # noqa: BLE001 — the view must never 500
                stats = {}
        capacity = _as_nonneg_int(stats.get("max_slots"))
        verdicts, signal = self._slo.evaluate(
            [self.obs_ring], None, capacity=capacity or None
        )
        fast_s, slow_s = self._slo.fast_s, self._slo.slow_s
        return {
            "windows": {"fast_s": fast_s, "slow_s": slow_s},
            "signal": signal.to_dict(),
            "slo": [verdict.to_dict() for verdict in verdicts],
            "replica": {
                "model": self.model_id,
                "role": self.role,
                "state": self.healthz()["state"],
                "queue_depth": _as_nonneg_int(stats.get("queue_depth")),
                "active_slots": _as_nonneg_int(stats.get("active_slots")),
                "max_slots": capacity,
                "samples": len(self.obs_ring),
                "resets": self.obs_ring.resets,
            },
            "serving": {
                "fast": serving_window_view([self.obs_ring], fast_s),
                "slow": serving_window_view([self.obs_ring], slow_s),
            },
            "incidents": {
                "total": len(self.incidents),
                "recent": self.incidents.list()[:5],
            },
            "uptime_s": round(time.monotonic() - self._t0, 3),
        }

    def healthz(self) -> dict:
        """GET /healthz: readiness for routers / k8s probes. ``state`` is the
        replica lifecycle — ``loading`` (socket bound, checkpoint still
        loading), ``ready``, or ``draining`` (finishing in-flight, refusing
        new work) — and the HTTP handler returns 503 for anything but
        ``ready`` so traffic gates on the status code alone. ``queue_depth``
        / ``active_slots`` / ``max_slots`` come from the backing engine's
        stats() snapshot when present; the fleet balancer's least-loaded
        fallback reads them from here."""
        if self.generator is None:
            state = "loading"
        elif self._draining:
            state = "draining"
        else:
            state = "ready"
        payload = {
            "status": "ok",
            "state": state,
            # phase role for the fleet router's disaggregated admission
            # (ADDITIVE: routers that predate the field ignore it; newer
            # routers parse it tolerantly — membership.apply_health)
            "role": self.role,
            "loaded": self.generator is not None,
            "queue_depth": 0,
            "active_slots": 0,
            "max_slots": 0,
            "uptime_s": round(time.monotonic() - self._t0, 3),
        }
        stats_fn = getattr(self.generator, "stats", None)
        if callable(stats_fn):
            try:
                stats = stats_fn()
                for key in ("queue_depth", "active_slots", "max_slots"):
                    payload[key] = int(stats.get(key, 0))
                # sharded replica: advertise the mesh shape so operators and
                # routers can tell a 4-chip replica from four 1-chip ones
                # (additive — pre-mesh engines simply omit the keys)
                if int(stats.get("mesh_devices", 0) or 0) > 1:
                    payload["mesh_devices"] = int(stats["mesh_devices"])
                    if isinstance(stats.get("mesh_axes"), dict):
                        payload["mesh"] = dict(stats["mesh_axes"])
            except Exception as e:  # noqa: BLE001 — health must never 500
                payload["stats_error"] = str(e)[:200]
        # ADDITIVE multi-LoRA advertisement: the adapters this replica can
        # serve unmerged — the fleet balancer narrows adapter traffic to
        # replicas advertising the name (membership.parse_adapters on the
        # consuming side is as tolerant as the digest parse). Omitted for
        # base-only replicas, exactly like the digest for cacheless ones.
        adapter_names = tuple(getattr(self.generator, "adapter_names", ()) or ())
        if adapter_names:
            payload["adapters"] = list(adapter_names)
        # ADDITIVE hot-prefix advertisement (serve/digest.py): text-proxy
        # hashes of recently served chat prompts, merged with the engine's
        # exact id-block export when the backend has one. Routers that
        # predate the field ignore it; health must never 500 over it.
        # Omitted entirely when the backend has no prefix cache — a
        # cacheless replica must not attract cache-aware reroutes it would
        # serve with a full recompute.
        try:
            if self._advertises_prefixes():
                engine_hashes: list[int] = []
                digest_fn = getattr(self.generator, "prefix_digest", None)
                if callable(digest_fn):
                    engine_hashes = list(digest_fn())
                payload["prefix_digest"] = self.prefix_digest.snapshot(extra=engine_hashes)
        except Exception as e:  # noqa: BLE001
            payload["digest_error"] = str(e)[:200]
        if self._draining:
            # a drain is complete when nothing is queued or decoding — the
            # fleet router (and a preStop hook's poll loop) watch this flag.
            # Backends without a `drained` property (one-shot generators)
            # fall back to the server's own in-flight chat count. The count
            # is ALSO required alongside an engine's drained flag: the
            # engine retires a request once every token is queued, but the
            # HTTP thread may still be flushing those tokens to a slow SSE
            # client — killing then would truncate the stream drain promised
            # to finish.
            drained = getattr(self.generator, "drained", None)
            if drained is None:
                drained = (
                    payload["queue_depth"] == 0 and payload["active_slots"] == 0
                )
            # read under the same lock the chat threads increment under:
            # drained=true is the kill-is-safe signal, and an unlocked read
            # could observe the count before a just-admitted chat's increment
            # lands (prime-lint lock-discipline)
            with self._inflight_lock:
                inflight_chats = self._inflight_chats
            payload["drained"] = bool(drained) and inflight_chats == 0
        return payload

    def drain(self) -> None:
        """Stop accepting new chat requests (503) while in-flight ones —
        including live SSE streams — run to completion. Forwards to the
        generator's drain hook when it has one (the continuous-batching
        engine stops admitting and finishes its slots). Idempotent."""
        self._draining = True
        drain_fn = getattr(self.generator, "drain", None)
        if callable(drain_fn):
            drain_fn()

    def kv_export(self, query: dict[str, list[str]], raw: bytes = b"") -> tuple[int, Any]:
        """GET /admin/kv: serialize the cached KV of a prompt's prefix over
        the versioned wire format. Three request forms:

        - a JSON body ``{"messages": […], "max_tokens": N}`` (what the
          fleet router's migration path sends): the backend tokenizes the
          chat EXACTLY like an admission — template, special tokens, and
          tail-keep included — so the export matches the stored path for
          ANY tokenizer, and the prompt length never hits the GET
          request-line cap;
        - ``?ids=1,2,3`` — exact id-space export;
        - ``?prompt=<text>`` — the untemplated-path tokenization of raw
          text (operator convenience; on a templated backend this cannot
          match what admissions stored).

        Returns (status, bytes payload) on a hit, (204, None) when nothing
        usable is cached, (status, error dict) otherwise."""
        if self.generator is None:
            return 503, {"error": {"message": "model is still loading"}}
        ids_raw = query.get("ids", [None])[0]
        prompt = query.get("prompt", [None])[0]
        messages = None
        max_new = 1
        if raw and not ids_raw and not prompt:
            try:
                body = json.loads(raw)
            except json.JSONDecodeError:
                return 400, {"error": {"message": "invalid JSON body"}}
            if isinstance(body, dict):
                messages = body.get("messages")
                if isinstance(body.get("max_tokens"), int):
                    max_new = body["max_tokens"]
            if not isinstance(messages, list) or not all(
                isinstance(m, dict) for m in messages
            ):
                return 400, {"error": {"message": "body messages must be a list of objects"}}
        try:
            if messages is not None:
                export_messages = getattr(self.generator, "export_kv_messages", None)
                if not callable(export_messages):
                    return 501, {"error": {"message": "backend has no KV export"}}
                payload = export_messages(messages, max_new_tokens=max_new)
            elif ids_raw:
                export_ids = getattr(self.generator, "export_kv_ids", None)
                if not callable(export_ids):
                    return 501, {"error": {"message": "backend has no KV export"}}
                try:
                    ids = [int(t) for t in ids_raw.split(",") if t.strip()]
                except ValueError:
                    return 400, {"error": {"message": "ids must be comma-separated ints"}}
                payload = export_ids(ids)
            elif prompt:
                export_text = getattr(self.generator, "export_kv_text", None)
                if not callable(export_text):
                    return 501, {"error": {"message": "backend has no KV export"}}
                payload = export_text(prompt)
            else:
                return 400, {"error": {"message": "pass ?ids=… or ?prompt=…"}}
        except TimeoutError as e:
            return 503, {"error": {"message": str(e)}}
        except Exception as e:  # noqa: BLE001 — an export bug must not 500 raw
            return 500, {"error": {"message": f"KV export failed: {e}"}}
        if not payload:
            return 204, None
        return 200, payload

    def kv_import(self, payload: bytes) -> tuple[int, dict]:
        """PUT /admin/kv: plant a wire payload in the backend's prefix
        cache. A version/shape mismatch answers 400 (the payload was
        validated before the cache was touched); backends without a prefix
        cache answer 501 so the router's migration falls back cleanly."""
        if self.generator is None:
            return 503, {"error": {"message": "model is still loading"}}
        import_fn = getattr(self.generator, "import_kv", None)
        if not callable(import_fn):
            return 501, {"error": {"message": "backend has no KV import"}}
        if not payload:
            return 400, {"error": {"message": "empty KV payload"}}
        try:
            added = import_fn(payload)
        except ValueError as e:
            return 400, {"error": {"message": f"KV payload rejected: {e}"}}
        except TimeoutError as e:
            return 503, {"error": {"message": str(e)}}
        except Exception as e:  # noqa: BLE001
            return 500, {"error": {"message": f"KV import failed: {e}"}}
        return 200, {"imported_bytes": int(added)}

    def _advertises_prefixes(self) -> bool:
        """Digest gate: only a backend that owns a live prefix cache
        (EngineBackend.prefix_cache_enabled) records/advertises hot
        prefixes — a cacheless replica advertising would steal cache-aware
        reroutes it then serves with a full recompute."""
        return bool(getattr(self.generator, "prefix_cache_enabled", False))

    def _admin_authorized(self, headers) -> bool:
        """One gate for every admin-grade surface (/admin/drain,
        /debug/requests) — mirrors FleetRouter._admin_authorized."""
        if self.admin_token is None:
            return True
        return headers.get("Authorization", "") == f"Bearer {self.admin_token}"

    def flight_recorder(self) -> FlightRecorder:
        """The flight recorder behind GET /debug/requests: the backing
        engine's (rich per-chunk timelines) when the generator exposes one,
        else the server's own HTTP-level recorder."""
        flight = getattr(self.generator, "flight", None)
        return flight if isinstance(flight, FlightRecorder) else self._own_flight

    def profiler(self):
        """The device-time profiler behind /admin/profile — present only when
        the backend wraps a continuous engine (EngineBackend.profiler)."""
        return getattr(self.generator, "profiler", None)

    # -- request handling -----------------------------------------------------

    @staticmethod
    def _backpressure(e: QueueFullError):
        """429 + Retry-After: the engine's bounded queue refused the request.
        Clients (api/inference.py) honor the header with bounded retries; the
        fleet router treats it as a signal to try a less-loaded replica."""
        return backpressure_response(f"server overloaded: {e}", e.retry_after)

    def _chat(
        self,
        request: dict,
        stream: bool = False,
        trace: TraceContext | None = None,
    ):
        if self.generator is None:
            return 503, {"error": {"message": "model is still loading"}}
        if self._draining:
            return 503, {"error": {"message": "server is draining", "type": "draining"}}
        messages = request.get("messages")
        if (
            not isinstance(messages, list)
            or not messages
            or not all(isinstance(m, dict) for m in messages)
        ):
            return 400, {"error": {"message": "messages must be a non-empty list of objects"}}
        model = request.get("model") or self.model_id
        # multi-LoRA model registry: the OpenAI `model` field selects a
        # loaded adapter by name; the base model id stays the base. Unknown
        # names 404 with the authoritative list.
        adapter: str | None = None
        if model != self.model_id:
            if model in getattr(self.generator, "adapter_names", ()):
                adapter = model
            else:
                return 404, {
                    "error": {
                        "message": f"model {model!r} not served (have {self.model_ids()})"
                    }
                }
        try:
            raw_max = request.get("max_tokens")
            max_tokens = 128 if raw_max is None else int(raw_max)
            raw_temp = request.get("temperature")
            temperature = 0.0 if raw_temp is None else float(raw_temp)
            raw_top_p = request.get("top_p")
            top_p = 1.0 if raw_top_p is None else float(raw_top_p)
        except (TypeError, ValueError):
            return 400, {"error": {"message": "max_tokens/temperature/top_p must be numbers"}}
        if max_tokens < 1:
            return 400, {"error": {"message": "max_tokens must be >= 1"}}
        if not 0.0 < top_p <= 1.0:
            return 400, {"error": {"message": "top_p must be in (0, 1]"}}
        prompt = None
        # model-faithful formatting first: a tokenizer chat template (e.g. a
        # served HF checkpoint) beats the generic role-tagged fallback
        tokenizer = getattr(self.generator, "tokenizer", None)
        if tokenizer is not None and hasattr(tokenizer, "render_chat"):
            prompt = tokenizer.render_chat(messages)
        kwargs = {"top_p": top_p} if top_p < 1.0 else {}
        templated = prompt is not None
        # the digest always hashes the ROUTER's rendering of the messages
        # (not the tokenizer template) so the router's probe of the same
        # request text produces identical digest entries; rendered at most
        # once — it doubles as the prompt on the untemplated path, and a
        # templated, non-advertising deployment skips the render entirely
        routed_text = (
            render_chat_prompt(messages)
            if not templated or self._advertises_prefixes()
            else None
        )
        if templated:
            # the template already renders BOS/headers — the generator must
            # not add special tokens again (double BOS skews generation).
            # Providers written before this kwarg existed keep working.
            if _accepts_kwarg(self.generator.generate, "templated"):
                kwargs["templated"] = True
        else:
            prompt = routed_text
        if trace is not None and _accepts_kwarg(self.generator.generate, "trace"):
            # thread the distributed trace down to the engine: its queue-wait
            # / prefill / per-request spans join the caller's trace id
            kwargs["trace"] = trace
        if adapter is not None and _accepts_kwarg(self.generator.generate, "adapter"):
            kwargs["adapter"] = adapter
        # continuous-batching backends stream live and batch across requests
        # themselves — no lock, no whole-turn wait
        if stream and hasattr(self.generator, "submit_text"):
            submit_kwargs = (
                {"trace": trace}
                if trace is not None
                and _accepts_kwarg(self.generator.submit_text, "trace")
                else {}
            )
            if adapter is not None and _accepts_kwarg(
                self.generator.submit_text, "adapter"
            ):
                submit_kwargs["adapter"] = adapter
            try:
                req = self.generator.submit_text(
                    prompt, max_new_tokens=max_tokens, temperature=temperature,
                    top_p=top_p, templated=templated, **submit_kwargs,
                )
            except QueueFullError as e:
                return self._backpressure(e)
            except DrainingError:
                return 503, {"error": {"message": "server is draining", "type": "draining"}}
            except Exception as e:  # noqa: BLE001
                return 500, {"error": {"message": f"generation failed: {e}"}}
            # admitted: this prompt's prefix blocks are about to be cached —
            # advertise them
            if self._advertises_prefixes():
                self.prefix_digest.observe(routed_text)
            return _LiveStream(self.generator.stream_text(req), request=req)
        try:
            with TRACER.span(
                "serve.chat", context=trace, model=self.model_id,
                max_tokens=max_tokens,
            ):
                if getattr(self.generator, "concurrent", False):
                    completion = self.generator.generate(
                        [prompt], max_new_tokens=max_tokens, temperature=temperature, **kwargs
                    )[0]
                else:
                    with self._lock:
                        completion = self.generator.generate(
                            [prompt], max_new_tokens=max_tokens, temperature=temperature, **kwargs
                        )[0]
        except QueueFullError as e:
            return self._backpressure(e)
        except DrainingError:
            return 503, {"error": {"message": "server is draining", "type": "draining"}}
        except Exception as e:  # noqa: BLE001 — surface as an API error, keep serving
            return 500, {"error": {"message": f"generation failed: {e}"}}
        # served: advertise the prompt's prefix chain (router-rendered text,
        # matching the balancer's probe of the same messages)
        if self._advertises_prefixes():
            self.prefix_digest.observe(routed_text)
        return {
            "id": f"chatcmpl-{uuid.uuid4().hex[:12]}",
            "object": "chat.completion",
            "created": int(time.time()),
            "model": model,
            "choices": [
                {
                    "index": 0,
                    "message": {"role": "assistant", "content": completion},
                    "finish_reason": "stop",
                }
            ],
            "usage": {
                "prompt_tokens": len(prompt.split()),
                "completion_tokens": len(completion.split()),
                # openai-python's usage model requires total_tokens
                "total_tokens": len(prompt.split()) + len(completion.split()),
            },
        }

    # -- lifecycle -------------------------------------------------------------

    @property
    def port(self) -> int:
        return self._server.server_address[1]

    @property
    def url(self) -> str:
        host, port = self._server.server_address[:2]
        return f"http://{host}:{port}"

    def start(self) -> "InferenceServer":
        self._serving = True
        self._sampler.start()  # periodic observatory captures (daemon)
        self._thread = threading.Thread(target=self._server.serve_forever, daemon=True)
        self._thread.start()
        return self

    def serve_forever(self) -> None:
        self._serving = True
        self._sampler.start()
        self._server.serve_forever()

    def stop(self) -> None:
        self._sampler.stop()
        # shutdown() handshakes with the serve_forever loop and DEADLOCKS if
        # that loop never started (e.g. model load failed right after bind)
        if getattr(self, "_serving", False):
            self._server.shutdown()
            self._serving = False
        self._server.server_close()
        if hasattr(self.generator, "shutdown"):
            self.generator.shutdown()  # stop a continuous-batching engine thread

    def __enter__(self) -> "InferenceServer":
        return self.start()

    def __exit__(self, *exc: Any) -> None:
        self.stop()


def serve_model(
    model: str,
    checkpoint: str | None = None,
    tokenizer: str | None = None,
    slice_name: str | None = None,
    tensor_parallel: int | None = None,
    sequence_parallel: int | None = None,
    kv_quant: bool = False,
    weight_quant: bool | str = False,  # True/'int8' -> W8A16; 'int4' -> W4A16
    adapter: str | None = None,
    adapters: "str | dict | None" = None,
    host: str = "127.0.0.1",
    port: int = 8000,
    continuous: bool = False,
    mesh: str | None = None,
    max_slots: int = 8,
    slot_capacity: int = 2048,
    chunk: int = 8,
    speculative: bool | None = None,
    draft_len: int | None = None,
    overlap: bool | None = None,
    warmup: bool | None = None,
    profile: bool | None = None,
    prefix_cache_mb: float | None = None,
    prefix_cache_host_mb: float | None = None,
    adapter_max_inflight: int | None = None,
    adapter_weights: "str | dict | None" = None,
    max_queue: int | None = None,
    admin_token: str | None = None,
    role: str | None = None,
    max_wave: int | None = None,
) -> InferenceServer:
    """Bind the port, then build the (optionally sharded) generator.

    ``continuous=True`` serves through the slot-based continuous-batching
    engine (serve/engine.py): concurrent requests share the chip via KV-cache
    slots and streaming responses emit tokens as they decode, instead of one
    whole-turn generation at a time behind a lock. ``overlap``/``warmup``
    (None = the PRIME_SERVE_OVERLAP / PRIME_SERVE_WARMUP env defaults)
    control the engine's one-chunk-deep decode pipeline and its AOT warmup
    pass — docs/architecture.md "Engine pipeline". ``profile`` (None = the
    PRIME_SERVE_PROFILE env default, off) arms the sampled device-time step
    clock — docs/observability.md "Device time". ``prefix_cache_mb``
    (None = the PRIME_SERVE_PREFIX_CACHE_MB env default, 0 = off) is the
    byte budget of the radix prefix-KV cache, and ``prefix_cache_host_mb``
    (None = PRIME_SERVE_PREFIX_CACHE_HOST_MB, 0 = off) the host-RAM spill
    tier its device LRU demotes into — docs/architecture.md
    "Prefix cache". ``max_queue`` (None = the PRIME_SERVE_MAX_QUEUE env
    default, 0 = unbounded) bounds the engine's pending queue: submissions
    past it get 429 + Retry-After instead of queueing unboundedly — the
    admission-control half of docs/architecture.md "Serve fleet".
    ``mesh`` (None = the ``PRIME_SERVE_MESH`` env default) is the sharded-
    replica spec string (``"dp=1,fsdp=2,tp=2"``): the mesh is built here,
    the generator creates (or places) params in their shardings on it, and
    the continuous engine shards the paged KV cache onto it and serves one
    replica across the whole slice — docs/architecture.md
    "Sharded replica". It is the declarative alternative to ``slice_name``
    (which derives a mesh from a provisioned slice's topology); passing
    both is an error. ``role`` (None = the ``PRIME_SERVE_ROLE`` env default,
    ``any``) declares the replica's phase in a disaggregated fleet —
    advertised in /healthz and honored by the fleet router's migration path;
    ``--mesh role:prefill`` / ``role:decode`` resolve to the matching
    role-preset layouts (serve/mesh_config.py). ``max_wave`` (None = the
    engine's rule from shapes) asks for narrower admission waves: the warm-up
    builds a program a row capacity and wave width, so fewer widths start
    sooner, and an expert model that gives width up gets longer prompt chunks
    for it (serve/engine.py ``prefill_shapes``) — docs/architecture.md
    "Engine pipeline"."""
    from prime_tpu.evals.runner import JaxGenerator

    if mesh and slice_name:
        raise ValueError(
            "mesh and slice_name both describe the serving mesh; pass one "
            "(--mesh is the declarative axis spec, --slice derives it from "
            "the slice topology)"
        )
    if mesh and not continuous:
        raise ValueError("--mesh requires --continuous (the sharded replica is engine-only)")
    if adapters and not continuous:
        raise ValueError(
            "--adapters requires --continuous (batched multi-LoRA serving "
            "is engine-only; use --adapter to merge ONE adapter into the "
            "one-shot generator)"
        )
    if adapters and adapter:
        raise ValueError(
            "--adapter merges one adapter into the base weights; --adapters "
            "serves a bank unmerged — pass one (a merged base would corrupt "
            "the bank's base-fingerprint check)"
        )
    if adapter_weights and not continuous:
        # the bank requirement itself is enforced by the engine (adapters
        # may arrive via PRIME_SERVE_ADAPTERS rather than this argument)
        raise ValueError(
            "--adapter-weight requires --continuous (weighted shares split "
            "the multi-LoRA engine's per-tenant admission)"
        )
    if adapters and weight_quant:
        raise ValueError(
            "--adapters does not compose with --weight-quant yet: the bank's "
            "base-fingerprint check (and the LoRA delta's reference layout) "
            "need the unquantized base weights"
        )
    if mesh is None and env_str("PRIME_SERVE_MESH", "").strip() and (
        not continuous or slice_name
    ):
        # the env default only reaches the continuous engine (and a --slice
        # mesh wins over it): an ambient PRIME_SERVE_MESH must not fail a
        # plain serve the way the explicit flag does, but silently serving
        # single-chip/slice-derived would be worse — say so once, loudly
        warnings.warn(
            "PRIME_SERVE_MESH is set but ignored: the sharded replica needs "
            "continuous=True and no slice_name (pass --continuous / drop "
            "--slice, or use --mesh to fail fast instead)",
            stacklevel=2,
        )
    # speculative defaults defer to the env knobs (the same helpers the
    # engine uses when constructed directly): the one-shot generator path
    # below needs them resolved to a concrete bool/int
    if speculative is None:
        speculative = env_flag("PRIME_SERVE_SPEC", False)
    if draft_len is None:
        draft_len = env_int("PRIME_SERVE_DRAFT_LEN", 4)
    # same clamp the engine applies: a junk env value must not crash the
    # one-shot generator path while the continuous path silently clamps
    draft_len = max(1, int(draft_len))
    # fail fast on EADDRINUSE; admin_token=None reads PRIME_FLEET_ADMIN_TOKEN,
    # role=None reads PRIME_SERVE_ROLE (the phase-split fleet's --role)
    server = InferenceServer(
        model, host=host, port=port, admin_token=admin_token, role=role
    )
    try:
        # the sharded replica's mesh is built HERE and handed to the
        # generator, so params are created (or placed) in their shardings
        # and the engine receives them already sharded — no device ever
        # holds the whole model
        serve_mesh = None
        if continuous and not slice_name:
            import jax

            from prime_tpu.serve.mesh_config import parse_mesh_spec

            mesh_config = parse_mesh_spec(
                mesh if mesh is not None else env_str("PRIME_SERVE_MESH", ""),
                jax.device_count(),
            )
            if mesh_config is not None and mesh_config.total_devices > 1:
                serve_mesh = mesh_config.build()
        generator = JaxGenerator(
            model,
            checkpoint=checkpoint,
            tokenizer=tokenizer,
            mesh=serve_mesh,
            slice_name=slice_name,
            tensor_parallel=tensor_parallel,
            sequence_parallel=sequence_parallel,
            kv_quant=kv_quant,
            weight_quant=weight_quant,
            adapter=adapter,
            # the engine drafts per-slot itself; the one-shot generator path
            # uses spec_generate directly
            speculative=speculative and not continuous,
            draft_len=draft_len,
        )
        if continuous:
            from prime_tpu.serve.engine import ContinuousBatchingEngine, EngineBackend

            cache_spec = None
            if generator.mesh is not None:
                # an sp axis shards each slot's KV cache over the slice's
                # slot dimension — long-context serving where one request's
                # cache exceeds a single chip's HBM; MLA caches keep their
                # single-latent head axis replicated (serving_cache_spec is
                # the one owner, shared with the engine and evals/runner.py)
                from prime_tpu.parallel.sharding import serving_cache_spec

                cache_spec = serving_cache_spec(generator.config, generator.mesh)
            # one chip's slice of a sharded vocabulary (vocab_published) holds
            # none of the tokenizer's special rows: ids on the wire index the
            # slice, its row 2 is no end of sequence, and with random weights
            # one first token in vocab_size would be an empty answer. Such a
            # replica ends a stream at max_tokens; the chip that holds the
            # end-of-sequence row would end it sooner
            sliced = generator.config.vocab_published is not None
            engine = ContinuousBatchingEngine(
                generator.params,
                generator.config,
                eos_id=-1 if sliced else generator.tokenizer.eos_id,
                pad_id=generator.tokenizer.pad_id,
                max_slots=max_slots,
                capacity=slot_capacity,
                chunk=chunk,
                mesh=generator.mesh,
                cache_spec=cache_spec,
                kv_quant=kv_quant,
                speculative=speculative,
                draft_len=draft_len,
                overlap=overlap,
                warmup=warmup,
                profile=profile,
                prefix_cache_mb=prefix_cache_mb,
                prefix_cache_host_mb=prefix_cache_host_mb,
                max_queue=max_queue,
                max_wave=max_wave,
                # multi-LoRA bank: {name: dir} / "name=dir,..." / None
                # (None reads PRIME_SERVE_ADAPTERS inside the engine); the
                # inflight cap and the weighted shares drive the per-tenant
                # fair (weighted round-robin) admission pop
                adapters=adapters,
                adapter_max_inflight=adapter_max_inflight,
                adapter_weights=adapter_weights,
                # a prefill-role replica's batched waves must store EVERY
                # member's KV: its GET /admin/kv exports are the migration's
                # whole point, and a batched admission that only stored
                # member 0 would turn wave members' migrations cold
                prefix_store_all=server.role == "prefill",
            )
            engine.start()
            server.generator = EngineBackend(engine, generator.tokenizer)
        else:
            server.generator = generator
    except BaseException:
        server.stop()  # don't leak the bound listener when the model fails to load
        raise
    return server
