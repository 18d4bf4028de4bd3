"""Backend adapters: one schedule, any serving surface.

The runner drives a schedule against a :class:`Target`:

- :class:`EngineTarget` — an in-process ``ContinuousBatchingEngine`` driven
  synchronously (the runner owns ``tick()``), the deterministic mode tests
  and bench sections use. Submissions, cancels, and queue-full rejections
  go through the exact engine API the server uses.
- :class:`HTTPTarget` — any OpenAI-compatible URL: a single
  ``InferenceServer``, a ``prime serve fleet`` router, or something else
  entirely. Requests ride real HTTP (SSE streams for cancellable requests,
  429s surfaced as rejections, never silently retried — loadgen measures
  the admission gate, it does not mask it), and observability is *scraped*:
  registry snapshots from ``/metrics?format=registry``, flight-recorder
  timelines from ``/debug/requests``, exposition text for linting from
  ``/metrics?format=prometheus``.

Both expose the same read surface — ``snapshots()`` (component name →
``Registry.snapshot()`` dict) and ``flight_summaries()`` — which is all the
report builder needs; tok/s, TTFT/TPOT percentiles, hit and overlap ratios
all come from snapshot deltas, not from anything the client timed.
"""

from __future__ import annotations

from typing import Any

from prime_tpu.loadgen.scenario import PlannedRequest

# Client-observed outcome labels (the report's `requests` section).
OUTCOME_COMPLETED = "completed"
OUTCOME_CANCELLED = "cancelled"
OUTCOME_REJECTED = "rejected_429"
OUTCOME_FAILED = "failed"


class NumericTokenizer:
    """Whitespace-number tokenizer: HTTP text round-trips to the same int
    ids loadgen feeds engines directly, so an HTTP-driven run and an
    in-process run exercise identical prompt blocks (non-numeric template
    words — role markers from the chat template — hash to stable small
    ids)."""

    def encode(self, text: str, add_special_tokens: bool = True) -> list[int]:
        return [
            int(tok) if tok.isdigit() else (sum(tok.encode()) % 97) + 3
            for tok in text.split()
        ]

    def decode(self, ids: list[int]) -> str:
        return " ".join(str(i) for i in ids)


def prompt_text(prompt_ids: tuple[int, ...]) -> str:
    """A prompt's on-the-wire form for :class:`NumericTokenizer` backends."""
    return " ".join(str(i) for i in prompt_ids)


class EngineTarget:
    """In-process engine, synchronously driven. The runner calls
    ``submit``/``tick``; this adapter owns nothing but the translation."""

    name = "engine"

    def __init__(self, engine: Any) -> None:
        self.engine = engine

    def submit(self, planned: PlannedRequest):
        """Submit one planned request; returns the live EngineRequest.
        Raises QueueFullError when the admission gate rejects (the runner
        records the rejection — deliberately no retry). A planned adapter
        rides through to the engine's multi-LoRA bank (``"base"``/None both
        mean the base model, matching the HTTP target's `model` field)."""
        kwargs = {}
        if planned.adapter and planned.adapter != "base":
            kwargs["adapter"] = planned.adapter
        return self.engine.submit(
            list(planned.prompt_ids), max_new_tokens=planned.max_new_tokens,
            **kwargs,
        )

    def tick(self) -> None:
        self.engine.tick()

    def snapshots(self) -> dict[str, dict]:
        self.engine.stats()  # refresh point-in-time gauges before the read
        return {"engine": self.engine.registry.snapshot()}

    def flight_summaries(self, limit: int = 1000) -> dict:
        return self.engine.flight.summaries(limit=limit)


class HTTPTarget:
    """An OpenAI-compatible chat endpoint plus the metrics surfaces to
    scrape. ``url`` takes the traffic; ``scrape_urls`` (default: just
    ``url``) are polled for registry snapshots and flight timelines —
    pass router + replica URLs to capture a whole fleet's view."""

    name = "http"

    def __init__(
        self,
        url: str,
        *,
        scrape_urls: dict[str, str] | None = None,
        model: str | None = None,
        timeout_s: float = 240.0,
        admin_token: str | None = None,
    ) -> None:
        import httpx

        self.url = url.rstrip("/")
        self.scrape_urls = {
            label: u.rstrip("/") for label, u in (scrape_urls or {"target": url}).items()
        }
        self.model = model
        self.timeout_s = timeout_s
        self._headers = (
            {"Authorization": f"Bearer {admin_token}"} if admin_token else {}
        )
        self._httpx = httpx

    # -- traffic ---------------------------------------------------------------

    def _body(self, planned: PlannedRequest, stream: bool) -> dict:
        body: dict = {
            "messages": [{"role": "user", "content": prompt_text(planned.prompt_ids)}],
            "max_tokens": planned.max_new_tokens,
            "temperature": 0.0,
        }
        model = planned.adapter or self.model
        if model:
            body["model"] = model
        if stream:
            body["stream"] = True
        return body

    def perform(self, planned: PlannedRequest, cancel_at_s: float | None) -> tuple[str, int]:
        """Blocking: run one request to completion, cancellation, or
        rejection. Returns ``(outcome, completion_tokens)``. ``cancel_at_s``
        is an absolute ``time.monotonic()`` deadline (already time-scaled by
        the runner); cancellable requests stream so closing the response
        mid-decode is a real client abandon, not a post-hoc label."""
        import time

        chat = f"{self.url}/v1/chat/completions"
        try:
            if cancel_at_s is None:
                response = self._httpx.post(
                    chat, json=self._body(planned, stream=False), timeout=self.timeout_s
                )
                if response.status_code == 429:
                    return OUTCOME_REJECTED, 0
                if response.status_code != 200:
                    return OUTCOME_FAILED, 0
                usage = response.json().get("usage", {})
                return OUTCOME_COMPLETED, int(usage.get("completion_tokens", 0))
            # cancel path: stream and abandon at the deadline
            deltas = 0
            with self._httpx.stream(
                "POST", chat, json=self._body(planned, stream=True),
                timeout=self.timeout_s,
            ) as response:
                if response.status_code == 429:
                    return OUTCOME_REJECTED, 0
                if response.status_code != 200:
                    return OUTCOME_FAILED, 0
                for line in response.iter_lines():
                    if time.monotonic() >= cancel_at_s:
                        response.close()
                        return OUTCOME_CANCELLED, deltas
                    if line.startswith("data: ") and '"content"' in line:
                        deltas += 1
            return OUTCOME_COMPLETED, deltas
        except self._httpx.HTTPError:
            return OUTCOME_FAILED, 0

    # -- observability scrape --------------------------------------------------

    def snapshots(self) -> dict[str, dict]:
        """Registry snapshots from every scrape URL, flattened to
        ``label.section`` keys (a server exposes ``server``+``engine``
        sections, a router exposes ``router``)."""
        out: dict[str, dict] = {}
        for label, base in self.scrape_urls.items():
            response = self._httpx.get(
                f"{base}/metrics", params={"format": "registry"}, timeout=10.0
            )
            response.raise_for_status()
            for section, snapshot in response.json().items():
                out[f"{label}.{section}"] = snapshot
        return out

    def flight_summaries(self, limit: int = 1000) -> dict:
        """The traffic URL's flight-recorder view (inflight + recent
        summaries) — the replay seed. Routers merge their hop with the
        serving replica's, so one scrape covers the fleet path."""
        response = self._httpx.get(
            f"{self.url}/debug/requests",
            params={"limit": limit},
            headers=self._headers,
            timeout=10.0,
        )
        response.raise_for_status()
        return response.json()

    def expositions(self) -> dict[str, str]:
        """Prometheus text from every scrape URL, for lint."""
        out = {}
        for label, base in self.scrape_urls.items():
            response = self._httpx.get(
                f"{base}/metrics", params={"format": "prometheus"}, timeout=10.0
            )
            response.raise_for_status()
            out[label] = response.text
        return out
