"""Device-time observatory: a sampled step clock under the serving engine.

The host-side stack (metrics/trace/flight recorder) goes dark at the
dispatch boundary — once a compiled program is enqueued, wall-time spent
*on the device* is invisible until something forces a sync. This module
makes device time a first-class observable without giving up the engine's
one-chunk-deep overlap pipeline:

- **Step clock** — an N-of-M sampler: every ``sample_every``-th dispatch of
  each phase (decode tick / prefill wave / speculative window / prefix
  assemble) is *fenced*: the profiler first drains any in-flight
  predecessor, stamps the clock, lets the engine dispatch, then
  ``block_until_ready``-s the output. The measured window is that one
  program's device execution, aggregated per program signature
  (phase x batch-bucket x mesh shape) into
  ``serve_device_step_seconds{phase=...}``. When the profiler is inactive
  the step() call returns a shared no-op — zero added syncs, asserted by
  test.
- **Compile accounting** lives in the engine's phase clock
  (``obs/phase.py``: ``jax.monitoring`` listeners, always on); the summary
  and a capture window read the clock's totals and its list of builds.
- **HBM accounting** — ``device.memory_stats()`` + live-buffer polling into
  gauges next to the prefix-cache byte gauges (the CPU backend reports no
  memory_stats; the gauges then stay at their last value / zero).
- **MFU attribution** — XLA ``cost_analysis`` FLOPs/bytes per compiled
  program (captured by lowering once per phase on a sampled dispatch — a
  host-side retrace, no compile, no device work) over the measured step
  seconds against a per-generation roofline, so BENCH/MULTICHIP rounds
  report achieved-vs-peak per phase.
- **Perfetto export** — a capture window (``/admin/profile`` start/stop or
  ``prime serve profile``) merges host spans from the tracer ring with the
  device step samples and XLA compile events into a Chrome-trace
  ``trace.json`` loadable at https://ui.perfetto.dev.

Like the rest of the obs layer this module imports nothing heavyweight at
import time; ``jax`` is imported lazily inside the code paths that fence or
poll, so importing ``prime_tpu.obs`` stays dependency-free.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Callable

from prime_tpu.obs.metrics import DEFAULT_LATENCY_BUCKETS, Registry
from prime_tpu.obs.phase import PhaseClock
from prime_tpu.utils.env import env_int

__all__ = ["DeviceProfiler", "chrome_trace", "PEAK_TFLOPS_BF16"]

# Per-chip dense bf16 peak (TFLOP/s) by device_kind substring — the roofline
# denominator for MFU attribution, scaled by the replica's mesh size. The
# numbers are the published per-chip peaks; treat the resulting MFU as a
# per-generation estimate, not a measurement. Unknown kinds (notably the CPU
# backend used in tests/CI) report mfu=None — see docs/observability.md
# "Device time" for the caveats.
PEAK_TFLOPS_BF16: dict[str, float] = {
    "TPU v2": 45.0,
    "TPU v3": 123.0,
    "TPU v4": 275.0,
    "TPU v5 lite": 197.0,
    "TPU v5e": 197.0,
    "TPU v5": 459.0,  # v5p; checked after the lite/e spellings
    "TPU v6 lite": 918.0,
    "TPU v6e": 918.0,
}


def _bucket_label(n: int) -> str:
    """Power-of-two batch bucket label — bounded series cardinality even
    when admission batch sizes wander."""
    b = 1
    while b < max(1, int(n)):
        b *= 2
    return str(b)


# ---------------------------------------------------------------------------
# Step handles returned by DeviceProfiler.step()


class _NullStep:
    """Inactive profiler: a shared, allocation-free no-op handle. Its
    __enter__/__exit__ touch neither jax nor the clock — profiling off
    means zero added syncs on the dispatch path."""

    __slots__ = ()

    def __enter__(self) -> "_NullStep":
        return self

    def __exit__(self, *exc: Any) -> bool:
        return False

    def fence(self, value: Any) -> None:
        pass


_NULL_STEP = _NullStep()


class _SampledStep:
    """One fenced step-clock sample. Enter drains the predecessor (the
    overlap pipeline may still be executing chunk N when chunk N+1
    dispatches — fencing without the drain would bill N's tail to N+1),
    stamps the clock; the engine dispatches and hands the output to
    ``fence``; exit blocks on it and records the window."""

    __slots__ = ("_prof", "_phase", "_batch", "_steps", "_pre", "_out", "_t0")

    def __init__(
        self,
        prof: "DeviceProfiler",
        phase: str,
        batch: int,
        steps: int,
        pre: Any,
    ) -> None:
        self._prof = prof
        self._phase = phase
        self._batch = batch
        self._steps = steps
        self._pre = pre
        self._out: Any = None
        self._t0 = 0.0

    def __enter__(self) -> "_SampledStep":
        import jax  # noqa: PLC0415

        if self._pre is not None:
            try:
                jax.block_until_ready(self._pre)
            except Exception:  # noqa: BLE001 — a deleted buffer skips the drain
                pass
        self._t0 = time.monotonic()
        return self

    def fence(self, value: Any) -> None:
        self._out = value

    def __exit__(self, exc_type: Any, exc: Any, tb: Any) -> bool:
        if exc_type is not None or self._out is None:
            return False
        import jax  # noqa: PLC0415

        try:
            jax.block_until_ready(self._out)
        except Exception:  # noqa: BLE001 — a failed dispatch records nothing
            return False
        self._prof._record(
            self._phase, self._t0, time.monotonic() - self._t0,
            self._batch, self._steps,
        )
        return False


class DeviceProfiler:
    """Sampled device step clock + HBM/MFU accounting for one engine.

    Constructed unconditionally by the engine (the metric families must
    exist whether or not profiling ever turns on, so the /metrics shape is
    stable); ``enabled`` turns on steady-state N-of-M sampling, and a
    capture window (``start_capture``/``stop_capture``) temporarily samples
    every dispatch and collects a mergeable timeline.
    """

    def __init__(
        self,
        registry: Registry,
        *,
        enabled: bool = False,
        sample_every: int | None = None,
        mesh_devices: int = 1,
        clock: PhaseClock | None = None,
    ) -> None:
        self.registry = registry
        # the engine's phase clock owns the compile accounting (None for a
        # profiler built without an engine: compile totals then read 0)
        self._clock = clock
        self.enabled = bool(enabled)
        if sample_every is None:
            sample_every = env_int("PRIME_SERVE_PROFILE_SAMPLE", 16)
        self.sample_every = max(1, int(sample_every))
        self.mesh_devices = max(1, int(mesh_devices or 1))
        self._mesh_label = str(self.mesh_devices)
        self._lock = threading.Lock()
        self._counts: dict[str, int] = {}  # engine-thread only
        self._agg: dict[str, list[float]] = {}  # phase -> [samples, total_s]
        self._costs: dict[str, dict[str, float]] = {}  # phase -> flops/bytes
        self._last_mem: dict[str, float] = {}
        self._last_mem_poll = 0.0
        # capture window state (None = no capture in progress)
        self._capture: list[dict] | None = None
        self._capture_t0 = 0.0
        self._capture_wall0 = 0.0
        r = registry
        # serve_device_step_seconds{phase,batch,mesh} (histogram): fenced
        # device execution seconds of one sampled dispatch, per program
        # signature. serve_hbm_bytes_in_use / serve_hbm_bytes_limit /
        # serve_live_buffers / serve_live_buffer_bytes (gauges): allocator
        # view next to the prefix-cache byte gauges. serve_mfu_ratio{phase}
        # (gauge): achieved FLOP/s over the per-generation roofline.
        self._m_step_s = r.histogram(
            "serve_device_step_seconds",
            "Fenced device seconds of one sampled dispatch, by program "
            "signature (phase x batch bucket x mesh size)",
            buckets=DEFAULT_LATENCY_BUCKETS,
            labelnames=("phase", "batch", "mesh"),
        )
        self._m_hbm_used = r.gauge(
            "serve_hbm_bytes_in_use", "Device allocator bytes in use"
        )
        self._m_hbm_limit = r.gauge(
            "serve_hbm_bytes_limit", "Device allocator byte limit"
        )
        self._m_live_buffers = r.gauge(
            "serve_live_buffers", "Live device arrays held by the process"
        )
        self._m_live_buffer_bytes = r.gauge(
            "serve_live_buffer_bytes", "Bytes of live device arrays"
        )
        self._m_mfu = r.gauge(
            "serve_mfu_ratio",
            "Achieved FLOP/s over the per-generation peak, by phase "
            "(cost-model FLOPs; absent roofline reports 0)",
            labelnames=("phase",),
        )

    # -- lifecycle ---------------------------------------------------------

    @property
    def active(self) -> bool:
        """True when any dispatch should carry a phase marker: steady-state
        sampling is on, or a capture window is open."""
        return self.enabled or self._capture is not None

    # -- step clock --------------------------------------------------------

    def step(
        self,
        phase: str,
        *,
        pre: Any = None,
        batch: int = 1,
        steps: int = 1,
        cost_fn: Callable | None = None,
        cost_args: tuple = (),
    ) -> Any:
        """Context handle for one dispatch. The engine wraps the dispatch
        call and hands the output array to ``handle.fence(out)``; whether
        that costs anything is the profiler's decision:

        - inactive, or active but unsampled -> shared no-op (zero syncs,
          zero allocation),
        - sampled -> drain ``pre``, time the dispatch, fence the output,
          and (once per phase) lower ``cost_fn(*cost_args)`` for its XLA
          cost analysis.
        """
        if not self.active:
            return _NULL_STEP
        capturing = self._capture is not None
        n = self._counts.get(phase, 0)
        self._counts[phase] = n + 1
        if not capturing and n % self.sample_every:
            return _NULL_STEP
        if cost_fn is not None and phase not in self._costs:
            self._note_cost(phase, cost_fn, cost_args)
        return _SampledStep(self, phase, batch, steps, pre)

    def _record(
        self, phase: str, t0: float, seconds: float, batch: int, steps: int
    ) -> None:
        self._m_step_s.observe(
            seconds,
            phase=phase,
            batch=_bucket_label(batch),
            mesh=self._mesh_label,
        )
        cost = self._costs.get(phase)
        if cost and cost.get("flops") and seconds > 0:
            peak = self.peak_flops()
            if peak:
                self._m_mfu.set(cost["flops"] / seconds / peak, phase=phase)
        with self._lock:
            agg = self._agg.setdefault(phase, [0.0, 0.0])
            agg[0] += 1
            agg[1] += seconds
            if self._capture is not None:
                self._capture.append(
                    {
                        "phase": phase,
                        "start_s": t0,
                        "duration_s": seconds,
                        "batch": int(batch),
                        "steps": int(steps),
                    }
                )

    # -- cost model --------------------------------------------------------

    def note_cost(self, phase: str, fn: Callable, args: tuple) -> None:
        """Public cost probe for call sites where the program/args pair is
        only known mid-region (the prefill chunk loop). One attr + dict
        check when nothing to do."""
        if not self.active or phase in self._costs:
            return
        self._note_cost(phase, fn, args)

    def _note_cost(self, phase: str, fn: Callable, args: tuple) -> None:
        """XLA cost_analysis FLOPs/bytes for this phase's program, captured
        once by lowering the jitted callable against the live dispatch args.
        Lowering re-traces on the host (tens of ms, no compile, no device
        work) — paid once per phase, only on a sampled dispatch."""
        # claim the slot first: a failing lower must not retry every sample
        self._costs[phase] = {}
        try:
            lowered = fn.lower(*args)
            analysis = lowered.cost_analysis()
            if isinstance(analysis, (list, tuple)):
                analysis = analysis[0] if analysis else {}
            if not isinstance(analysis, dict):
                return
            self._costs[phase] = {
                "flops": float(analysis.get("flops", 0.0) or 0.0),
                "bytes": float(analysis.get("bytes accessed", 0.0) or 0.0),
            }
        except Exception:  # noqa: BLE001 — cost attribution is best-effort
            return

    def peak_flops(self) -> float | None:
        """Replica roofline in FLOP/s (per-chip generation peak x mesh
        size), or None when the device generation is unknown (CPU backend)."""
        kind = self._device_kind()
        if kind is None:
            return None
        for prefix, tflops in PEAK_TFLOPS_BF16.items():
            if kind.startswith(prefix):
                return tflops * 1e12 * self.mesh_devices
        return None

    def _device_kind(self) -> str | None:
        try:
            import jax  # noqa: PLC0415

            device = jax.local_devices()[0]
            if device.platform != "tpu":
                return None
            return str(device.device_kind)
        except Exception:  # noqa: BLE001
            return None

    # -- HBM accounting ----------------------------------------------------

    def poll_memory(self, min_interval_s: float = 1.0) -> None:
        """Refresh the allocator gauges (engine stats refresh calls this).
        Rate-limited; a backend without memory_stats (CPU) still reports
        the live-buffer census."""
        if not self.active:
            return
        now = time.monotonic()
        if now - self._last_mem_poll < min_interval_s:
            return
        self._last_mem_poll = now
        mem: dict[str, float] = {}
        try:
            import jax  # noqa: PLC0415

            stats = jax.local_devices()[0].memory_stats() or {}
            if stats:
                mem["hbm_bytes_in_use"] = float(stats.get("bytes_in_use", 0))
                mem["hbm_bytes_limit"] = float(
                    stats.get("bytes_limit")
                    or stats.get("bytes_reservable_limit")
                    or 0
                )
                self._m_hbm_used.set(mem["hbm_bytes_in_use"])
                self._m_hbm_limit.set(mem["hbm_bytes_limit"])
            arrays = jax.live_arrays()
            mem["live_buffers"] = float(len(arrays))
            mem["live_buffer_bytes"] = float(
                sum(int(getattr(a, "nbytes", 0) or 0) for a in arrays)
            )
            self._m_live_buffers.set(mem["live_buffers"])
            self._m_live_buffer_bytes.set(mem["live_buffer_bytes"])
        except Exception:  # noqa: BLE001 — telemetry must not fail serving
            return
        with self._lock:
            self._last_mem.update(mem)

    # -- capture window ----------------------------------------------------

    def start_capture(self) -> bool:
        """Open a capture window: every dispatch is fenced and collected
        until ``stop_capture``. Returns False when one is already open."""
        with self._lock:
            if self._capture is not None:
                return False
            self._capture = []
            self._capture_t0 = time.monotonic()
            self._capture_wall0 = time.time()
        return True

    def stop_capture(self) -> dict | None:
        """Close the window; returns the profile result (summary + merged
        Chrome-trace timeline) or None when no capture was open."""
        with self._lock:
            if self._capture is None:
                return None
            samples = self._capture
            t0 = self._capture_t0
            wall0 = self._capture_wall0
            self._capture = None
        # programs built inside the window, from the phase clock's list
        compiles = [
            {"phase": b["phase"], "start_s": b["start_s"], "duration_s": b["seconds"]}
            for b in (self._clock.builds() if self._clock else [])
            if b["start_s"] + b["seconds"] >= t0
        ]
        duration_s = time.monotonic() - t0
        host_spans = self._host_spans_since(t0)
        trace = chrome_trace(
            samples, compiles, host_spans, base_s=t0, base_unix_s=wall0
        )
        return {
            "duration_s": round(duration_s, 6),
            "samples": len(samples),
            "host_spans": len(host_spans),
            "summary": self.summary(),
            "trace": trace,
        }

    @staticmethod
    def _host_spans_since(t0: float) -> list[dict]:
        """Finished host spans from the tracer ring whose start falls inside
        the capture window — non-destructive, so the JSONL sink and other
        ring consumers are untouched."""
        from prime_tpu.obs.trace import TRACER  # noqa: PLC0415

        return [s for s in TRACER.tail() if s.get("start_s", 0.0) >= t0]

    def status(self) -> dict:
        """GET /admin/profile payload."""
        with self._lock:
            capturing = self._capture is not None
            captured = len(self._capture) if self._capture is not None else 0
        return {
            "enabled": self.enabled,
            "capturing": capturing,
            "captured_samples": captured,
            "sample_every": self.sample_every,
            "summary": self.summary(),
        }

    # -- summaries ---------------------------------------------------------

    def summary(self) -> dict:
        """The ``device_profile`` dict embedded in BENCH records and loadgen
        reports: per-phase step seconds, compile totals, cost-model
        FLOPs/bytes, achieved-vs-roofline MFU, and the last memory poll."""
        peak = self.peak_flops()
        with self._lock:
            agg = {k: list(v) for k, v in self._agg.items()}
            mem = dict(self._last_mem)
        compiles = (
            self._clock.build_totals() if self._clock
            else {"total": 0, "seconds": 0.0, "loads": 0}
        )
        phases: dict[str, dict] = {}
        for phase, (count, total_s) in sorted(agg.items()):
            mean_s = total_s / count if count else 0.0
            cost = self._costs.get(phase) or {}
            flops = cost.get("flops") or 0.0
            entry: dict[str, Any] = {
                "samples": int(count),
                "total_s": round(total_s, 6),
                "mean_s": round(mean_s, 6),
            }
            if flops:
                entry["flops_per_dispatch"] = flops
                if mean_s > 0:
                    entry["achieved_tflops"] = round(flops / mean_s / 1e12, 4)
                    if peak:
                        entry["mfu"] = round(flops / mean_s / peak, 6)
            if cost.get("bytes"):
                entry["bytes_per_dispatch"] = cost["bytes"]
                if mean_s > 0:
                    entry["achieved_gbps"] = round(
                        cost["bytes"] / mean_s / 1e9, 4
                    )
            phases[phase] = entry
        return {
            "sample_every": self.sample_every,
            "mesh_devices": self.mesh_devices,
            "peak_tflops": round(peak / 1e12, 3) if peak else None,
            "phases": phases,
            "compiles": compiles,
            "memory": mem,
        }


# ---------------------------------------------------------------------------
# Chrome-trace (Perfetto) export


def chrome_trace(
    device_samples: list[dict],
    compile_events: list[dict],
    host_spans: list[dict],
    *,
    base_s: float,
    base_unix_s: float | None = None,
) -> dict:
    """Merge device step samples, XLA compile events, and host tracer spans
    into one Chrome-trace object (``{"traceEvents": [...]}``) loadable in
    Perfetto / chrome://tracing.

    Tracks: pid 1 = host spans (one tid per span name, since spans finish on
    many threads), pid 2 = device step samples (one tid per phase) with the
    compile events on their own tid. All duration events use phase ``"X"``;
    timestamps are microseconds from ``base_s`` (monotonic), sorted so every
    (pid, tid) track is monotonic.
    """
    events: list[dict] = []
    host_tids: dict[str, int] = {}
    device_tids: dict[str, int] = {}

    def _tid(table: dict[str, int], key: str) -> int:
        if key not in table:
            table[key] = len(table) + 1
        return table[key]

    def _ts(start_s: float) -> float:
        return round(max(0.0, start_s - base_s) * 1e6, 3)

    for span in host_spans:
        name = str(span.get("name", "span"))
        events.append(
            {
                "name": name,
                "ph": "X",
                "pid": 1,
                "tid": _tid(host_tids, name),
                "ts": _ts(float(span.get("start_s", base_s))),
                "dur": round(max(0.0, float(span.get("duration_s", 0.0))) * 1e6, 3),
                "args": dict(span.get("attrs") or {}),
            }
        )
    for sample in device_samples:
        phase = str(sample.get("phase", "step"))
        events.append(
            {
                "name": f"device.{phase}",
                "ph": "X",
                "pid": 2,
                "tid": _tid(device_tids, phase),
                "ts": _ts(float(sample.get("start_s", base_s))),
                "dur": round(max(0.0, float(sample.get("duration_s", 0.0))) * 1e6, 3),
                "args": {
                    "batch": sample.get("batch"),
                    "steps": sample.get("steps"),
                },
            }
        )
    compile_tid = len(device_tids) + 1
    for comp in compile_events:
        events.append(
            {
                "name": "xla.compile",
                "ph": "X",
                "pid": 2,
                "tid": compile_tid,
                "ts": _ts(float(comp.get("start_s", base_s))),
                "dur": round(max(0.0, float(comp.get("duration_s", 0.0))) * 1e6, 3),
                "args": {"phase": comp.get("phase")},
            }
        )
    events.sort(key=lambda e: (e["pid"], e["tid"], e["ts"]))
    # metadata events name the tracks in the Perfetto UI
    meta: list[dict] = [
        {"name": "process_name", "ph": "M", "pid": 1, "tid": 0,
         "args": {"name": "host spans"}},
        {"name": "process_name", "ph": "M", "pid": 2, "tid": 0,
         "args": {"name": "device steps"}},
    ]
    for name, tid in host_tids.items():
        meta.append(
            {"name": "thread_name", "ph": "M", "pid": 1, "tid": tid,
             "args": {"name": name}}
        )
    for phase, tid in device_tids.items():
        meta.append(
            {"name": "thread_name", "ph": "M", "pid": 2, "tid": tid,
             "args": {"name": phase}}
        )
    meta.append(
        {"name": "thread_name", "ph": "M", "pid": 2, "tid": compile_tid,
         "args": {"name": "xla compile"}}
    )
    trace: dict[str, Any] = {
        "traceEvents": meta + events,
        "displayTimeUnit": "ms",
    }
    if base_unix_s is not None:
        trace["metadata"] = {"capture_start_unix_s": base_unix_s}
    return trace
