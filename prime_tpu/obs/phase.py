"""Phase clock: every second of the engine loop's thread charged to one phase.

The thread that drives an engine (``tick()``'s caller: the loop thread once
``start()`` ran) is at every instant in exactly one *phase*. Entering a
phase charges the time since the last switch to the phase being left;
leaving restores the enclosing one. The phases are therefore exclusive and
exhaustive by construction: over any interval their seconds sum to the wall
time of that thread. Seconds accumulate in plain floats on the thread and
are published once a tick (``flush``) into
``serve_loop_seconds_total{phase=...}``.

**The starved clock.** The engine has one device queue, in order, so the
newest output of a model program is ready exactly when everything enqueued
before it has run. The engine hands the clock that output at each enqueue
(``enqueued``); the clock asks ``is_ready()`` at every switch, without
blocking (and before and after a program build or a collection, which are
long beside a chunk), and keeps one bit: *starved*, true from the first look
that finds the chip with no model program left until the next ``enqueued``. While it is
true, the seconds charged to a phase are charged a second time to
``serve_loop_starved_seconds_total{phase=...}``. A look that finds the output
ready after one that did not means the chip finished somewhere in between:
those seconds go to ``serve_loop_starved_unseen_seconds_total``, so the chip's
true idle time with work lies between *starved* and *starved + unseen*. An
annotation opened while the bit is set carries ``starved=1`` into a capture.

One call per site feeds three consumers:

- the phase clock itself (always on: two ``time.monotonic()`` reads a scope);
- a ``jax.profiler.TraceAnnotation`` of the span's name and attributes, so a
  ``jax.profiler`` capture holds the host's phases on the same clock as the
  device's operations (outside a capture the annotation is a no-op);
- the ``TRACER`` span of the same name when ``PRIME_TRACE`` is set.

**Program builds.** ``jax.monitoring`` reports every trace, lowering and
backend compile (or persistent-cache load) with its duration, on the thread
that paid for it. One process-wide listener set moves those seconds out of
the phase that triggered them into ``program_build``, counts them into
``serve_compiles_total`` / ``serve_compile_seconds`` /
``serve_program_loads_total`` under that phase's label, and keeps the last
builds by name. No private JAX entry point is patched. A ``gc.callbacks`` hook
beside them moves the seconds of a garbage collection that ran on a clock's
thread into ``gc`` the same way.

Like the rest of ``obs`` this module imports ``jax`` only where an
annotation is opened or the listeners are registered.
"""

from __future__ import annotations

import gc
import threading
import time
from collections import deque
from typing import Any

from prime_tpu.obs.metrics import DEFAULT_LATENCY_BUCKETS, Registry
from prime_tpu.obs.trace import TRACER, TraceContext, Tracer

__all__ = ["PHASES", "PhaseClock"]

# the loop's phases (docs/observability.md "Engine loop phases"); `warmup`
# is the AOT pass on the thread that calls start(), before the loop exists
PHASES: tuple[str, ...] = (
    "no_work", "dispatch", "sync_wait", "emit", "admit", "prefix_seed",
    "prefill", "prefill_sync", "prefix_store", "program_build", "gc", "kv_jobs",
    "stats", "other", "warmup",
)
# the loop spends these inside a blocking read of a model program's output,
# which returns when the chip finishes: a look at their exit misses nothing
BLOCKED_ON_DEVICE = ("sync_wait", "prefill_sync")

# jax.monitoring event names, checked against JAX 0.9.0
# (jax/_src/dispatch.py, jax/_src/compiler.py)
TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BUILD_EVENT = "/jax/core/compile/backend_compile_duration"
TIMED_EVENTS = (TRACE_EVENT, LOWER_EVENT, BUILD_EVENT)
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"

BUILDS_KEPT = 64

# per thread: the clock attached to it, whether the build in flight was found
# in the persistent cache, and how deep inside nested timed regions it is
_LOCAL = threading.local()
_LISTENER_LOCK = threading.Lock()
_listeners_registered = False
_annotation_cls: Any = None


def _attached_clock() -> "PhaseClock | None":
    clock = getattr(_LOCAL, "clock", None)
    if clock is not None and clock._thread != threading.get_ident():
        # the engine moved to another thread (start() after synchronous
        # ticks): this thread's events are no longer that loop's
        _LOCAL.clock = clock = None
    return clock


def _on_scalar(event: str, value: float, **kwargs: Any) -> None:
    # jax records a scalar when a timed region is ENTERED. Regions nest (a jit
    # traced inside an outer trace, an eager op compiled while tracing): the
    # outermost one's duration holds the others', which must not be charged twice
    if event in TIMED_EVENTS:
        depth = _LOCAL.depth = getattr(_LOCAL, "depth", 0) + 1
        clock = _attached_clock() if depth == 1 else None
        if clock is not None:
            clock.look()  # a build is long beside a chunk: know how the chip stood before it


def _on_event(event: str, **kwargs: Any) -> None:
    if event == CACHE_HIT_EVENT:
        _LOCAL.cache_hit = True


def _on_duration(event: str, duration: float, **kwargs: Any) -> None:
    if event not in TIMED_EVENTS:
        return
    depth = _LOCAL.depth = max(0, getattr(_LOCAL, "depth", 1) - 1)
    if event == BUILD_EVENT:
        loaded, _LOCAL.cache_hit = getattr(_LOCAL, "cache_hit", False), False
    clock = _attached_clock()
    if clock is None:
        return
    if not depth:
        clock._carve(duration)
    if event == BUILD_EVENT:
        clock._note_build(str(kwargs.get("fun_name", "?")), duration, loaded)


def _on_gc(phase: str, info: dict) -> None:
    # called on the thread whose allocation set the collection off
    if phase == "start":
        _LOCAL.gc_at = time.monotonic()
        return
    began, _LOCAL.gc_at = getattr(_LOCAL, "gc_at", None), None
    # inside a build being timed, that build's duration holds the collection
    if began is None or getattr(_LOCAL, "depth", 0):
        return
    clock = _attached_clock()
    if clock is not None:
        clock._carve(time.monotonic() - began, "gc")


def _register_listeners() -> None:
    """Once a process, whatever the number of engines."""
    global _listeners_registered
    with _LISTENER_LOCK:
        if _listeners_registered:
            return
        import jax.monitoring as monitoring  # noqa: PLC0415

        monitoring.register_scalar_listener(_on_scalar)
        monitoring.register_event_listener(_on_event)
        monitoring.register_event_duration_secs_listener(_on_duration)
        gc.callbacks.append(_on_gc)
        _listeners_registered = True


def _annotation(name: str, attrs: dict[str, Any]) -> Any:
    global _annotation_cls
    if _annotation_cls is None:
        from jax.profiler import TraceAnnotation  # noqa: PLC0415

        _annotation_cls = TraceAnnotation
    return _annotation_cls(name, **attrs)


class _Scope:
    """One phase entered: clock switch, trace annotation, tracer span."""

    __slots__ = ("_clock", "_phase", "_prev", "_name", "_attrs", "_note", "_span")

    def __init__(
        self, clock: "PhaseClock", phase: str, name: str, attrs: dict[str, Any], span: Any
    ) -> None:
        self._clock = clock
        self._phase = phase
        self._name = name
        self._attrs = attrs
        self._span = span

    def __enter__(self) -> Any:
        clock = self._clock
        self._prev = clock.phase
        clock.switch(self._phase)
        # opened after the switch's look, so the annotation carries its verdict
        attrs = {**self._attrs, "starved": 1} if clock.starved else self._attrs
        self._note = _annotation(self._name, attrs)
        self._note.__enter__()
        return self._span.__enter__() if self._span is not None else None

    def __exit__(self, exc_type: Any, exc: Any, tb: Any) -> None:
        if self._span is not None:
            self._span.__exit__(exc_type, exc, tb)
        self._note.__exit__(exc_type, exc, tb)
        self._clock.switch(self._prev)


class PhaseClock:
    """The phase accounting of one engine's driving thread."""

    def __init__(self, registry: Registry, tracer: Tracer = TRACER) -> None:
        self._tracer = tracer
        self._registry = registry
        self._m_loop_s = registry.counter(
            "serve_loop_seconds_total",
            "Seconds of the engine loop thread, by the one phase it was in",
            labelnames=("phase",),
        )
        self._m_starved_s = registry.counter(
            "serve_loop_starved_seconds_total",
            "Seconds of the engine loop thread in which the chip was seen to have "
            "no model program left to run, by the phase that held the loop",
            labelnames=("phase",),
        )
        self._m_unseen_s = registry.counter(
            "serve_loop_starved_unseen_seconds_total",
            "Seconds between a look that found the chip with a model program to run "
            "and the next, which found none: it finished somewhere in between",
        )
        self._m_compiles = registry.counter(
            "serve_compiles_total",
            "XLA backend compiles (persistent-cache misses) by loop phase",
            labelnames=("phase",),
        )
        self._m_compile_s = registry.histogram(
            "serve_compile_seconds",
            "Wall seconds of one XLA backend compile, by loop phase",
            buckets=DEFAULT_LATENCY_BUCKETS,
            labelnames=("phase",),
        )
        self._m_loads = registry.counter(
            "serve_program_loads_total",
            "Programs loaded from the persistent compile cache, by loop phase",
            labelnames=("phase",),
        )
        self.phase = "other"
        self.switched_at = time.monotonic()  # instant of the last switch
        self._pending: dict[str, float] = {}  # phase -> seconds not yet flushed
        self._carved = 0.0  # build seconds already charged inside the running phase
        # the starved clock: the newest model program's output, the bit, the
        # instant from which the running phase is charged as starved, the last
        # look that found the chip with work, and what is not yet flushed
        self._newest: Any = None
        self.starved = False
        self._starved_from = self.switched_at
        self._looked_at = self.switched_at
        self._carved_starved = 0.0
        self._pending_starved: dict[str, float] = {}
        self._pending_unseen = 0.0
        # everything published so far, for stats(): family -> phase -> seconds
        self._totals: dict[str, dict[str, float]] = {"loop_s": {}, "loop_starved_s": {}}
        self._unseen_total = 0.0
        self._thread: int | None = None
        # builds are read by other threads (stats(), a profiler capture)
        self._lock = threading.Lock()
        self._builds: deque[dict] = deque(maxlen=BUILDS_KEPT)
        _register_listeners()

    # -- the thread ----------------------------------------------------------

    def attach(self) -> None:
        """Make the calling thread this clock's thread, and this clock the one
        that the thread's program builds are charged to (engines ticked in
        turn on one thread each get their own). Time before the first attach,
        and between two threads, is nobody's: the clock starts anew."""
        _LOCAL.clock = self
        ident = threading.get_ident()
        if self._thread == ident:
            return
        self._thread = ident
        self.phase = "other"
        self._carved = self._carved_starved = 0.0
        self.switched_at = self._starved_from = self._looked_at = time.monotonic()

    # -- phases --------------------------------------------------------------

    def switch(self, phase: str) -> None:
        """Charge the time since the last switch to the phase being left,
        and a second time as starved where the chip had no model program."""
        now = time.monotonic()
        elapsed = now - self.switched_at - self._carved
        if elapsed > 0.0:
            pending = self._pending
            pending[self.phase] = pending.get(self.phase, 0.0) + elapsed
        if self.starved:
            self._charge_starved(now)
        else:
            self._look(now, self.phase in BLOCKED_ON_DEVICE)
        self._carved = 0.0
        self.phase = phase
        self.switched_at = self._starved_from = now

    def enqueued(self, output: Any) -> None:
        """The engine enqueued a model program; ``output`` is an array it
        returns that no later program takes by donation. A starved stretch
        ends here, inside the running phase."""
        now = time.monotonic()
        if self.starved:
            self._charge_starved(now)
        else:
            self._look(now, False)  # the predecessor may have run out since the last look
        self.starved = False
        self._newest = output
        self._looked_at = now

    def _look(self, now: float, blocked: bool) -> None:
        """Ask, without blocking, whether the newest announced output is
        there. If so the chip is starved from now on; it finished at an
        unknown instant since the last look (unless the loop was ``blocked``
        reading it, and came back when it did)."""
        newest = self._newest
        if newest is None:
            return
        try:
            ready = newest.is_ready()
        except RuntimeError:  # deleted by a recovery: nothing of it is left to run
            ready = True
        if not ready:
            self._looked_at = now
            return
        if not blocked:
            self._pending_unseen += now - self._looked_at
        self.starved = True
        self._starved_from = now

    def look(self) -> None:
        """A look inside a phase, where one is long beside what the chip has
        queued (before and after a program build or a collection)."""
        if not self.starved:
            self._look(time.monotonic(), self.phase in BLOCKED_ON_DEVICE)

    def _charge_starved(self, now: float) -> None:
        seconds = now - self._starved_from - self._carved_starved
        if seconds > 0.0:
            pending = self._pending_starved
            pending[self.phase] = pending.get(self.phase, 0.0) + seconds
        self._carved_starved = 0.0
        self._starved_from = now

    def enter(self, phase: str) -> _Scope:
        """A phase that no tracer span names: annotated ``serve.<phase>``."""
        return _Scope(self, phase, f"serve.{phase}", {}, None)

    def span(
        self, name: str, phase: str, *, context: TraceContext | None = None, **attrs: Any
    ) -> _Scope:
        """A phase under a span's name: the trace annotation carries ``attrs``
        and, with ``PRIME_TRACE`` set, so does the ``TRACER`` span (joined to
        ``context``, a request's distributed trace, when given)."""
        tracer = self._tracer
        span = tracer.span(name, context=context, **attrs) if tracer.enabled else None
        return _Scope(self, phase, name, attrs, span)

    def flush(self) -> dict[str, float]:
        """Publish what accumulated since the last flush, the running phase
        up to now included; returns the seconds published, by phase. The
        starved seconds go out under the same hold of the registry's lock, so
        a snapshot has every family as of one instant."""
        self.switch(self.phase)
        flushed, self._pending = self._pending, {}
        starved, self._pending_starved = self._pending_starved, {}
        unseen, self._pending_unseen = self._pending_unseen, 0.0
        with self._registry.hold():
            for key, counter, seconds_by in (
                ("loop_s", self._m_loop_s, flushed), ("loop_starved_s", self._m_starved_s, starved),
            ):
                totals = self._totals[key]
                for phase, seconds in seconds_by.items():
                    counter.inc(seconds, phase=phase)
                    totals[phase] = totals.get(phase, 0.0) + seconds
            self._m_unseen_s.inc(unseen)
            self._unseen_total += unseen
        return flushed

    def totals(self) -> dict[str, Any]:
        """Everything published so far, as stats() shows it: the loop's
        seconds and its starved seconds by phase, and the unseen seconds."""
        out: dict[str, Any] = {
            key: {phase: round(s, 6) for phase, s in by.items()} for key, by in self._totals.items()
        }
        out["loop_starved_unseen_s"] = round(self._unseen_total, 6)
        return out

    # -- program builds and collections (called by the process-wide hooks) ---

    def _carve(self, seconds: float, into: str = "program_build") -> None:
        self._pending[into] = self._pending.get(into, 0.0) + seconds
        self._carved += seconds
        if self.starved:  # since before the build began: a look is this thread's
            starved = self._pending_starved
            starved[into] = starved.get(into, 0.0) + seconds
            self._carved_starved += seconds
        else:
            self.look()  # the chip may have run out during it

    def _note_build(self, name: str, seconds: float, loaded: bool) -> None:
        phase = self.phase
        if loaded:
            self._m_loads.inc(phase=phase)
        else:
            self._m_compiles.inc(phase=phase)
            self._m_compile_s.observe(seconds, phase=phase)
        with self._lock:
            self._builds.append({
                "name": name, "seconds": round(seconds, 6),
                "kind": "load" if loaded else "compile", "phase": phase,
                "start_s": time.monotonic() - seconds,
            })

    def builds(self) -> list[dict]:
        """The last builds (at most 64), oldest first: name, seconds, kind
        (``compile`` or ``load``), phase, and the monotonic instant it began."""
        with self._lock:
            return list(self._builds)

    def build_totals(self) -> dict[str, float]:
        """Over every phase: backend compiles, their seconds, cache loads."""
        return {"total": int(self._m_compiles.total()),
                "seconds": round(self._m_compile_s.total(), 6),
                "loads": int(self._m_loads.total())}
