"""Scenario DSL: seeded, deterministic multi-tenant traffic schedules.

A :class:`Scenario` is a declarative description of a traffic shape — a
name, a seed, and a list of :class:`Phase` blocks (shared-prefix chat
bursts, long-context outliers, cancel storms, 429 storms, mixed-adapter
tenants). :func:`build_schedule` expands it into a flat, fully materialized
list of :class:`PlannedRequest` — every prompt token, tenant, arrival
offset, and cancel point pinned — using nothing but ``random.Random(seed)``,
so the same scenario yields a byte-identical schedule on every machine and
every run (:func:`schedule_digest` is the test anchor for that claim).

The schedule is backend-agnostic: prompts are token-id tuples, and the
:mod:`prime_tpu.loadgen.backends` adapters turn them into direct engine
submissions or OpenAI-style HTTP bodies (via the numeric tokenizer that
round-trips ids through text). Determinism is a property of the SCHEDULE,
not the run — wall-clock arrival jitter, server-side batching, and thread
interleaving still vary, which is exactly why the SLO report reads the obs
registry instead of client stopwatches (docs/benchmarking.md).
"""

from __future__ import annotations

import hashlib
import json
import random
from dataclasses import asdict, dataclass

from prime_tpu.utils.env import env_flag, env_int

# Matches tiny-test's vocab comfortably; scenario builders clamp into
# [RESERVED_IDS, vocab) so pad/BOS/EOS ids never appear mid-prompt.
DEFAULT_VOCAB = 1000
RESERVED_IDS = 3

PHASE_KINDS = (
    "chat_burst",      # shared-prefix multi-tenant chat wave
    "longctx",         # rare long-context outlier prompts
    "cancel_storm",    # clients that abandon mid-decode
    "rate_storm",      # oversubscription wave aimed at the 429 admission gate
    "mixed",           # per-tenant adapters riding the OpenAI `model` field
    "spec_friendly",   # repetitive/templated prompts where n-gram drafts accept
)


def loadgen_seed_default() -> int:
    """The ``PRIME_LOADGEN_SEED`` knob: default seed for scenario builders
    (0 when unset) — CI and the bench set it to pin or vary a round."""
    return env_int("PRIME_LOADGEN_SEED", 0)


def bench_smoke_scale() -> bool:
    """The ``PRIME_BENCH_SMOKE`` knob as loadgen sees it: builders shrink
    their request counts/lengths to CPU-minutes scale when it is set."""
    return env_flag("PRIME_BENCH_SMOKE", False)


@dataclass(frozen=True)
class PlannedRequest:
    """One fully materialized request in a schedule. ``arrival_s`` is the
    offset from run start in *schedule time* (the runner may compress it
    with ``time_scale``); ``cancel_after_s`` is the client-abandon point in
    the same clock, ``None`` for requests that run to completion."""

    index: int
    tenant: str
    arrival_s: float
    prompt_ids: tuple[int, ...]
    max_new_tokens: int
    cancel_after_s: float | None = None
    adapter: str | None = None

    def to_dict(self) -> dict:
        out = asdict(self)
        out["prompt_ids"] = list(self.prompt_ids)
        return out


@dataclass(frozen=True)
class Phase:
    """One traffic block. ``shared_prefix`` tokens are drawn once per tenant
    and shared by every request of that tenant in the phase — the shape the
    radix prefix cache and affinity router exist for. ``spread_s`` spreads
    arrivals uniformly over the window starting at ``start_s`` (0 = one
    simultaneous burst)."""

    kind: str
    n: int
    start_s: float = 0.0
    spread_s: float = 0.0
    tenants: int = 1
    shared_prefix: int = 0
    prompt_tokens: int = 32
    max_new_tokens: int = 8
    cancel_frac: float = 0.0
    cancel_after_s: float = 0.1
    adapters: tuple[str, ...] = ()
    # > 0: each request's tail tiles a freshly drawn cycle of this many
    # tokens instead of i.i.d. draws — the templated/repetitive shape where
    # greedy continuations loop and prompt-lookup drafts accept (the
    # spec_friendly phase kind's default; any kind may opt in)
    cycle_tokens: int = 0

    def __post_init__(self) -> None:
        if self.kind not in PHASE_KINDS:
            raise ValueError(f"unknown phase kind {self.kind!r}; one of {PHASE_KINDS}")
        if self.n <= 0:
            raise ValueError("phase n must be positive")
        if self.shared_prefix >= self.prompt_tokens:
            raise ValueError("shared_prefix must leave room for a unique tail")
        if self.cycle_tokens < 0:
            raise ValueError("cycle_tokens must be non-negative")
        if self.cycle_tokens >= self.prompt_tokens:
            raise ValueError("cycle_tokens must be shorter than the prompt")


@dataclass(frozen=True)
class Scenario:
    name: str
    seed: int
    phases: tuple[Phase, ...]
    vocab: int = DEFAULT_VOCAB
    description: str = ""

    def __post_init__(self) -> None:
        if not self.phases:
            raise ValueError("a scenario needs at least one phase")
        if self.vocab <= RESERVED_IDS + 1:
            raise ValueError("vocab too small for prompt synthesis")


def _draw_tokens(rng: random.Random, n: int, vocab: int) -> tuple[int, ...]:
    return tuple(rng.randrange(RESERVED_IDS, vocab) for _ in range(n))


def build_schedule(
    scenario: Scenario, vocab: int | None = None
) -> list[PlannedRequest]:
    """Expand a scenario into its deterministic request schedule. All
    randomness flows from ONE ``random.Random(seed)`` consumed in a fixed
    order (phase by phase, request by request), so equality of (scenario,
    vocab) implies equality of every schedule byte. ``vocab`` overrides the
    scenario's vocab (e.g. clamp to a real model's vocab_size) — it is part
    of the determinism key, not ambient state.

    Prompts lead with token 1 (a stable BOS stand-in) so schedules never
    start on the pad id; per-tenant shared preambles are drawn once per
    (phase, tenant) and shared verbatim across that tenant's requests."""
    vocab = scenario.vocab if vocab is None else vocab
    if vocab <= RESERVED_IDS + 1:
        raise ValueError("vocab too small for prompt synthesis")
    rng = random.Random(scenario.seed)
    out: list[PlannedRequest] = []
    index = 0
    for phase in scenario.phases:
        preambles = {
            t: (1,) + _draw_tokens(rng, max(0, phase.shared_prefix - 1), vocab)
            for t in range(phase.tenants)
        }
        for i in range(phase.n):
            tenant_slot = i % phase.tenants
            tenant = f"{phase.kind}-t{tenant_slot}"
            preamble = preambles[tenant_slot] if phase.shared_prefix else (1,)
            need = phase.prompt_tokens - len(preamble)
            if phase.cycle_tokens > 0:
                # repetitive tail: one short cycle tiled to length, so the
                # sequence's own history is full of repeated bigrams
                cycle = _draw_tokens(rng, phase.cycle_tokens, vocab)
                tail = (cycle * -(-need // len(cycle)))[:need]
            else:
                tail = _draw_tokens(rng, need, vocab)
            arrival = phase.start_s + (
                rng.uniform(0.0, phase.spread_s) if phase.spread_s > 0 else 0.0
            )
            cancel = None
            if phase.cancel_frac > 0 and rng.random() < phase.cancel_frac:
                cancel = round(arrival + phase.cancel_after_s, 6)
            adapter = None
            if phase.adapters:
                adapter = phase.adapters[tenant_slot % len(phase.adapters)]
            out.append(
                PlannedRequest(
                    index=index,
                    tenant=tenant,
                    arrival_s=round(arrival, 6),
                    prompt_ids=preamble + tail,
                    max_new_tokens=phase.max_new_tokens,
                    cancel_after_s=cancel,
                    adapter=adapter,
                )
            )
            index += 1
    # stable order: arrival time, then submission index as the tie-break —
    # a simultaneous burst keeps its within-phase order
    out.sort(key=lambda r: (r.arrival_s, r.index))
    return out


def schedule_digest(schedule: list[PlannedRequest]) -> str:
    """SHA-256 over the canonical JSON of a schedule — the determinism
    anchor: two runs agree on the digest iff they agree on every prompt
    token, tenant, arrival offset, and cancel point."""
    canonical = json.dumps(
        [r.to_dict() for r in schedule], sort_keys=True, separators=(",", ":")
    )
    return hashlib.sha256(canonical.encode()).hexdigest()


def schedule_from_prompts(
    name: str,
    prompts: list[list[int]],
    max_new_tokens: int,
    *,
    tenant: str = "bench",
) -> list[PlannedRequest]:
    """Wrap an explicit prompt list as a zero-offset burst schedule: a
    hand-picked prompt set (tuned to exercise specific cache shapes) rides
    the loadgen runner/report machinery like every other scenario."""
    return [
        PlannedRequest(
            index=i,
            tenant=tenant,
            arrival_s=0.0,
            prompt_ids=tuple(ids),
            max_new_tokens=max_new_tokens,
        )
        for i, ids in enumerate(prompts)
    ]


# ---- builtin scenarios -------------------------------------------------------

def _scale(small: int, large: int) -> int:
    return small if bench_smoke_scale() else large


def chat_burst(seed: int | None = None, **overrides) -> Scenario:
    """Shared-prefix multi-tenant chat wave: every tenant's requests open
    with that tenant's system preamble and diverge after it."""
    seed = loadgen_seed_default() if seed is None else seed
    phase = dict(
        kind="chat_burst", n=_scale(6, 16), tenants=3, shared_prefix=16,
        prompt_tokens=_scale(24, 96), max_new_tokens=_scale(6, 32),
        spread_s=0.2,
    )
    phase.update(overrides)
    return Scenario(
        "chat_burst", seed, (Phase(**phase),),
        description="shared-prefix multi-tenant chat wave",
    )


def longctx_outliers(seed: int | None = None, **overrides) -> Scenario:
    """Mostly short chat traffic with rare long-context outliers mixed in —
    the head-of-line-blocking shape that punishes naive admission."""
    seed = loadgen_seed_default() if seed is None else seed
    short = dict(
        kind="chat_burst", n=_scale(5, 12), tenants=2, shared_prefix=8,
        prompt_tokens=_scale(20, 64), max_new_tokens=_scale(4, 16),
        spread_s=0.3,
    )
    longp = dict(
        kind="longctx", n=_scale(2, 3), prompt_tokens=_scale(72, 768),
        max_new_tokens=_scale(4, 16), start_s=0.05, spread_s=0.2,
    )
    longp.update(overrides)
    return Scenario(
        "longctx_outliers", seed, (Phase(**short), Phase(**longp)),
        description="short chat traffic with long-context outliers",
    )


def cancel_storm(seed: int | None = None, **overrides) -> Scenario:
    """A wave of clients that abandon mid-decode: exercises cancel sweeps,
    slot retirement, and wasted-decode accounting."""
    seed = loadgen_seed_default() if seed is None else seed
    phase = dict(
        kind="cancel_storm", n=_scale(6, 16), tenants=2, shared_prefix=8,
        prompt_tokens=_scale(20, 48), max_new_tokens=_scale(8, 64),
        cancel_frac=0.5, cancel_after_s=0.05, spread_s=0.1,
    )
    phase.update(overrides)
    return Scenario(
        "cancel_storm", seed, (Phase(**phase),),
        description="clients abandoning requests mid-decode",
    )


def rate_storm(seed: int | None = None, **overrides) -> Scenario:
    """An oversubscription burst aimed at the admission gate: more
    simultaneous arrivals than the queue bound, so the 429 path (and the
    client's Retry-After handling) actually fires."""
    seed = loadgen_seed_default() if seed is None else seed
    phase = dict(
        kind="rate_storm", n=_scale(10, 48), tenants=4, shared_prefix=8,
        prompt_tokens=_scale(16, 48), max_new_tokens=_scale(4, 16),
    )
    phase.update(overrides)
    return Scenario(
        "rate_storm", seed, (Phase(**phase),),
        description="simultaneous burst past the admission gate (429 storm)",
    )


def mixed_tenants(seed: int | None = None, **overrides) -> Scenario:
    """Tenants pinned to different adapters via the OpenAI ``model`` field —
    the multi-model routing shape (ROADMAP Open item 4). Backends without a
    model registry serve them all from the base model; the schedule still
    pins which request WOULD go where."""
    seed = loadgen_seed_default() if seed is None else seed
    phase = dict(
        kind="mixed", n=_scale(6, 24), tenants=3, shared_prefix=8,
        prompt_tokens=_scale(20, 64), max_new_tokens=_scale(4, 16),
        adapters=("base", "adapter-a", "adapter-b"), spread_s=0.2,
    )
    phase.update(overrides)
    return Scenario(
        "mixed_tenants", seed, (Phase(**phase),),
        description="per-tenant adapters behind one endpoint",
    )


def spec_friendly(seed: int | None = None, **overrides) -> Scenario:
    """Repetitive/templated completions — the favorable regime for
    prompt-lookup speculative decoding: each prompt tiles a short token
    cycle, so greedy continuations settle into loops the n-gram drafter
    predicts and verify windows accept several tokens per dispatch. Run it
    spec-on vs spec-off (the loadgen smoke does) to
    publish the speedup and accept ratio."""
    seed = loadgen_seed_default() if seed is None else seed
    phase = dict(
        kind="spec_friendly", n=_scale(4, 12), tenants=2,
        cycle_tokens=8, prompt_tokens=_scale(49, 97),
        max_new_tokens=_scale(24, 64), spread_s=0.1,
    )
    phase.update(overrides)
    return Scenario(
        "spec_friendly", seed, (Phase(**phase),),
        description="repetitive/templated completions where n-gram drafts accept",
    )


def disagg(seed: int | None = None, **overrides) -> Scenario:
    """Long-prompt-heavy traffic — the phase-split shape (ROADMAP Open item
    4): a continuous wave of long-prefill requests with real decode tails.
    On a colocated fleet every admission's long prefill blocks the engine
    loop, stalling the decode ticks of every slot sharing the replica; a
    disaggregated fleet prefills on one replica (whose slots free at
    admission, so waves batch) and decodes on another (whose loop only ever
    pays the assemble + unaligned-tail suffix per migrated request).
    A short per-tenant preamble keeps the prefix-affinity/cache machinery
    in play (each admission still prefills ≥ 95% of its prompt cold, so
    the interference the scenario exists to create survives)."""
    seed = loadgen_seed_default() if seed is None else seed
    phase = dict(
        kind="longctx", n=_scale(8, 16), tenants=2, shared_prefix=16,
        prompt_tokens=_scale(192, 384), max_new_tokens=16,
        spread_s=_scale(1, 2) * 1.0,
    )
    phase.update(overrides)
    return Scenario(
        "disagg", seed, (Phase(**phase),),
        description="long-prompt-heavy wave for the phase-split fleet",
    )


def longprefix(seed: int | None = None, **overrides) -> Scenario:
    """Long-shared-prefix traffic — the paged-gather seeding shape
    (docs/kernels.md "paged_gather"): each tenant's requests open with a
    LONG common preamble (several radix blocks) and diverge only in a short
    tail, so after the first admission per tenant every request is
    dominated by hit seeding, not cold prefill. The arrival spread splits
    the schedule into an effective seed wave (first request per tenant
    stores the preamble) and a hit wave (everything after reuses it) —
    run it paged vs copy (the loadgen smoke's longprefix section) to
    publish the seeding-path comparison."""
    seed = loadgen_seed_default() if seed is None else seed
    phase = dict(
        kind="chat_burst", n=_scale(8, 24), tenants=2,
        shared_prefix=_scale(48, 192), prompt_tokens=_scale(56, 224),
        max_new_tokens=_scale(4, 16), spread_s=0.6,
    )
    phase.update(overrides)
    return Scenario(
        "longprefix", seed, (Phase(**phase),),
        description="long shared prefixes where hit seeding dominates",
    )


def smoke(seed: int | None = None) -> Scenario:
    """The CI scenario: one tiny composite touching every phase kind in
    seconds on CPU — shared-prefix burst, one long outlier, a couple of
    cancels, and a small oversubscription wave."""
    seed = loadgen_seed_default() if seed is None else seed
    return Scenario(
        "smoke",
        seed,
        (
            # 16-token shared preambles span one MIN_BUCKET block, so the
            # radix cache can actually hit; the spread staggers admissions
            # past the first store
            Phase(kind="chat_burst", n=6, tenants=2, shared_prefix=16,
                  prompt_tokens=28, max_new_tokens=6, spread_s=0.3),
            Phase(kind="longctx", n=1, prompt_tokens=48, max_new_tokens=4,
                  start_s=0.02),
            Phase(kind="cancel_storm", n=2, prompt_tokens=16, max_new_tokens=24,
                  cancel_frac=1.0, cancel_after_s=0.4, start_s=0.04),
            Phase(kind="rate_storm", n=4, prompt_tokens=16, max_new_tokens=4,
                  start_s=0.06),
        ),
        description="tiny composite of every phase kind (CI smoke)",
    )


SCENARIOS = {
    "chat_burst": chat_burst,
    "longctx_outliers": longctx_outliers,
    "cancel_storm": cancel_storm,
    "rate_storm": rate_storm,
    "mixed_tenants": mixed_tenants,
    "spec_friendly": spec_friendly,
    "disagg": disagg,
    "longprefix": longprefix,
    "smoke": smoke,
}
