"""`prime bench autotune` — sweep candidate block configs per pallas kernel
and persist the winners as this device kind's config artifact.

The campaign's loop: the kernels resolve their tiling through
ops/kernel_configs.py (env > tuned > default); this harness produces the
"tuned" tier. For each kernel it times every candidate on representative
shapes and writes the fastest to ``<config dir>/<device-kind>.json`` —
keyed by ``jax.devices()[0].device_kind``, so the artifact a v5e sweep
persists never feeds a v5p process.

Two sweep mechanics, dictated by each kernel's surface:

- ``lora_mm`` takes its block as an argument — the candidate is passed
  explicitly.
- the flash kernels resolve blocks inside their traces — candidates are
  applied through the promoted ``PRIME_TPU_BLOCK_*`` env overrides with the
  kernel's jit cache cleared per candidate, exercising exactly the
  resolution path production dispatches use.

Timing is best-of-``repeats`` wall time around ``block_until_ready`` after
a warmup call that eats the compile. ``dry_run`` shrinks shapes, runs the
kernels in interpret mode, and trims the candidate lists — CI uses it to
prove the sweep → artifact → resolution round-trip on CPU, not to produce
meaningful timings (the artifact it writes should go to a throwaway
directory, never the committed registry).

Every swept kernel emits a ``serve.autotune`` span (rows in
docs/observability.md) so a fleet's tuning runs leave trace evidence.
"""

from __future__ import annotations

import math
import os
import time
from typing import Any, Callable

from prime_tpu.obs.trace import TRACER

# Candidate grids. Order matters only for tie-breaks (first wins on equal
# time); defaults lead so a tie keeps the shipped behavior.
CANDIDATES: dict[str, list[dict[str, int]]] = {
    "flash_prefill": [
        {"block_q": q, "block_k": k}
        for q in (128, 64, 256)
        for k in (128, 64, 256)
    ],
    "flash_decode": [{"block_c": c} for c in (128, 256, 512)],
    "flash_decode_int8": [{"block_c": c} for c in (128, 256, 512)],
    "lora_mm": [{"block_out": o} for o in (256, 128, 512)],
}


def _dry_candidates() -> dict[str, list[dict[str, int]]]:
    # two candidates per kernel: enough to exercise the comparison and the
    # winner selection without CI paying a 9-point interpret-mode grid
    return {name: grid[:2] for name, grid in CANDIDATES.items()}


def _time_call(fn: Callable[[], Any], repeats: int) -> float:
    """Best-of-repeats microseconds; the first (untimed) call eats compile."""
    fn()
    best = math.inf
    for _ in range(repeats):
        t0 = time.perf_counter()
        out = fn()
        try:
            out.block_until_ready()
        except AttributeError:
            pass
        best = min(best, time.perf_counter() - t0)
    return best * 1e6


def _with_env(overrides: dict[str, int], fn: Callable[[], float]) -> float:
    """Run ``fn`` with PRIME_TPU_BLOCK_* pinned (and restored after)."""
    saved = {k: os.environ.get(k) for k in overrides}
    os.environ.update({k: str(v) for k, v in overrides.items()})
    try:
        return fn()
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


_ENV_KEYS = {"block_q": "PRIME_TPU_BLOCK_Q", "block_k": "PRIME_TPU_BLOCK_K",
             "block_c": "PRIME_TPU_BLOCK_C"}


def _sweep_flash_prefill(dry_run: bool, repeats: int, interpret: bool):
    import jax
    import jax.numpy as jnp

    from prime_tpu.ops.pallas_attention import flash_attention_causal

    batch, heads, seq, dim = (1, 2, 256, 128) if dry_run else (1, 8, 2048, 128)
    key = jax.random.PRNGKey(0)
    q, k, v = (
        jax.random.normal(kk, (batch, heads, seq, dim), dtype=jnp.float32)
        for kk in jax.random.split(key, 3)
    )

    def run(cand: dict[str, int]) -> float:
        env = {_ENV_KEYS[p]: val for p, val in cand.items()}

        def call() -> float:
            flash_attention_causal.clear_cache()
            return _time_call(
                lambda: flash_attention_causal(q, k, v, interpret=interpret),
                repeats,
            )

        return _with_env(env, call)

    return run


def _sweep_flash_decode(dry_run: bool, repeats: int, interpret: bool, int8: bool):
    import jax
    import jax.numpy as jnp

    from prime_tpu.ops.pallas_attention import flash_decode

    batch, heads, kv_heads, dim = (2, 2, 1, 128) if dry_run else (8, 8, 1, 128)
    capacity = 512 if dry_run else 2048
    key = jax.random.PRNGKey(1)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (batch, heads, 1, dim), dtype=jnp.float32)
    k = jax.random.normal(kk, (batch, kv_heads, dim, capacity), dtype=jnp.float32)
    v = jax.random.normal(kv, (batch, kv_heads, dim, capacity), dtype=jnp.float32)
    lengths = jnp.full((batch,), capacity, dtype=jnp.int32)
    k_scale = v_scale = None
    if int8:
        from prime_tpu.models.llama import quantize_kv

        (k, k_scale), (v, v_scale) = quantize_kv(k), quantize_kv(v)

    def run(cand: dict[str, int]) -> float:
        env = {_ENV_KEYS[p]: val for p, val in cand.items()}

        def call() -> float:
            flash_decode.clear_cache()
            return _time_call(
                lambda: flash_decode(
                    q, k, v, lengths, k_scale=k_scale, v_scale=v_scale,
                    interpret=interpret,
                ),
                repeats,
            )

        return _with_env(env, call)

    return run


def _sweep_lora_mm(dry_run: bool, repeats: int, interpret: bool):
    import jax
    import jax.numpy as jnp

    from prime_tpu.ops.pallas_lora import fused_lora_matmul

    batch, seq, d_in, rank, d_out, bank = (
        (2, 4, 128, 8, 256, 2) if dry_run else (8, 1, 2048, 16, 2048, 4)
    )
    key = jax.random.PRNGKey(3)
    kx, kw, ka, kb = jax.random.split(key, 4)
    x = jax.random.normal(kx, (batch, seq, d_in), dtype=jnp.float32)
    w = jax.random.normal(kw, (d_in, d_out), dtype=jnp.float32)
    a = jax.random.normal(ka, (bank, d_in, rank), dtype=jnp.float32)
    b = jax.random.normal(kb, (bank, rank, d_out), dtype=jnp.float32)
    ids = jnp.arange(batch, dtype=jnp.int32) % bank

    def run(cand: dict[str, int]) -> float:
        return _time_call(
            lambda: fused_lora_matmul(
                x, w, a, b, ids, block_out=cand["block_out"],
                interpret=interpret,
            ),
            repeats,
        )

    return run


def run_autotune(
    kernels: list[str] | None = None,
    dry_run: bool = False,
    repeats: int = 3,
    log: Callable[[str], None] | None = None,
) -> dict[str, dict[str, Any]]:
    """Sweep each requested kernel's candidate grid and return the winners
    as a kernel_configs.save_artifact-ready table (winning params plus a
    ``us`` timing record). Candidates that fail to compile/run on this
    backend are skipped; a kernel whose every candidate fails is omitted."""
    from prime_tpu.ops.attention import _pallas_interpret

    interpret = dry_run or _pallas_interpret()
    grids = _dry_candidates() if dry_run else CANDIDATES
    if kernels:
        unknown = sorted(set(kernels) - set(grids))
        if unknown:
            raise ValueError(f"unknown kernel(s): {', '.join(unknown)}")
        grids = {name: grids[name] for name in kernels}
    repeats = 1 if dry_run else max(1, repeats)
    builders: dict[str, Callable[..., Callable[[dict[str, int]], float]]] = {
        "flash_prefill": lambda: _sweep_flash_prefill(dry_run, repeats, interpret),
        "flash_decode": lambda: _sweep_flash_decode(dry_run, repeats, interpret, False),
        "flash_decode_int8": lambda: _sweep_flash_decode(dry_run, repeats, interpret, True),
        "lora_mm": lambda: _sweep_lora_mm(dry_run, repeats, interpret),
    }
    winners: dict[str, dict[str, Any]] = {}
    for name, grid in grids.items():
        with TRACER.span(
            "serve.autotune", kernel=name, candidates=len(grid),
            dry_run=dry_run,
        ):
            runner = builders[name]()
            best_us, best = math.inf, None
            for cand in grid:
                try:
                    us = runner(cand)
                except Exception as e:  # noqa: BLE001 — candidate doesn't fit
                    if log:
                        log(f"  {name} {cand}: skipped ({e})")
                    continue
                if log:
                    log(f"  {name} {cand}: {us:.1f}us")
                if us < best_us:
                    best_us, best = us, cand
            if best is not None:
                winners[name] = {**best, "us": round(best_us, 1)}
                if log:
                    log(f"{name}: winner {best} ({best_us:.1f}us)")
            elif log:
                log(f"{name}: no viable candidate on this backend")
    return winners
