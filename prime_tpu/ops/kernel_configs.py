"""Per-device-kind kernel block-size registry (the autotune campaign's spine).

The pallas kernels used to hardcode their tiling (``BLOCK_Q = BLOCK_K =
BLOCK_C = 128``) — right for the v5e the numbers were measured on, wrong in
general: MXU shape, VMEM size, and HBM bandwidth all move across TPU
generations, and the PAPERS survey's point that block sizes must be re-tuned
per topology is exactly the failure mode a hardcoded constant bakes in.

This module is the ONE resolution point every kernel call site goes through:

    env/flag override  >  tuned per-device-kind artifact  >  built-in default

- **env**: ``PRIME_TPU_BLOCK_Q/K/C`` (read via the utils/env helpers, rows
  in the architecture.md knobs table) pin a value for the whole process —
  the operator escape hatch, and how a sweep times candidates.
- **tuned**: ``prime bench autotune`` times candidates on the local device
  and persists winners to ``<config dir>/<device-kind>.json`` (versioned
  schema below). The artifact is keyed by ``jax.devices()[0].device_kind``
  so a v5e artifact never feeds a v5p process; an artifact for a different
  schema or device kind is ignored, not half-applied.
- **default**: the measured-on-v5e constants the kernels shipped with.

Call sites treat the resolved value as a *preference*, not a command: each
kernel keeps its own divisibility/eligibility fallbacks (e.g. flash_decode
drops to the largest block dividing the capacity), so a tuned or overridden
value that doesn't fit a shape degrades to the old behavior instead of
failing the dispatch.

``source()`` reports which tier won for observability: the serve engine
publishes it as the ``serve_kernel_config_source`` gauge so a fleet
operator can see at a glance whether a replica is running tuned configs.
"""

from __future__ import annotations

import json
import os
import warnings
from typing import Any

from prime_tpu.utils.env import env_int, env_str

__all__ = [
    "DEFAULTS",
    "SCHEMA_VERSION",
    "artifact_path",
    "device_kind",
    "invalidate_cache",
    "load_tuned",
    "resolve",
    "save_artifact",
    "source",
]

SCHEMA_VERSION = 1

# Built-in defaults: the values the kernels hardcoded before the registry
# existed (docs/kernels.md "Kernel campaign & autotune"). flash-decode's 0
# means "no preference": its block comes from the cache's shape
# (ops/pallas_attention.py::decode_tiling, every kv head of a row and the
# widest block that fits VMEM): at 128 slots and one head a step a call is
# 1024 grid steps at the served shapes, and the ledger's PR 26 traces show it
# bound by those steps at a tenth of the v5e's bandwidth.
DEFAULTS: dict[str, dict[str, int]] = {
    "flash_prefill": {"block_q": 128, "block_k": 128},
    "flash_decode": {"block_c": 0},
    "flash_decode_int8": {"block_c": 0},
    "lora_mm": {"block_out": 256},
    "int4_matmul": {"block_out": 512},
}

# The promoted BLOCK_Q/BLOCK_K/BLOCK_C module constants: a process-wide env
# override beats any tuned artifact (the operator knob, and the lever the
# autotune sweep itself uses to time candidates out-of-process).
_ENV_OVERRIDES: dict[tuple[str, str], str] = {
    ("flash_prefill", "block_q"): "PRIME_TPU_BLOCK_Q",
    ("flash_prefill", "block_k"): "PRIME_TPU_BLOCK_K",
    ("flash_decode", "block_c"): "PRIME_TPU_BLOCK_C",
    ("flash_decode_int8", "block_c"): "PRIME_TPU_BLOCK_C",
}

_SENTINEL = -1  # env_int default marking "knob unset"

# artifact cache: {(dir, kind): kernels dict or None}; resolve() is on the
# dispatch path of every kernel call, so the JSON read happens once
_cache: dict[tuple[str, str], dict[str, dict[str, int]] | None] = {}


def config_dir() -> str:
    """Directory holding tuned artifacts: PRIME_TPU_KERNEL_CONFIG_DIR, or
    the in-package ``kernel_configs/`` directory (committed artifacts ship
    with the wheel; a read-only install still resolves)."""
    configured = env_str("PRIME_TPU_KERNEL_CONFIG_DIR", "")
    if configured:
        return configured
    return os.path.join(os.path.dirname(__file__), "kernel_configs")


def device_kind() -> str:
    """``jax.devices()[0].device_kind`` slugged for a filename ("TPU v5e" ->
    "tpu-v5e"). Falls back to the platform name when the runtime has no
    device kind (interpret-mode CPU runs still get a stable key)."""
    import jax

    try:
        kind = jax.devices()[0].device_kind
    except Exception:  # pragma: no cover — no devices at all
        kind = jax.default_backend()
    slug = "".join(c if c.isalnum() else "-" for c in str(kind).lower())
    return slug.strip("-") or "unknown"


def artifact_path(kind: str | None = None, directory: str | None = None) -> str:
    return os.path.join(
        directory or config_dir(), f"{kind or device_kind()}.json"
    )


def load_tuned(kind: str | None = None) -> dict[str, dict[str, int]] | None:
    """The tuned kernels table for this device kind, or None. Malformed or
    mismatched artifacts (wrong schema/device kind) warn once and resolve as
    absent — a stale artifact must degrade to defaults, not take down the
    process at first dispatch."""
    kind = kind or device_kind()
    key = (config_dir(), kind)
    if key in _cache:
        return _cache[key]
    path = artifact_path(kind)
    kernels: dict[str, dict[str, int]] | None = None
    if os.path.exists(path):
        try:
            with open(path) as f:
                data = json.load(f)
            if data.get("schema") != SCHEMA_VERSION:
                raise ValueError(f"schema {data.get('schema')!r} != {SCHEMA_VERSION}")
            if data.get("device_kind") != kind:
                raise ValueError(
                    f"device_kind {data.get('device_kind')!r} != {kind!r}"
                )
            raw = data.get("kernels")
            if not isinstance(raw, dict):
                raise ValueError("kernels table missing")
            kernels = {
                name: {p: int(v) for p, v in entry.items() if isinstance(v, (int, float)) and p != "us"}
                for name, entry in raw.items()
                if isinstance(entry, dict)
            }
        except (OSError, ValueError, TypeError) as e:
            warnings.warn(
                f"ignoring kernel config artifact {path}: {e}", stacklevel=2
            )
            kernels = None
    _cache[key] = kernels
    return kernels


def invalidate_cache() -> None:
    """Drop the artifact cache (tests, and the autotune CLI after a save)."""
    _cache.clear()


def resolve(kernel: str, param: str) -> int:
    """The block value a call site should PREFER for (kernel, param):
    env override > tuned artifact > built-in default. Unknown (kernel,
    param) pairs raise — a typo'd name must fail loudly in tests, not
    silently resolve to nothing."""
    default = DEFAULTS[kernel][param]
    env_name = _ENV_OVERRIDES.get((kernel, param))
    if env_name is not None:
        value = env_int(env_name, _SENTINEL)
        if value != _SENTINEL and value > 0:
            return value
    tuned = load_tuned()
    if tuned is not None:
        entry = tuned.get(kernel, {})
        value = entry.get(param)
        if isinstance(value, int) and value > 0:
            return value
    return default


def source(kernel: str | None = None) -> str:
    """Which tier is feeding resolution: "env" if any promoted BLOCK_* knob
    is set (scoped to ``kernel`` when given), else "tuned" if this device
    kind has a loadable artifact, else "default". The engine publishes the
    process-wide form as the serve_kernel_config_source gauge."""
    for (k, _), env_name in _ENV_OVERRIDES.items():
        if kernel is not None and k != kernel:
            continue
        if env_int(env_name, _SENTINEL) != _SENTINEL:
            return "env"
    tuned = load_tuned()
    if tuned is not None and (kernel is None or kernel in tuned):
        return "tuned"
    return "default"


def save_artifact(
    kernels: dict[str, dict[str, Any]],
    directory: str | None = None,
    kind: str | None = None,
) -> str:
    """Persist sweep winners as this device kind's artifact and return its
    path. ``kernels`` maps kernel name -> winning params (a ``us`` timing
    key rides along for the record but is ignored by resolution)."""
    kind = kind or device_kind()
    directory = directory or config_dir()
    os.makedirs(directory, exist_ok=True)
    path = artifact_path(kind, directory)
    payload = {
        "schema": SCHEMA_VERSION,
        "device_kind": kind,
        "kernels": kernels,
    }
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(payload, f, indent=2, sort_keys=True)
        f.write("\n")
    os.replace(tmp, path)
    invalidate_cache()
    return path
