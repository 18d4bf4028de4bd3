"""Dedicated full-finetune dispatch (reference: prime_cli/api/training.py:33-118).

Full-FT runs ship the WHOLE TOML as opaque config (the training stack owns
the schema); the backend mints a per-run token server-side. The client only
picks the TPU slice shape.
"""

from __future__ import annotations

import tomllib
from pathlib import Path
from typing import Any

from prime_tpu.core.client import APIClient


def build_payload_from_toml(
    toml_path: str | Path,
    env_vars: dict[str, str] | None = None,
    tpu_type: str | None = None,
    num_slices: int | None = None,
) -> dict[str, Any]:
    raw = Path(toml_path).read_text()
    parsed = tomllib.loads(raw)  # validates syntax before shipping
    payload: dict[str, Any] = {
        "name": parsed.get("name") or Path(toml_path).stem,
        "config": raw,
        "envVars": env_vars or {},
    }
    infra = parsed.get("infrastructure", {})
    payload["tpuType"] = tpu_type or infra.get("tpu_type", "v5e-8")
    payload["numSlices"] = num_slices or infra.get("num_slices", 1)
    return payload


class HostedTrainingClient:
    def __init__(self, client: APIClient) -> None:
        self.client = client

    def create_run(self, payload: dict[str, Any]) -> dict[str, Any]:
        return self.client.post("/training/runs", json=payload, idempotent_post=True)

    def get_run(self, run_id: str) -> dict[str, Any]:
        return self.client.get(f"/training/runs/{run_id}")
