"""Hosted-training TOML config schema (reference: commands/rl.py:362-913).

Pydantic with ``extra="forbid"`` everywhere — typos in TOML keys are errors,
not silently ignored config. Deprecated keys are stripped with warnings
(reference :829); GPU-era keys map to their TPU replacements. Full-finetune
detection (reference :882) switches dispatch to the dedicated trainer.
"""

from __future__ import annotations

import tomllib
from pathlib import Path
from typing import Literal

from pydantic import BaseModel, ConfigDict, Field

# GPU-era keys → TPU replacement (or None if dropped outright)
DEPRECATED_KEYS: dict[str, str | None] = {
    "gpu_type": "infrastructure.tpu_type",
    "num_gpus": "infrastructure.tpu_type (slice size)",
    "gpus": "infrastructure.tpu_type (slice size)",
    "interconnect": None,
    "nccl_timeout": None,
}


class EnvSection(BaseModel):
    model_config = ConfigDict(extra="forbid")

    id: str
    version: str | None = None
    max_input_tokens: int | None = None
    max_output_tokens: int | None = None
    max_total_tokens: int | None = None


class SamplingSection(BaseModel):
    model_config = ConfigDict(extra="forbid")

    temperature: float = 1.0
    top_p: float = 1.0
    max_tokens: int = 512
    seq_len: int = 4096


class EvalSection(BaseModel):
    model_config = ConfigDict(extra="forbid")

    interval: int = 100
    n_samples: int = 64


class WandbSection(BaseModel):
    model_config = ConfigDict(extra="forbid")

    project: str | None = None
    entity: str | None = None


class CheckpointsSection(BaseModel):
    model_config = ConfigDict(extra="forbid")

    interval: int = 500
    keep: int = 3


class AdapterSection(BaseModel):
    model_config = ConfigDict(extra="forbid")

    r: int = 16
    alpha: int = 32
    dropout: float = 0.0


class InfrastructureSection(BaseModel):
    model_config = ConfigDict(extra="forbid")

    tpu_type: str = "v5e-8"        # slice name — chips implied by the slice
    num_slices: int = 1            # DCN data parallelism across slices


class RLConfig(BaseModel):
    model_config = ConfigDict(extra="forbid")

    name: str
    model: str
    type: Literal["lora", "full_finetune"] = "lora"
    env: EnvSection
    learning_rate: float = 1e-5
    batch_size: int = 32
    max_steps: int = 1000
    checkpoint_id: str | None = None     # warm start (reference :778)
    sampling: SamplingSection = Field(default_factory=SamplingSection)
    eval: EvalSection = Field(default_factory=EvalSection)
    wandb: WandbSection = Field(default_factory=WandbSection)
    checkpoints: CheckpointsSection = Field(default_factory=CheckpointsSection)
    adapter: AdapterSection = Field(default_factory=AdapterSection)
    infrastructure: InfrastructureSection = Field(default_factory=InfrastructureSection)

    @property
    def is_full_finetune(self) -> bool:
        return self.type == "full_finetune"

    def to_payload(self) -> dict:
        payload = {
            "name": self.name,
            "model": self.model,
            "runType": self.type,
            "env": self.env.model_dump(exclude_none=True),
            "learningRate": self.learning_rate,
            "batchSize": self.batch_size,
            "maxSteps": self.max_steps,
            "sampling": self.sampling.model_dump(),
            "eval": self.eval.model_dump(),
            "checkpoints": self.checkpoints.model_dump(),
            "adapter": self.adapter.model_dump(),
            "tpuType": self.infrastructure.tpu_type,
            "numSlices": self.infrastructure.num_slices,
        }
        if self.checkpoint_id:
            payload["checkpointId"] = self.checkpoint_id
        if self.wandb.project:
            payload["wandb"] = self.wandb.model_dump(exclude_none=True)
        return payload


def strip_deprecated(raw: dict) -> tuple[dict, list[str]]:
    """Remove deprecated keys anywhere in the tree; return warnings."""
    warnings = []

    def walk(node: dict) -> dict:
        out = {}
        for key, value in node.items():
            if key in DEPRECATED_KEYS:
                replacement = DEPRECATED_KEYS[key]
                hint = f" — use {replacement}" if replacement else " (no TPU equivalent)"
                warnings.append(f"deprecated key '{key}' ignored{hint}")
                continue
            out[key] = walk(value) if isinstance(value, dict) else value
        return out

    return walk(raw), warnings


def load_rl_config(toml_path: str | Path) -> tuple[RLConfig, list[str]]:
    raw = tomllib.loads(Path(toml_path).read_text())
    cleaned, warnings = strip_deprecated(raw)
    return RLConfig.model_validate(cleaned), warnings


RL_TOML_TEMPLATE = """\
name = "{name}"
model = "llama3-8b"
type = "lora"            # or "full_finetune"
learning_rate = 1e-5
batch_size = 32
max_steps = 1000

[env]
id = "gsm8k"

[sampling]
temperature = 1.0
max_tokens = 512
seq_len = 4096

[adapter]
r = 16
alpha = 32

[infrastructure]
tpu_type = "v5e-8"       # TPU slice per worker
num_slices = 1           # DCN data parallelism across slices

[checkpoints]
interval = 500
keep = 3
"""
