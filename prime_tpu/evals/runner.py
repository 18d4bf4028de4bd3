"""Native JAX eval runner: the verifiers role, TPU-first (SURVEY.md §7 st.5).

Pipeline (north-star workload, BASELINE.md):
  resolve dataset → batch prompts → pjit-sharded generate on the TPU slice
  → score → write outputs/evals/{env}--{model}/<run>/ (metadata.json +
  results.jsonl, the reference's results contract) → push to the Evals Hub
  (prime_tpu.evals.client batched upload; reference utils/eval_push.py:54).

The model provider is pluggable: ``JaxGenerator`` drives the native stack
(HF checkpoint or random-init architecture); tests inject an oracle provider.
"""

from __future__ import annotations

import json
import time
import uuid
from dataclasses import dataclass, field
from pathlib import Path
from typing import Callable, Protocol

from prime_tpu.evals.datasets import EvalExample, load_gsm8k, score_completion, synthetic_arithmetic
from prime_tpu.evals.models import CreateEvaluationRequest, EvalSample
from prime_tpu.evals.tokenizer import Tokenizer, load_tokenizer
from prime_tpu.obs.metrics import Registry
from prime_tpu.obs.trace import TRACER


class Generator(Protocol):
    def generate(
        self,
        prompts: list[str],
        max_new_tokens: int,
        temperature: float,
        top_p: float = 1.0,
        templated: bool = False,
    ) -> list[str]: ...


@dataclass
class EvalRunSpec:
    env: str = "gsm8k"
    model: str = "llama3-8b"
    dataset_path: str | None = None      # None -> synthetic arithmetic
    limit: int | None = 64
    batch_size: int = 8
    max_new_tokens: int = 256
    temperature: float = 0.0
    output_dir: str = "outputs/evals"
    checkpoint: str | None = None        # local HF checkpoint dir
    tokenizer: str | None = None         # tokenizer name/path; None -> byte fallback
    slice_name: str | None = None        # TPU slice (e.g. v5e-8) -> sharded generate
    tensor_parallel: int | None = None   # override tp axis (default: mesh_for_slice policy)
    sequence_parallel: int | None = None  # sp axis: slot-sharded long-context KV cache
    kv_quant: bool = False               # int8 KV cache (halved decode HBM traffic)
    weight_quant: bool | str = False     # True/'int8' W8A16; 'int4' W4A16
    speculative: bool = False            # prompt-lookup speculation (any temperature)
    draft_len: int = 4                   # draft tokens per verify pass
    adapter: str | None = None           # LoRA adapter artifact dir to merge
    metadata: dict = field(default_factory=dict)


@dataclass
class EvalRunResult:
    run_dir: Path
    metrics: dict[str, float]
    samples: list[EvalSample]

    @property
    def accuracy(self) -> float:
        return self.metrics.get("accuracy", 0.0)


class JaxGenerator:
    """Model provider backed by prime_tpu.models (the native TPU path).

    Sharded serving (the north-star workload, reference verifiers_bridge.py:944
    played by a native pjit path): pass ``mesh`` (or ``slice_name`` to derive a
    (dp, fsdp, tp) mesh via parallel.mesh.mesh_for_slice) and params are placed
    with the megatron-TP + ZeRO-3 specs from parallel.sharding; prefill+decode
    then run SPMD with the KV cache pinned batch-on-data-axes / heads-on-tp.
    An 8B bf16 checkpoint (~16 GB) only fits a v5e-8 slice this way.
    """

    def __init__(
        self,
        model: str,
        checkpoint: str | None = None,
        tokenizer: str | None = None,
        dtype=None,
        mesh=None,
        slice_name: str | None = None,
        tensor_parallel: int | None = None,
        sequence_parallel: int | None = None,  # sp axis: slot-sharded KV cache
        kv_quant: bool = False,
        weight_quant: bool | str = False,  # True/'int8' -> W8A16; 'int4' -> W4A16
        speculative: bool = False,
        draft_len: int = 4,
        adapter: str | None = None,   # LoRA adapter artifact dir to merge
    ) -> None:
        import jax
        import jax.numpy as jnp

        from prime_tpu.models import get_config
        from prime_tpu.models.llama import init_params

        dtype = dtype or jnp.bfloat16
        if checkpoint is None and Path(model).is_dir():
            checkpoint = model  # `-m ./my-checkpoint` means "load this"
        if checkpoint is not None and not Path(checkpoint).exists():
            raise ValueError(
                f"Checkpoint path {checkpoint!r} does not exist — refusing to "
                "fall back to random weights (results would be garbage)"
            )
        self.tokenizer: Tokenizer = load_tokenizer(tokenizer or checkpoint)
        if checkpoint:
            from prime_tpu.models.hf_loader import load_hf_checkpoint

            self.params, self.config = load_hf_checkpoint(checkpoint, dtype=dtype)
        else:
            self.config = get_config(model)
            self.params = None  # random init, created below once the mesh is known
        tok_vocab = getattr(self.tokenizer, "vocab_size", None)
        if tok_vocab and tok_vocab > self.config.vocab_size:
            raise ValueError(
                f"Tokenizer vocab ({tok_vocab}) exceeds model vocab "
                f"({self.config.vocab_size}) — ids would index out of bounds"
            )

        if self.config.is_moe:
            # inference must not drop tokens: capacity_factor = E/k guarantees
            # every token is served even if routing sends them all to one
            # expert (training keeps the tighter factor; drops there are fine)
            no_drop = self.config.n_experts / self.config.experts_per_token
            if self.config.capacity_factor < no_drop:
                self.config = self.config.scaled(capacity_factor=no_drop)
        if sequence_parallel and (mesh is not None or slice_name is None):
            # silently dropping the flag would leave the user believing a
            # long-context cache is spread across the slice when it isn't
            raise ValueError(
                "sequence_parallel needs slice_name (the sp axis is carved "
                "from the slice's mesh); with an explicit mesh, build the sp "
                "axis into it instead"
            )
        if mesh is None and slice_name is not None:
            from prime_tpu.parallel.mesh import mesh_for_slice

            mesh = mesh_for_slice(
                slice_name,
                tensor_parallel=tensor_parallel,
                expert_parallel=(
                    "auto" if self.config.is_moe and not sequence_parallel else None
                ),
                n_experts=self.config.n_experts or None,
                sequence_parallel=sequence_parallel,
            )
        self.mesh = mesh
        # pure-argument validation first: no failure below should cost a
        # multi-GB parameter placement before surfacing
        if weight_quant and mesh is not None and mesh.size > 1:
            raise ValueError(
                "weight_quant currently serves single-device only (the "
                "quantized (q, scale) leaves have no sharding specs yet)"
            )
        self._data_size = 1
        if mesh is not None:
            tp = mesh.shape.get("tp", 1)
            if self.config.n_kv_heads % tp or self.config.n_heads % tp:
                raise ValueError(
                    f"tp={tp} must divide n_heads={self.config.n_heads} and "
                    f"n_kv_heads={self.config.n_kv_heads} ({self.config.name})"
                )
            self._data_size = mesh.shape.get("dp", 1) * mesh.shape.get("fsdp", 1)
        if self.params is None:
            if mesh is not None:
                # created IN its shardings: a model larger than one chip
                # (qwen3-8b over tp=4) must never be whole on any device
                from prime_tpu.parallel.sharding import init_params_sharded

                self.params = init_params_sharded(
                    jax.random.PRNGKey(0), self.config, mesh, dtype=dtype
                )
            else:
                self.params = init_params(jax.random.PRNGKey(0), self.config, dtype=dtype)

        if adapter is not None:
            from prime_tpu.train.lora import (
                base_fingerprint,
                fingerprints_match,
                load_adapters,
                merge_lora,
            )

            adapters, lora_cfg, meta = load_adapters(adapter)
            if meta["base_model"] != self.config.name:
                raise ValueError(
                    f"Adapter {adapter!r} was trained on {meta['base_model']!r} but "
                    f"this model is {self.config.name!r} — merging would corrupt weights"
                )
            recorded = meta.get("base_fingerprint")
            if recorded is not None and not fingerprints_match(
                recorded, base_fingerprint(self.params)
            ):
                raise ValueError(
                    f"Adapter {adapter!r} was trained over DIFFERENT base weights "
                    f"than this model (same config name {self.config.name!r}, "
                    "different weight fingerprint — e.g. adapters from a "
                    "random-init training base merged into a real checkpoint). "
                    "Re-train the adapters against this checkpoint."
                )
            self.params = merge_lora(self.params, adapters, lora_cfg)

        if mesh is not None:
            from prime_tpu.parallel.sharding import shard_params

            self.params = shard_params(self.params, mesh, self.config)
        if weight_quant:
            # True / "int8" -> W8A16; "int4" -> W4A16 group-wise (half the
            # weight HBM bytes again; MoE expert stacks get int8 first since
            # int4 serves the dense matmul path only)
            from prime_tpu.models.quantize import (
                quantize_params_int4,
                quantize_params_int8,
            )

            if weight_quant == "int4":
                # int4 claims the dense 3D stacks; int8 then covers whatever
                # remains unquantized (MoE expert stacks)
                self.params = quantize_params_int8(quantize_params_int4(self.params))
            else:
                self.params = quantize_params_int8(self.params)
        self.kv_quant = kv_quant
        self.speculative = speculative
        self.draft_len = draft_len
        self._rng = jax.random.PRNGKey(0)

    def generate(
        self,
        prompts: list[str],
        max_new_tokens: int,
        temperature: float,
        top_p: float = 1.0,
        templated: bool = False,  # prompts already carry BOS/chat headers
    ) -> list[str]:
        ids = self.generate_ids(prompts, max_new_tokens, temperature, top_p, templated)
        return [self.tokenizer.decode(row) for row in ids]

    def generate_ids(
        self,
        prompts: list[str],
        max_new_tokens: int,
        temperature: float,
        top_p: float = 1.0,
        templated: bool = False,
    ) -> list[list[int]]:
        """``generate`` before the tokenizer decodes: each prompt's new token ids."""
        import jax
        import jax.numpy as jnp

        from prime_tpu.models.sampler import generate as sample_generate

        if max_new_tokens >= self.config.max_seq_len:
            raise ValueError(
                f"max_new_tokens ({max_new_tokens}) must be < the model's "
                f"max_seq_len ({self.config.max_seq_len})"
            )
        keep = self.config.max_seq_len - max_new_tokens
        encoded = [
            self.tokenizer.encode(p, add_special_tokens=not templated)[-keep:]
            for p in prompts
        ]
        n_real = len(encoded)
        pad_id = self.tokenizer.pad_id
        # SPMD needs the batch divisible by the data axes; pad with dummy rows
        pad_rows = (-n_real) % self._data_size
        encoded += [[pad_id]] * pad_rows
        max_len = max(len(e) for e in encoded)
        batch = jnp.asarray(
            [e + [pad_id] * (max_len - len(e)) for e in encoded], dtype=jnp.int32
        )
        lengths = jnp.asarray([len(e) for e in encoded], dtype=jnp.int32)
        self._rng, rng = jax.random.split(self._rng)
        kw: dict = {}
        if self.mesh is not None:
            from jax.sharding import NamedSharding

            from prime_tpu.parallel.sharding import (
                batch_spec,
                lengths_spec,
                prune_spec,
                serving_cache_spec,
            )

            batch = jax.device_put(
                batch, NamedSharding(self.mesh, prune_spec(batch_spec(), self.mesh))
            )
            lengths = jax.device_put(
                lengths, NamedSharding(self.mesh, prune_spec(lengths_spec(), self.mesh))
            )
            # an sp axis shards the KV cache's SLOT dimension: a long-context
            # cache larger than one chip's HBM spreads across the slice.
            # serving_cache_spec keeps MLA's single-latent head axis
            # replicated (one owner, shared with the serve engine/server)
            kw["cache_spec"] = serving_cache_spec(self.config, self.mesh)
            if self.mesh.size > 1:
                # pallas kernels are not SPMD-partitionable under jit; on a
                # real multi-device mesh the XLA paths (which XLA shards) must
                # run instead of the single-device pallas decode kernel
                kw["attn_impl"] = "xla"
        import contextlib

        ctx = jax.set_mesh(self.mesh) if self.mesh is not None else contextlib.nullcontext()
        with ctx:
            if self.speculative:
                from prime_tpu.models.speculative import spec_generate

                # sampled speculation is rejection sampling against the
                # n-gram proposal — exact in DISTRIBUTION at any temperature
                result = spec_generate(
                    self.params,
                    batch,
                    lengths,
                    self.config,
                    max_new_tokens=max_new_tokens,
                    draft_len=self.draft_len,
                    eos_id=self.tokenizer.eos_id,
                    pad_id=pad_id,
                    attn_impl=kw.get("attn_impl", "auto"),
                    cache_spec=kw.get("cache_spec"),
                    temperature=temperature,
                    top_p=top_p,
                    nucleus=top_p < 1.0,
                    rng=rng,
                    kv_quant=self.kv_quant,
                )
            else:
                result = sample_generate(
                    self.params,
                    batch,
                    lengths,
                    self.config,
                    rng,
                    max_new_tokens=max_new_tokens,
                    temperature=temperature,
                    top_p=top_p,
                    nucleus=top_p < 1.0,
                    eos_id=self.tokenizer.eos_id,
                    pad_id=pad_id,
                    kv_quant=self.kv_quant,
                    **kw,
                )
        tokens = jax.device_get(result.tokens).tolist()[:n_real]
        lens = jax.device_get(result.lengths).tolist()[:n_real]
        return [t[:n] for t, n in zip(tokens, lens)]


def run_eval(
    spec: EvalRunSpec,
    generator: Generator | None = None,
    progress: Callable[[int, int], None] | None = None,
    examples: list[EvalExample] | None = None,
    scorer: Callable[[str, str], float] | None = None,
) -> EvalRunResult:
    """Run an eval. ``examples``/``scorer`` come from an executed environment
    (envhub.execution.load_environment); otherwise the dataset path / synthetic
    fallback supplies examples and exact-match scoring applies."""
    if examples is not None:
        examples = examples[: spec.limit] if spec.limit else list(examples)
    elif spec.dataset_path:
        examples = load_gsm8k(spec.dataset_path, limit=spec.limit)
    else:
        examples = synthetic_arithmetic(spec.limit or 64)
    if not examples:
        raise ValueError(f"No examples loaded from {spec.dataset_path!r}")
    if generator is None:
        generator = JaxGenerator(
            spec.model,
            checkpoint=spec.checkpoint,
            tokenizer=spec.tokenizer,
            slice_name=spec.slice_name,
            tensor_parallel=spec.tensor_parallel,
            sequence_parallel=spec.sequence_parallel,
            kv_quant=spec.kv_quant,
            weight_quant=spec.weight_quant,
            speculative=spec.speculative,
            draft_len=spec.draft_len,
            adapter=spec.adapter,
        )

    # per-run registry: batch/sample latency histograms land in the run's
    # metadata.json under "obs" (runs stay isolated from each other); the
    # summary metrics below are derived from the same observations
    registry = Registry()
    batch_hist = registry.histogram(
        "eval_batch_seconds", "Wall time per generate() batch"
    )
    sample_hist = registry.histogram(
        "eval_sample_seconds", "Amortized wall time per sample (batch/size)"
    )
    samples_counter = registry.counter("eval_samples_total", "Samples scored")
    sample_latencies: list[float] = []

    samples: list[EvalSample] = []
    t0 = time.monotonic()
    for start in range(0, len(examples), spec.batch_size):
        chunk: list[EvalExample] = examples[start : start + spec.batch_size]
        batch_t0 = time.monotonic()
        with TRACER.span("eval.batch", start=start, size=len(chunk)):
            completions = generator.generate(
                [e.prompt for e in chunk], spec.max_new_tokens, spec.temperature
            )
        batch_elapsed = time.monotonic() - batch_t0
        batch_hist.observe(batch_elapsed)
        per_sample = batch_elapsed / len(chunk)
        for _ in chunk:
            sample_hist.observe(per_sample)
            sample_latencies.append(per_sample)
        samples_counter.inc(len(chunk))
        for example, completion in zip(chunk, completions):
            if scorer is not None:
                reward = float(scorer(completion, example.answer))
                correct = reward >= 0.5
            else:
                correct = score_completion(completion, example.answer)
                reward = 1.0 if correct else 0.0
            samples.append(
                EvalSample(
                    sample_id=f"s_{len(samples)}",
                    prompt=example.prompt,
                    completion=completion,
                    answer=example.answer,
                    reward=reward,
                    correct=correct,
                )
            )
        if progress:
            progress(len(samples), len(examples))
    elapsed = time.monotonic() - t0

    n = len(samples)
    ordered = sorted(sample_latencies)
    metrics = {
        "accuracy": sum(1 for s in samples if s.correct) / n,
        "samples_per_sec": n / elapsed if elapsed > 0 else 0.0,
        "num_samples": float(n),
        "wall_time_s": elapsed,
        # per-sample latency distribution (amortized over each batch) — a
        # single elapsed scalar hides stragglers and warmup/compile skew
        "sample_latency_mean_s": sum(ordered) / len(ordered) if ordered else 0.0,
        "sample_latency_p50_s": ordered[len(ordered) // 2] if ordered else 0.0,
        "sample_latency_p95_s": ordered[int(len(ordered) * 0.95)] if ordered else 0.0,
        "sample_latency_max_s": ordered[-1] if ordered else 0.0,
    }

    run_id = f"{time.strftime('%Y%m%d-%H%M%S')}-{uuid.uuid4().hex[:8]}"
    run_dir = Path(spec.output_dir) / f"{spec.env}--{spec.model}" / run_id
    run_dir.mkdir(parents=True, exist_ok=True)
    (run_dir / "metadata.json").write_text(
        json.dumps(
            {
                "env": spec.env,
                "model": spec.model,
                "metrics": metrics,
                "spec": {
                    "limit": spec.limit,
                    "batch_size": spec.batch_size,
                    "max_new_tokens": spec.max_new_tokens,
                    "temperature": spec.temperature,
                },
                # full histogram data (bucket counts) for offline analysis —
                # the scalar metrics above are a lossy summary of these
                "obs": registry.snapshot(),
                **spec.metadata,
            },
            indent=2,
        )
    )
    with open(run_dir / "results.jsonl", "w") as f:
        for sample in samples:
            f.write(json.dumps(sample.model_dump(by_alias=True, exclude_none=True)) + "\n")
    return EvalRunResult(run_dir=run_dir, metrics=metrics, samples=samples)


def find_latest_run(output_dir: str | Path, env: str | None = None, model: str | None = None) -> Path:
    """Newest outputs/evals/{env}--{model}/<run>/ dir (reference eval_push.py)."""
    base = Path(output_dir)
    candidates = []
    for env_model_dir in base.iterdir() if base.exists() else []:
        if not env_model_dir.is_dir() or "--" not in env_model_dir.name:
            continue
        dir_env, _, dir_model = env_model_dir.name.partition("--")
        if env and dir_env != env:
            continue
        if model and dir_model != model:
            continue
        for run_dir in env_model_dir.iterdir():
            if (run_dir / "metadata.json").exists():
                candidates.append(run_dir)
    if not candidates:
        raise FileNotFoundError(f"No eval runs under {base}")
    return max(candidates, key=lambda p: p.stat().st_mtime)


def push_eval_results(run_dir: str | Path, client) -> "tuple[str, dict]":
    """Upload a run dir to the Evals Hub: create → push samples → finalize."""
    run_dir = Path(run_dir)
    metadata = json.loads((run_dir / "metadata.json").read_text())
    samples = []
    with open(run_dir / "results.jsonl") as f:
        for line in f:
            if line.strip():
                samples.append(json.loads(line))
    evaluation = client.create_evaluation(
        CreateEvaluationRequest(
            env=metadata["env"], model=metadata["model"], metadata=metadata.get("spec", {})
        )
    )
    client.push_samples(evaluation.eval_id, samples)
    metrics = {k: v for k, v in metadata.get("metrics", {}).items() if isinstance(v, (int, float))}
    client.finalize_evaluation(evaluation.eval_id, metrics)
    return evaluation.eval_id, metrics
