"""Evals SDK + native JAX runner tests against the fake hub."""

import json

import pytest

from prime_tpu.core.client import APIClient, AsyncAPIClient
from prime_tpu.core.config import Config
from prime_tpu.evals import AsyncEvalsClient, CreateEvaluationRequest, EvalsClient
from prime_tpu.evals.client import build_batches
from prime_tpu.evals.datasets import (
    extract_gold_answer,
    normalize_number,
    score_completion,
    synthetic_arithmetic,
)
from prime_tpu.evals.runner import EvalRunSpec, find_latest_run, push_eval_results, run_eval
from prime_tpu.testing import FakeControlPlane


@pytest.fixture
def fake():
    return FakeControlPlane()


@pytest.fixture
def client(fake):
    cfg = Config()
    cfg.api_key = "test-key"
    return EvalsClient(APIClient(config=cfg, base_url="https://api.fake", transport=fake.transport))


# -- scoring -----------------------------------------------------------------


@pytest.mark.parametrize(
    "text,expected",
    [
        ("The answer is 42.", "42"),
        ("costs $1,234 total", "1234"),
        ("= 3.5 exactly", "3.5"),
        ("first 12 then 99", "99"),
        ("no numbers here", None),
    ],
)
def test_normalize_number(text, expected):
    assert normalize_number(text) == expected


def test_gold_answer_extraction():
    assert extract_gold_answer("Step 1... #### 1,234") == "1234"
    assert score_completion("so the total is 72", "72")
    assert not score_completion("so the total is 71", "72")


# -- SDK ---------------------------------------------------------------------


def test_env_get_or_create_and_resolution(client, fake):
    env1 = client.resolve_environment("gsm8k")
    env2 = client.resolve_environment("gsm8k")
    assert env1.env_id == env2.env_id  # second call found, not re-created
    by_id = client.resolve_environment(env1.env_id)
    assert by_id.name == "gsm8k"
    by_slug = client.resolve_environment("user_1/gsm8k")
    assert by_slug.env_id == env1.env_id


def test_eval_lifecycle_and_push(client, fake):
    evaluation = client.create_evaluation(CreateEvaluationRequest(env="gsm8k", model="llama3-8b"))
    assert evaluation.status == "RUNNING"
    n = client.push_samples(
        evaluation.eval_id,
        [{"sampleId": f"s{i}", "completion": f"c{i}", "correct": i % 2 == 0} for i in range(10)],
    )
    assert n == 10
    final = client.finalize_evaluation(evaluation.eval_id, {"accuracy": 0.5})
    assert final.status == "FINALIZED" and final.metrics["accuracy"] == 0.5
    assert len(client.get_samples(evaluation.eval_id)) == 10


def test_build_batches_respects_size_cap():
    samples = [{"completion": "x" * 1000} for _ in range(100)]
    batches = build_batches(samples, max_bytes=10_500)
    assert len(batches) > 1
    assert sum(len(b) for b in batches) == 100
    for batch in batches:
        assert len(json.dumps(batch)) <= 10_500 + 1100  # one-sample slack


def test_push_samples_retries_429(client, fake):
    evaluation = client.create_evaluation(CreateEvaluationRequest(env="e", model="m"))
    fake.evals_plane.rate_limit_next = 2
    n = client.push_samples(evaluation.eval_id, [{"sampleId": "a"}])
    assert n == 1
    assert fake.evals_plane.upload_posts >= 3  # 2 rate-limited + 1 success
    assert len(fake.evals_plane.samples[evaluation.eval_id]) == 1


def test_push_samples_parallel_batches(client, fake):
    evaluation = client.create_evaluation(CreateEvaluationRequest(env="e", model="m"))
    samples = [{"sampleId": f"s{i}", "completion": "y" * 100} for i in range(50)]
    posts_before = fake.evals_plane.upload_posts
    progress_calls = []
    n = client.push_samples(
        evaluation.eval_id,
        samples,
        max_batch_bytes=2000,
        progress=lambda done, total: progress_calls.append((done, total)),
    )
    assert n == 50
    batches_sent = fake.evals_plane.upload_posts - posts_before
    assert batches_sent > 3  # the cap really split the upload
    assert progress_calls[-1] == (batches_sent, batches_sent)
    assert len(fake.evals_plane.samples[evaluation.eval_id]) == 50


@pytest.mark.anyio
async def test_async_client_mirror(fake):
    cfg = Config()
    cfg.api_key = "test-key"
    client = AsyncEvalsClient(
        AsyncAPIClient(config=cfg, base_url="https://api.fake", transport=fake.transport)
    )
    evaluation = await client.create_evaluation(CreateEvaluationRequest(env="async-env", model="m"))
    fake.evals_plane.rate_limit_next = 1
    n = await client.push_samples(evaluation.eval_id, [{"sampleId": f"s{i}"} for i in range(5)])
    assert n == 5
    final = await client.finalize_evaluation(evaluation.eval_id, {"accuracy": 1.0})
    assert final.status == "FINALIZED"
    await client.api.close()


# -- runner ------------------------------------------------------------------


class OracleGenerator:
    """Always answers correctly — pins the scoring/writing plumbing."""

    def __init__(self, examples):
        self.answers = {e.prompt: e.answer for e in examples}

    def generate(self, prompts, max_new_tokens, temperature):
        return [f"The answer is {self.answers[p]}." for p in prompts]


def test_run_eval_oracle_end_to_end(tmp_path, client, fake):
    examples = synthetic_arithmetic(10)
    spec = EvalRunSpec(env="arith", model="oracle", limit=10, batch_size=4, output_dir=str(tmp_path))
    result = run_eval(spec, generator=OracleGenerator(examples))
    assert result.metrics["accuracy"] == 1.0
    assert result.metrics["num_samples"] == 10

    # results contract: metadata.json + results.jsonl
    metadata = json.loads((result.run_dir / "metadata.json").read_text())
    assert metadata["env"] == "arith" and metadata["metrics"]["accuracy"] == 1.0
    lines = (result.run_dir / "results.jsonl").read_text().strip().splitlines()
    assert len(lines) == 10
    assert json.loads(lines[0])["correct"] is True

    # discovery + hub push
    latest = find_latest_run(tmp_path)
    assert latest == result.run_dir
    eval_id, metrics = push_eval_results(latest, client)
    assert metrics["accuracy"] == 1.0
    assert fake.evals_plane.evaluations[eval_id]["status"] == "FINALIZED"
    assert len(fake.evals_plane.samples[eval_id]) == 10


def test_run_eval_with_jax_generator(tmp_path):
    """Full native path: tiny model + byte tokenizer (random weights — the
    pipeline is what's under test, accuracy will be ~0)."""
    spec = EvalRunSpec(
        env="arith",
        model="tiny-test",
        limit=4,
        batch_size=2,
        max_new_tokens=8,
        output_dir=str(tmp_path),
    )
    result = run_eval(spec)
    assert result.metrics["num_samples"] == 4
    assert result.metrics["samples_per_sec"] > 0
    assert (result.run_dir / "results.jsonl").exists()
    completions = [s.completion for s in result.samples]
    assert all(isinstance(c, str) for c in completions)


def test_missing_checkpoint_raises(tmp_path):
    from prime_tpu.evals.runner import JaxGenerator

    with pytest.raises(ValueError, match="does not exist"):
        JaxGenerator("llama3-8b", checkpoint=str(tmp_path / "nope"))


def test_bad_tokenizer_name_raises():
    from prime_tpu.evals.tokenizer import load_tokenizer

    with pytest.raises(ValueError, match="Could not load tokenizer"):
        load_tokenizer("meta-lama/definitely-not-a-tokenizer")


def test_max_new_tokens_bound(tmp_path):
    from prime_tpu.evals.runner import JaxGenerator

    gen = JaxGenerator("tiny-test")
    with pytest.raises(ValueError, match="max_new_tokens"):
        gen.generate(["hi"], max_new_tokens=600, temperature=0.0)


def test_run_eval_sharded_slice(tmp_path):
    """North-star shape: eval run with --slice shards the generator over the
    (virtual) v5e-8 mesh and still writes the results contract."""
    spec = EvalRunSpec(
        env="arith",
        model="tiny-test",
        limit=4,
        batch_size=3,  # deliberately not divisible by the data axes
        max_new_tokens=8,
        output_dir=str(tmp_path),
        slice_name="v5e-8",
    )
    result = run_eval(spec)
    assert result.metrics["num_samples"] == 4
    assert (result.run_dir / "results.jsonl").exists()


def test_checkpoint_without_tokenizer_errors_not_byte_fallback(tmp_path):
    """A real checkpoint whose tokenizer can't load must be an error — a
    silent byte fallback would score garbage as results (VERDICT r1 weak #4)."""
    import json as _json

    from prime_tpu.evals.runner import JaxGenerator

    ckpt = tmp_path / "ckpt"
    ckpt.mkdir()
    (ckpt / "config.json").write_text(_json.dumps({
        "vocab_size": 64, "hidden_size": 32, "num_hidden_layers": 1,
        "num_attention_heads": 4, "num_key_value_heads": 2,
        "intermediate_size": 64, "rms_norm_eps": 1e-5,
    }))
    # no tokenizer files and no weights: tokenizer failure must surface first
    with pytest.raises(ValueError, match="Could not load tokenizer"):
        JaxGenerator("some-model", checkpoint=str(ckpt))


def test_run_eval_with_kv_quant(tmp_path):
    spec = EvalRunSpec(
        env="arith",
        model="tiny-test",
        limit=2,
        batch_size=2,
        max_new_tokens=6,
        output_dir=str(tmp_path),
        kv_quant=True,
    )
    result = run_eval(spec)
    assert result.metrics["num_samples"] == 2


def test_run_eval_with_weight_quant(tmp_path):
    spec = EvalRunSpec(
        env="arith",
        model="tiny-test",
        limit=2,
        batch_size=2,
        max_new_tokens=6,
        output_dir=str(tmp_path),
        weight_quant=True,
        kv_quant=True,
    )
    result = run_eval(spec)
    assert result.metrics["num_samples"] == 2


def test_run_eval_sequence_parallel_slot_sharded_cache(tmp_path):
    """eval run --slice --sp: the KV cache's slot axis shards over sp and
    the whole eval pipeline still produces results (long-context serving
    building block through the real runner)."""
    from prime_tpu.evals.runner import EvalRunSpec, run_eval

    spec = EvalRunSpec(
        env="synthetic-arith", model="tiny-test", limit=4, batch_size=4,
        max_new_tokens=8, output_dir=str(tmp_path),
        slice_name="v5e-8", tensor_parallel=1, sequence_parallel=4,
    )
    result = run_eval(spec)
    assert result.metrics["num_samples"] == 4
    assert (result.run_dir / "results.jsonl").exists()


def test_sequence_parallel_without_slice_rejected():
    """--sp must not be silently dropped: without a slice (or with an
    explicit mesh) the generator refuses instead of serving unsharded."""
    import pytest as _pytest

    from prime_tpu.evals.runner import JaxGenerator

    with _pytest.raises(ValueError, match="sequence_parallel needs slice_name"):
        JaxGenerator("tiny-test", sequence_parallel=4)
