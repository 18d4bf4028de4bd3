"""Hosted training: TOML schema, dispatch, monitoring."""

import json

import pytest
from click.testing import CliRunner

import prime_tpu.commands._deps as deps
from prime_tpu.commands.main import cli
from prime_tpu.testing import FakeControlPlane
from prime_tpu.train.config import RL_TOML_TEMPLATE, load_rl_config, strip_deprecated


@pytest.fixture
def fake(monkeypatch):
    fake = FakeControlPlane()
    monkeypatch.setattr(deps, "transport_override", fake.transport)
    monkeypatch.setenv("PRIME_API_KEY", "test-key")
    monkeypatch.setenv("PRIME_BASE_URL", "https://api.fake")
    return fake


@pytest.fixture
def runner():
    return CliRunner()


@pytest.fixture
def toml_file(tmp_path):
    path = tmp_path / "job.toml"
    path.write_text(RL_TOML_TEMPLATE.format(name="my-run"))
    return path


# -- schema ------------------------------------------------------------------


def test_template_parses(toml_file):
    config, warnings = load_rl_config(toml_file)
    assert config.name == "my-run" and config.type == "lora"
    assert config.infrastructure.tpu_type == "v5e-8"
    assert warnings == []


def test_unknown_key_is_an_error(tmp_path):
    path = tmp_path / "bad.toml"
    path.write_text('name = "x"\nmodel = "m"\nbogus_key = 1\n[env]\nid = "e"\n')
    import pydantic

    with pytest.raises(pydantic.ValidationError):
        load_rl_config(path)


def test_deprecated_gpu_keys_stripped_with_warning(tmp_path):
    raw = {"name": "x", "gpu_type": "H100", "env": {"id": "e", "nccl_timeout": 30}}
    cleaned, warnings = strip_deprecated(raw)
    assert "gpu_type" not in cleaned
    assert "nccl_timeout" not in cleaned["env"]
    assert any("tpu_type" in w for w in warnings)
    assert any("no TPU equivalent" in w for w in warnings)


def test_full_finetune_detection(tmp_path):
    path = tmp_path / "ft.toml"
    path.write_text('name = "ft"\nmodel = "llama3-8b"\ntype = "full_finetune"\n[env]\nid = "e"\n')
    config, _ = load_rl_config(path)
    assert config.is_full_finetune


# -- dispatch ----------------------------------------------------------------


def test_train_run_lora_dispatch(runner, fake, toml_file):
    result = runner.invoke(cli, ["train", "run", str(toml_file), "--yes", "--output", "json"])
    assert result.exit_code == 0, result.output
    run_id = json.loads(result.output)["runId"]
    payload = fake.training_plane.payloads[run_id]
    assert payload["tpuType"] == "v5e-8" and payload["adapter"]["r"] == 16


def test_train_default_group_toml_shorthand(runner, fake, toml_file):
    """`prime train foo.toml` ≡ `prime train run foo.toml`."""
    result = runner.invoke(cli, ["train", str(toml_file), "--yes"])
    assert result.exit_code == 0, result.output
    assert "dispatched" in result.output


def test_train_full_ft_ships_whole_toml(runner, fake, tmp_path):
    path = tmp_path / "ft.toml"
    path.write_text(
        'name = "ft"\nmodel = "llama3-70b"\ntype = "full_finetune"\n'
        '[env]\nid = "e"\n[infrastructure]\ntpu_type = "v5p-64"\nnum_slices = 2\n'
    )
    result = runner.invoke(cli, ["train", str(path), "--yes", "--output", "json"])
    assert result.exit_code == 0, result.output
    run_id = json.loads(result.output)["runId"]
    payload = fake.training_plane.payloads[run_id]
    assert "config" in payload and 'type = "full_finetune"' in payload["config"]
    assert payload["tpuType"] == "v5p-64" and payload["numSlices"] == 2
    assert fake.training_plane.runs[run_id]["runToken"].startswith("rtok_")


def test_invalid_config_fails_cleanly(runner, fake, tmp_path):
    path = tmp_path / "bad.toml"
    path.write_text('name = "x"\nmodel = "m"\nwrong = 1\n[env]\nid = "e"\n')
    result = runner.invoke(cli, ["train", str(path), "--yes"])
    assert result.exit_code != 0
    assert "Invalid config" in result.output and "wrong" in result.output


# -- monitoring --------------------------------------------------------------


def _dispatch(runner, toml_file) -> str:
    result = runner.invoke(cli, ["train", "run", str(toml_file), "--yes", "--output", "json"])
    return json.loads(result.output)["runId"]


def test_lifecycle_and_monitoring(runner, fake, toml_file):
    run_id = _dispatch(runner, toml_file)
    result = runner.invoke(cli, ["train", "list", "--plain"])
    assert "my-run" in result.output

    # status advances per poll
    runner.invoke(cli, ["train", "get", run_id])
    result = runner.invoke(cli, ["train", "get", run_id, "--output", "json"])
    assert json.loads(result.output)["status"] in ("RUNNING", "COMPLETED")

    result = runner.invoke(cli, ["train", "logs", run_id, "--component", "trainer", "--worker", "0", "--plain"])
    assert "trainer w0" in result.output and "inference" not in result.output

    result = runner.invoke(cli, ["train", "metrics", run_id, "--output", "json"])
    assert "loss" in json.loads(result.output)

    result = runner.invoke(cli, ["train", "progress", run_id, "--output", "json"])
    assert "pct" in json.loads(result.output)

    result = runner.invoke(cli, ["train", "rollouts", run_id, "--plain"])
    assert "rollout" in result.output

    result = runner.invoke(cli, ["train", "components", run_id, "--plain"])
    assert "trainer" in result.output

    # drive to completion, then checkpoints exist
    for _ in range(4):
        runner.invoke(cli, ["train", "get", run_id])
    result = runner.invoke(cli, ["train", "checkpoints", run_id, "--output", "json"])
    assert json.loads(result.output)


def test_stop_restart_delete(runner, fake, toml_file):
    run_id = _dispatch(runner, toml_file)
    result = runner.invoke(cli, ["train", "stop", run_id])
    assert "STOPPED" in result.output
    result = runner.invoke(cli, ["train", "restart", run_id])
    assert "PENDING" in result.output
    assert runner.invoke(cli, ["train", "delete", run_id, "--yes"]).exit_code == 0
    result = runner.invoke(cli, ["train", "list", "--output", "json"])
    assert json.loads(result.output) == []


def test_models_tpus_configs(runner, fake):
    result = runner.invoke(cli, ["train", "models", "--plain"])
    assert "llama3-8b" in result.output and "llama3-70b" in result.output
    result = runner.invoke(cli, ["train", "tpus", "--plain"])
    assert "v5p-64" in result.output
    result = runner.invoke(cli, ["train", "configs"])
    schema = json.loads(result.output)
    assert schema["properties"]["infrastructure"]


def test_train_init_writes_template(runner, tmp_path, monkeypatch):
    monkeypatch.chdir(tmp_path)
    result = runner.invoke(cli, ["train", "init", "exp1"])
    assert result.exit_code == 0
    assert (tmp_path / "exp1.toml").exists()
    config, _ = load_rl_config(tmp_path / "exp1.toml")
    assert config.name == "exp1"


# -- native trainer checkpoint/metrics ---------------------------------------


def test_checkpoint_save_restore_roundtrip(tmp_path):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from prime_tpu.models import get_config
    from prime_tpu.models.llama import init_params
    from prime_tpu.train import default_optimizer, init_train_state, make_train_step
    from prime_tpu.train.checkpoint import CheckpointManager

    cfg = get_config("tiny-test")
    optimizer = default_optimizer(learning_rate=1e-2)
    state = init_train_state(init_params(jax.random.PRNGKey(0), cfg, dtype=jnp.float32), optimizer)
    step = make_train_step(cfg, optimizer)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, cfg.vocab_size)
    targets = jnp.roll(tokens, -1, axis=1)
    mask = jnp.ones_like(tokens, dtype=jnp.float32)
    state, _ = step(state, tokens, targets, mask)

    manager = CheckpointManager(tmp_path / "ckpts", keep=2)
    saved_step = manager.save(state, metrics={"loss": 1.0})
    assert saved_step == 1
    assert manager.latest_step() == 1

    fresh = init_train_state(init_params(jax.random.PRNGKey(7), cfg, dtype=jnp.float32), optimizer)
    restored = manager.restore(fresh)
    np.testing.assert_array_equal(
        np.asarray(restored.params["embed"]), np.asarray(state.params["embed"])
    )
    assert int(restored.step) == 1
    # resumed training continues from the restored state
    resumed, metrics = step(restored, tokens, targets, mask)
    assert int(resumed.step) == 2 and np.isfinite(float(metrics["loss"]))
    manager.close()


def test_checkpoint_retention(tmp_path):
    import jax
    import jax.numpy as jnp

    from prime_tpu.models import get_config
    from prime_tpu.models.llama import init_params
    from prime_tpu.train import default_optimizer, init_train_state
    from prime_tpu.train.checkpoint import CheckpointManager
    from prime_tpu.train.trainer import TrainState

    cfg = get_config("tiny-test")
    optimizer = default_optimizer()
    state = init_train_state(init_params(jax.random.PRNGKey(0), cfg, dtype=jnp.float32), optimizer)
    manager = CheckpointManager(tmp_path / "ckpts", keep=2)
    for i in range(1, 5):
        state = TrainState(state.params, state.opt_state, jnp.asarray(i))
        manager.save(state)
    assert manager.latest_step() == 4
    steps = sorted(int(p.name) for p in (tmp_path / "ckpts").iterdir() if p.name.isdigit())
    assert steps == [3, 4]  # retention pruned older checkpoints
    manager.close()


def test_metrics_logger(tmp_path):
    from prime_tpu.train.metrics import MetricsLogger

    logger = MetricsLogger(tmp_path)
    logger.log(1, loss=2.5, grad_norm=1.1)
    logger.log(2, loss=2.1, note="warmup done")
    rows = logger.read()
    assert [r["step"] for r in rows] == [1, 2]
    assert rows[0]["loss"] == 2.5
    assert logger.last()["note"] == "warmup done"


# -- round-2 trainer depth: accumulation, schedule, loop ----------------------


def test_grad_accumulation_matches_full_batch():
    """accum_steps=N on batch B must match one step on the same batch (same
    data, same update) up to fp32 accumulation noise."""
    import jax
    import jax.numpy as jnp

    from prime_tpu.models import get_config
    from prime_tpu.models.llama import init_params
    from prime_tpu.train import default_optimizer, init_train_state, make_train_step

    config = get_config("tiny-test")
    params = init_params(jax.random.PRNGKey(0), config, dtype=jnp.float32)
    optimizer = default_optimizer(learning_rate=1e-3)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, config.vocab_size)
    targets = jnp.roll(tokens, -1, axis=1)
    mask = jnp.ones_like(tokens, dtype=jnp.float32)

    # the jitted step donates its state: each run needs its own buffers
    params_b = init_params(jax.random.PRNGKey(0), config, dtype=jnp.float32)
    state_a = init_train_state(params, optimizer)
    state_b = init_train_state(params_b, optimizer)
    step_full = make_train_step(config, optimizer)
    step_accum = make_train_step(config, optimizer, accum_steps=2)

    state_a, metrics_a = step_full(state_a, tokens, targets, mask)
    state_b, metrics_b = step_accum(state_b, tokens, targets, mask)

    import numpy as np

    np.testing.assert_allclose(
        float(metrics_a["loss"]), float(metrics_b["loss"]), rtol=1e-5, atol=1e-5
    )
    leaves_a = jax.tree.leaves(state_a.params)
    leaves_b = jax.tree.leaves(state_b.params)
    for a, b in zip(leaves_a, leaves_b):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-4)


def test_grad_accumulation_rejects_indivisible_batch():
    import jax
    import jax.numpy as jnp
    import pytest

    from prime_tpu.models import get_config
    from prime_tpu.models.llama import init_params
    from prime_tpu.train import default_optimizer, init_train_state, make_train_step

    config = get_config("tiny-test")
    params = init_params(jax.random.PRNGKey(0), config, dtype=jnp.float32)
    optimizer = default_optimizer()
    state = init_train_state(params, optimizer)
    step = make_train_step(config, optimizer, accum_steps=3)
    tokens = jnp.zeros((4, 8), jnp.int32)
    with pytest.raises(ValueError, match="not divisible"):
        step(state, tokens, tokens, jnp.ones((4, 8), jnp.float32))


def test_warmup_cosine_schedule_shape():
    from prime_tpu.train import warmup_cosine

    schedule = warmup_cosine(3e-4, total_steps=100, warmup_steps=10)
    assert float(schedule(0)) == 0.0
    assert abs(float(schedule(10)) - 3e-4) < 1e-9  # peak after warmup
    assert float(schedule(100)) < 3e-4 * 0.11  # decayed to the floor


def test_train_loop_times_and_logs(tmp_path):
    import jax
    import jax.numpy as jnp

    from prime_tpu.models import get_config
    from prime_tpu.models.llama import init_params
    from prime_tpu.train import (
        default_optimizer,
        init_train_state,
        make_train_step,
        train_loop,
    )
    from prime_tpu.train.metrics import MetricsLogger

    config = get_config("tiny-test")
    params = init_params(jax.random.PRNGKey(0), config, dtype=jnp.float32)
    optimizer = default_optimizer(learning_rate=1e-3)
    state = init_train_state(params, optimizer)
    step = make_train_step(config, optimizer)

    def batches(n=4):
        for i in range(n):
            tokens = jax.random.randint(jax.random.PRNGKey(i), (2, 16), 0, config.vocab_size)
            yield tokens, jnp.roll(tokens, -1, axis=1), jnp.ones_like(tokens, jnp.float32)

    metrics = MetricsLogger(tmp_path)
    seen = []
    state, report = train_loop(
        state, step, batches(), metrics=metrics, on_step=lambda s, row: seen.append(s),
        profile_dir=str(tmp_path / "trace"), profile_window=(1, 3),
    )
    assert report.steps == 4 and seen == [0, 1, 2, 3]
    assert report.mean_step_time_s > 0 and report.tokens_per_sec > 0
    rows = metrics.read()
    assert len(rows) == 4 and rows[-1]["step_time_s"] > 0
    assert (tmp_path / "trace").exists()  # profiler trace captured


def test_accum_matches_full_batch_with_ragged_mask():
    """Token-weighted accumulation: ragged masks must give the same global
    objective as the full-batch step (mean-of-means would not)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from prime_tpu.models import get_config
    from prime_tpu.models.llama import init_params
    from prime_tpu.train import default_optimizer, init_train_state, make_train_step

    config = get_config("tiny-test")
    optimizer = default_optimizer(learning_rate=1e-3)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, config.vocab_size)
    targets = jnp.roll(tokens, -1, axis=1)
    mask = jnp.zeros((4, 16), jnp.float32)
    mask = mask.at[0, :16].set(1.0).at[1, :2].set(1.0).at[2, :9].set(1.0).at[3, :1].set(1.0)

    state_a = init_train_state(init_params(jax.random.PRNGKey(0), config, jnp.float32), optimizer)
    state_b = init_train_state(init_params(jax.random.PRNGKey(0), config, jnp.float32), optimizer)
    state_a, ma = make_train_step(config, optimizer)(state_a, tokens, targets, mask)
    state_b, mb = make_train_step(config, optimizer, accum_steps=2)(state_b, tokens, targets, mask)
    np.testing.assert_allclose(float(ma["loss"]), float(mb["loss"]), rtol=1e-5)
    for a, b in zip(jax.tree.leaves(state_a.params), jax.tree.leaves(state_b.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-4)


def test_bf16_params_get_fp32_adam_moments():
    import jax
    import jax.numpy as jnp

    from prime_tpu.models import get_config
    from prime_tpu.models.llama import init_params
    from prime_tpu.train import default_optimizer, init_train_state, make_train_step

    config = get_config("tiny-test")
    optimizer = default_optimizer(learning_rate=1e-3)
    params = init_params(jax.random.PRNGKey(0), config, dtype=jnp.bfloat16)
    state = init_train_state(params, optimizer)
    moment_dtypes = {leaf.dtype for leaf in jax.tree.leaves(state.opt_state)}
    assert jnp.bfloat16 not in moment_dtypes  # both mu and nu fp32

    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, config.vocab_size)
    step = make_train_step(config, optimizer)
    state, metrics = step(state, tokens, jnp.roll(tokens, -1, 1), jnp.ones_like(tokens, jnp.float32))
    assert all(leaf.dtype == jnp.bfloat16 for leaf in jax.tree.leaves(state.params))
    assert jnp.bfloat16 not in {leaf.dtype for leaf in jax.tree.leaves(state.opt_state)}


def test_train_local_cli(tmp_path):
    from click.testing import CliRunner

    from prime_tpu.commands.main import cli

    result = CliRunner().invoke(
        cli,
        ["train", "local", "-m", "tiny-test", "--steps", "6", "-b", "4",
         "--seq-len", "32", "--accum", "2", "--lr", "1e-3",
         "--name", "cli-run", "--output-dir", str(tmp_path), "--output", "json"],
    )
    assert result.exit_code == 0, result.output
    import json as _json

    payload = _json.loads(result.output)
    assert payload["steps"] == 6 and payload["tokens_per_sec"] > 0
    metrics = (tmp_path / "cli-run" / "metrics.jsonl").read_text().splitlines()
    assert len(metrics) == 6


def test_train_local_cli_sharded_with_text_data(tmp_path):
    from click.testing import CliRunner

    from prime_tpu.commands.main import cli

    data = tmp_path / "corpus.txt"
    data.write_text("the quick brown fox jumps over the lazy dog. " * 200)
    result = CliRunner().invoke(
        cli,
        ["train", "local", "-m", "tiny-test", "--steps", "4", "-b", "4",
         "--seq-len", "32", "--slice", "v5e-8", "--data", str(data),
         "--name", "sharded-run", "--output-dir", str(tmp_path), "--plain"],
    )
    assert result.exit_code == 0, result.output
    assert "mesh" in result.output and "done:" in result.output


def test_text_batches_shapes_and_determinism(tmp_path):
    from prime_tpu.train.data import text_batches

    data = tmp_path / "c.txt"
    data.write_text("abcdefgh" * 100)
    a = list(text_batches(data, batch=2, seq=16, steps=3, seed=7))
    b = list(text_batches(data, batch=2, seq=16, steps=3, seed=7))
    assert len(a) == 3
    tokens, targets, mask = a[0]
    assert tokens.shape == (2, 16) == targets.shape == mask.shape
    import numpy as _np

    _np.testing.assert_array_equal(_np.asarray(a[1][0]), _np.asarray(b[1][0]))
    # next-token contract: targets are tokens shifted by one
    _np.testing.assert_array_equal(_np.asarray(a[0][0][:, 1:]), _np.asarray(a[0][1][:, :-1]))


@pytest.mark.parametrize("remat", ["full", "dots"])
def test_remat_train_step_matches_plain(remat):
    """jax.checkpoint is semantics-preserving: loss and the updated params
    must match the un-checkpointed step (fp32: exactly, modulo recompute
    ordering — pinned with a tight tolerance)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from prime_tpu.models import get_config
    from prime_tpu.models.llama import init_params
    from prime_tpu.train import default_optimizer, init_train_state, make_train_step

    cfg = get_config("tiny-test")
    optimizer = default_optimizer(learning_rate=1e-2)
    params = init_params(jax.random.PRNGKey(0), cfg, dtype=jnp.float32)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, cfg.vocab_size)
    targets = jnp.roll(tokens, -1, axis=1)
    mask = jnp.ones_like(tokens, dtype=jnp.float32)

    from prime_tpu.models.llama import forward
    from prime_tpu.train.trainer import cross_entropy_loss

    # compare RAW gradients, not post-Adam params: a fresh Adam step
    # normalizes every gradient to ~±lr, so a single ULP-level sign flip at
    # a zero-gradient coordinate would look like a full-update difference
    def loss_with(remat_mode):
        def loss(p):
            logits, _ = forward(p, tokens, cfg, cache=None, remat=remat_mode)
            return cross_entropy_loss(logits, targets, mask)

        return jax.jit(jax.value_and_grad(loss))

    plain_loss, plain_grads = loss_with("none")(params)
    remat_loss, remat_grads = loss_with(remat)(params)
    np.testing.assert_allclose(float(plain_loss), float(remat_loss), rtol=1e-6)
    for plain_leaf, remat_leaf in zip(
        jax.tree.leaves(plain_grads), jax.tree.leaves(remat_grads)
    ):
        np.testing.assert_allclose(
            np.asarray(plain_leaf), np.asarray(remat_leaf), rtol=1e-4, atol=1e-6
        )

    # and the full donated train step compiles + runs under remat
    state, metrics = make_train_step(cfg, optimizer, remat=remat)(
        init_train_state(jax.tree.map(jnp.copy, params), optimizer), tokens, targets, mask
    )
    assert np.isfinite(float(metrics["loss"]))


def test_train_local_remat_cli(tmp_path):
    """--remat drives a real (tiny) local training run end to end."""
    runner = CliRunner()
    with runner.isolated_filesystem(temp_dir=tmp_path):
        result = runner.invoke(
            cli,
            ["train", "local", "-m", "tiny-test", "--steps", "2", "-b", "2",
             "--seq-len", "16", "--remat", "dots", "--name", "remat-run", "--plain"],
        )
        assert result.exit_code == 0, result.output
        assert "loss" in result.output


def test_train_local_lora_with_remat(tmp_path):
    """--remat composes with --lora: the adapter step checkpoints the merged
    forward the same way the full-FT step does."""
    runner = CliRunner()
    with runner.isolated_filesystem(temp_dir=tmp_path):
        result = runner.invoke(
            cli,
            ["train", "local", "-m", "tiny-test", "--steps", "2", "-b", "2",
             "--seq-len", "16", "--lora", "--remat", "full", "--plain",
             "--name", "lora-remat"],
        )
        assert result.exit_code == 0, result.output
        assert "loss" in result.output


def test_text_batches_rejects_tiny_corpus(tmp_path):
    import pytest as _pytest

    from prime_tpu.train.data import text_batches

    data = tmp_path / "tiny.txt"
    data.write_text("ab")
    with _pytest.raises(ValueError, match="need at least"):
        list(text_batches(data, batch=2, seq=128, steps=1))


def test_train_local_rejects_bad_accum_and_reused_name(tmp_path):
    from click.testing import CliRunner

    from prime_tpu.commands.main import cli

    runner = CliRunner()
    bad = runner.invoke(cli, ["train", "local", "--accum", "0", "--output-dir", str(tmp_path)])
    assert bad.exit_code != 0 and "--accum" in bad.output

    args = ["train", "local", "-m", "tiny-test", "--steps", "2", "-b", "2",
            "--seq-len", "16", "--name", "dup", "--output-dir", str(tmp_path), "--plain"]
    assert runner.invoke(cli, args).exit_code == 0
    rerun = runner.invoke(cli, args)
    assert rerun.exit_code != 0 and "already has metrics" in rerun.output


def test_text_batches_exact_window_corpus(tmp_path):
    """A corpus of exactly seq+1 tokens has one valid window and must work."""
    from prime_tpu.evals.tokenizer import ByteTokenizer
    from prime_tpu.train.data import text_batches

    seq = 7
    text = "x" * (seq + 1 - 1)  # byte tokenizer adds a BOS -> seq+1 tokens total
    data = tmp_path / "exact.txt"
    data.write_text(text)
    assert len(ByteTokenizer().encode(text)) == seq + 1
    batches = list(text_batches(data, batch=2, seq=seq, steps=2))
    assert batches[0][0].shape == (2, seq)


def test_train_local_resume_from_checkpoint(tmp_path):
    import json as _json

    from click.testing import CliRunner

    from prime_tpu.commands.main import cli

    runner = CliRunner()
    base = ["train", "local", "-m", "tiny-test", "-b", "2", "--seq-len", "16",
            "--name", "resumable", "--output-dir", str(tmp_path),
            "--checkpoint-every", "2", "--plain"]
    first = runner.invoke(cli, base + ["--steps", "4"])
    assert first.exit_code == 0, first.output

    resumed = runner.invoke(cli, base + ["--steps", "3", "--resume"])
    assert resumed.exit_code == 0, resumed.output
    assert "resumed resumable from step 4" in resumed.output

    rows = [_json.loads(l) for l in (tmp_path / "resumable" / "metrics.jsonl").read_text().splitlines()]
    steps = [r["step"] for r in rows]
    assert steps == [0, 1, 2, 3, 4, 5, 6]  # continuous numbering across resume


def test_train_local_resume_requires_name_and_checkpoints(tmp_path):
    from click.testing import CliRunner

    from prime_tpu.commands.main import cli

    runner = CliRunner()
    no_name = runner.invoke(cli, ["train", "local", "--resume", "--output-dir", str(tmp_path)])
    assert no_name.exit_code != 0 and "--name" in no_name.output
    no_ckpt = runner.invoke(
        cli, ["train", "local", "--resume", "--name", "x", "--output-dir", str(tmp_path)]
    )
    assert no_ckpt.exit_code != 0 and "--checkpoint-every" in no_ckpt.output


def test_train_local_rl_remat_cli(tmp_path):
    """GRPO with --remat: the checkpointed update forward trains end to end."""
    from click.testing import CliRunner

    from prime_tpu.commands.main import cli

    result = CliRunner().invoke(
        cli,
        ["train", "local-rl", "arith", "-m", "tiny-test", "--steps", "2",
         "-g", "2", "-p", "2", "--max-prompt-len", "16", "--max-new-tokens", "4",
         "--remat", "dots", "--name", "rl-remat", "--output-dir", str(tmp_path),
         "--plain"],
    )
    assert result.exit_code == 0, result.output
    assert (tmp_path / "rl-remat" / "metrics.jsonl").exists()


def test_train_local_rl_cli_arith(tmp_path):
    """`prime train local-rl arith`: native GRPO from the CLI — the built-in
    arith env drives rollouts, metrics.jsonl gets one row per step."""
    import json as _json

    from click.testing import CliRunner

    from prime_tpu.commands.main import cli

    result = CliRunner().invoke(
        cli,
        ["train", "local-rl", "arith", "-m", "tiny-test", "--steps", "3",
         "-g", "2", "-p", "2", "--max-prompt-len", "16", "--max-new-tokens", "4",
         "--lr", "1e-3", "--name", "rl-run", "--output-dir", str(tmp_path),
         "--output", "json"],
    )
    assert result.exit_code == 0, result.output
    payload = _json.loads(result.output)
    assert payload["steps"] == 3 and payload["env"] == "arith"
    rows = [
        _json.loads(l)
        for l in (tmp_path / "rl-run" / "metrics.jsonl").read_text().splitlines()
    ]
    assert len(rows) == 3
    assert all("reward_mean" in r and "kl" in r for r in rows)


def test_train_local_rl_rejects_bad_flags(tmp_path):
    from click.testing import CliRunner

    from prime_tpu.commands.main import cli

    runner = CliRunner()
    greedy = runner.invoke(
        cli, ["train", "local-rl", "arith", "--temperature", "0",
              "--output-dir", str(tmp_path)]
    )
    assert greedy.exit_code != 0 and "temperature" in greedy.output
    solo = runner.invoke(
        cli, ["train", "local-rl", "arith", "-g", "1", "--output-dir", str(tmp_path)]
    )
    assert solo.exit_code != 0 and "group_size" in solo.output


def test_train_local_rl_lora_cli(tmp_path):
    """`train local-rl --lora`: GRPO over frozen base, adapter artifact out."""
    import json as _json

    from click.testing import CliRunner

    from prime_tpu.commands.main import cli

    result = CliRunner().invoke(
        cli,
        ["train", "local-rl", "arith", "-m", "tiny-test", "--steps", "2",
         "-g", "2", "-p", "2", "--max-prompt-len", "16", "--max-new-tokens", "4",
         "--lora", "--lora-r", "4", "--name", "rl-lora", "--output-dir",
         str(tmp_path), "--output", "json"],
    )
    assert result.exit_code == 0, result.output
    payload = _json.loads(result.output)
    assert payload["steps"] == 2
    adapter_dir = payload["adapterDir"]
    assert (tmp_path / "rl-lora" / "adapters" / "adapters.npz").exists()
    meta = _json.loads(
        (tmp_path / "rl-lora" / "adapters" / "adapter_config.json").read_text()
    )
    assert meta["r"] == 4 and meta["base_model"] == "tiny-test"
    assert adapter_dir.endswith("adapters")


def test_train_local_cli_context_parallel(tmp_path):
    """--sp shards the sequence over the ring (context parallelism) through
    the real CLI: mesh reported, loss finite, metrics written."""
    from click.testing import CliRunner

    from prime_tpu.commands.main import cli

    result = CliRunner().invoke(
        cli,
        ["train", "local", "-m", "tiny-test", "--steps", "3", "-b", "2",
         "--seq-len", "64", "--slice", "v5e-8", "--sp", "8",
         "--name", "cp-run", "--output-dir", str(tmp_path), "--plain"],
    )
    assert result.exit_code == 0, result.output
    assert "'sp': 8" in result.output and "context-parallel" in result.output
    assert "done:" in result.output
    assert (tmp_path / "cp-run" / "metrics.jsonl").exists()
    # guardrails: --sp without --slice, indivisible seq, per-layer schedules
    bad = CliRunner().invoke(
        cli, ["train", "local", "-m", "tiny-test", "--sp", "8", "--steps", "2"]
    )
    assert bad.exit_code != 0 and "--slice" in bad.output
    bad = CliRunner().invoke(
        cli,
        ["train", "local", "-m", "tiny-test", "--sp", "8", "--slice", "v5e-8",
         "--seq-len", "30", "--steps", "2"],
    )
    assert bad.exit_code != 0 and "divide" in bad.output
    bad = CliRunner().invoke(
        cli,
        ["train", "local", "-m", "tiny-gptoss", "--sp", "8", "--slice", "v5e-8",
         "--seq-len", "64", "--steps", "2"],
    )
    assert bad.exit_code != 0 and "uniform" in bad.output


def test_train_request_models(runner, fake):
    """`prime train request` submits a model request as product feedback
    (reference rl.py:1803)."""
    from prime_tpu.commands.main import cli

    result = runner.invoke(
        cli, ["train", "request", "-m", "meta/llama-4-behemoth", "--context", "RL"]
    )
    assert result.exit_code == 0, result.output
    assert "Thanks" in result.output
    assert any("llama-4-behemoth" in m["message"] for m in fake.misc_plane.feedback)
    # a blank models answer is rejected
    result = runner.invoke(cli, ["train", "request", "-m", "  "])
    assert result.exit_code != 0 and "required" in result.output
