"""CLI tests: CliRunner driving the real `prime` app against the fake backend.

Mirrors the reference's tier-1 CLI testing approach (tests/test_pods_create.py:
CliRunner + isolated HOME + canned fixtures), with the in-process fake control
plane replacing monkeypatched client methods.
"""

import json
import os

import pytest
from click.testing import CliRunner

import prime_tpu.commands._deps as deps
from prime_tpu.commands.main import cli
from prime_tpu.testing import FakeControlPlane

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def fake(monkeypatch, tmp_path):
    fake = FakeControlPlane(pod_ready_after_polls=2)
    monkeypatch.setattr(deps, "transport_override", fake.transport)
    monkeypatch.setenv("PRIME_API_KEY", "test-key")
    monkeypatch.setenv("PRIME_BASE_URL", "https://api.fake")
    return fake


@pytest.fixture
def runner():
    return CliRunner()


def test_help_lists_panels(runner):
    result = runner.invoke(cli, ["--help"])
    assert result.exit_code == 0
    for cmd in ("availability", "pods", "config", "whoami"):
        assert cmd in result.output


def test_availability_tpu_types_plain(runner, fake):
    result = runner.invoke(cli, ["availability", "tpu-types", "--plain"])
    assert result.exit_code == 0, result.output
    lines = result.output.strip().splitlines()
    assert lines[0].startswith("TPU TYPE")
    assert any(line.startswith("v5e") for line in lines)


def test_availability_list_json_filters(runner, fake):
    result = runner.invoke(
        cli, ["availability", "list", "--tpu-type", "v5e", "--min-chips", "8", "--output", "json"]
    )
    assert result.exit_code == 0, result.output
    rows = json.loads(result.output)
    assert rows and all(r["tpuType"] == "v5e" and r["chips"] >= 8 for r in rows)
    assert {"iciTopology", "hosts", "priceHourly"} <= set(rows[0])


def test_availability_disks_plain(runner, fake):
    result = runner.invoke(cli, ["availability", "disks", "--plain"])
    assert result.exit_code == 0
    assert "hyperdisk-balanced" in result.output


def test_pods_create_noninteractive_and_lifecycle(runner, fake):
    result = runner.invoke(
        cli, ["pods", "create", "--slice", "v5e-16", "--name", "trainer", "--yes", "--output", "json"]
    )
    assert result.exit_code == 0, result.output
    pod = json.loads(result.output)
    assert pod["sliceName"] == "v5e-16" and pod["hosts"] == 2 and pod["iciTopology"] == "4x4"
    pod_id = pod["podId"]

    # status polls advance the fake lifecycle
    runner.invoke(cli, ["pods", "status", pod_id, "--plain"])
    result = runner.invoke(cli, ["pods", "status", pod_id, "--output", "json"])
    status = json.loads(result.output)
    assert status["status"] == "ACTIVE"
    assert len(status["sshConnections"]) == 2

    result = runner.invoke(cli, ["pods", "list", "--plain"])
    assert "trainer" in result.output

    result = runner.invoke(cli, ["pods", "terminate", pod_id, "--yes"])
    assert result.exit_code == 0
    result = runner.invoke(cli, ["pods", "history", "--plain"])
    assert "trainer" in result.output


def test_pods_create_wizard_interactive(runner, fake):
    # generation 2 (v5e), slice 4 (v5e-8), offer 1, runtime 1, disk 100, confirm
    result = runner.invoke(
        cli,
        ["pods", "create"],
        input="2\n4\n1\n1\n100\ny\n",
    )
    assert result.exit_code == 0, result.output
    assert "v5e-8" in result.output
    assert len(fake.pods) == 1


def test_pods_create_bad_slice_fails_cleanly(runner, fake):
    result = runner.invoke(cli, ["pods", "create", "--slice", "v9z-8", "--yes"])
    assert result.exit_code != 0
    assert "Unknown TPU generation" in result.output


def test_pods_connect_waits_and_uses_ssh_key(runner, fake, monkeypatch):
    calls = []

    class R:
        returncode = 0

    monkeypatch.setattr("prime_tpu.commands.pods.ssh_runner", lambda args: calls.append(args) or R())
    monkeypatch.setattr("prime_tpu.commands.pods.POLL_INTERVAL_S", 0)
    monkeypatch.setenv("PRIME_SSH_KEY_PATH", "/tmp/key")

    result = runner.invoke(cli, ["pods", "create", "--slice", "v5e-1", "--yes", "--output", "json"])
    pod_id = json.loads(result.output)["podId"]
    result = runner.invoke(cli, ["pods", "connect", pod_id])
    assert result.exit_code == 0, result.output
    assert calls and calls[0][0] == "ssh" and "/tmp/key" in calls[0]


def test_pods_connect_multihost_fanout(runner, fake, monkeypatch):
    calls = []

    class R:
        returncode = 0

    monkeypatch.setattr("prime_tpu.commands.pods.ssh_runner", lambda args: calls.append(args) or R())
    monkeypatch.setattr("prime_tpu.commands.pods.POLL_INTERVAL_S", 0)

    result = runner.invoke(cli, ["pods", "create", "--slice", "v5e-32", "--yes", "--output", "json"])
    pod_id = json.loads(result.output)["podId"]
    fake.make_pod_active(pod_id)
    result = runner.invoke(
        cli, ["pods", "connect", pod_id, "--all-workers", "--command", "hostname"]
    )
    assert result.exit_code == 0, result.output
    assert len(calls) == 4  # v5e-32 = 4 hosts; same command on every worker
    assert all(args[-1] == "hostname" for args in calls)


def test_config_view_and_set(runner, fake, monkeypatch):
    monkeypatch.delenv("PRIME_API_KEY")
    result = runner.invoke(cli, ["config", "set-api-key", "pk-test-1234567890"])
    assert result.exit_code == 0
    result = runner.invoke(cli, ["config", "view", "--output", "json"])
    view = json.loads(result.output)
    assert "1234567890" not in view["api_key"]  # masked


def test_config_contexts_roundtrip(runner, fake):
    assert runner.invoke(cli, ["config", "envs", "save", "prod"]).exit_code == 0
    result = runner.invoke(cli, ["config", "envs", "list", "--output", "json"])
    assert json.loads(result.output) == ["prod"]
    assert runner.invoke(cli, ["config", "envs", "use", "prod"]).exit_code == 0
    assert runner.invoke(cli, ["config", "envs", "delete", "prod"]).exit_code == 0
    result = runner.invoke(cli, ["config", "envs", "use", "missing"])
    assert result.exit_code != 0


def test_whoami_and_teams(runner, fake):
    result = runner.invoke(cli, ["whoami", "--output", "json"])
    assert json.loads(result.output)["email"] == "dev@example.com"
    result = runner.invoke(cli, ["teams", "list", "--plain"])
    assert "research" in result.output
    assert runner.invoke(cli, ["teams", "switch", "team_1"]).exit_code == 0


def test_wallet(runner, fake):
    result = runner.invoke(cli, ["wallet", "--output", "json"])
    assert json.loads(result.output)["balanceUsd"] == 100.0


def test_disks_crud(runner, fake):
    result = runner.invoke(
        cli, ["disks", "create", "--name", "data", "--size-gib", "200", "--output", "json"]
    )
    assert result.exit_code == 0, result.output
    disk = json.loads(result.output)
    assert disk["sizeGib"] == 200
    result = runner.invoke(cli, ["disks", "list", "--plain"])
    assert "data" in result.output
    assert runner.invoke(cli, ["disks", "delete", disk["diskId"], "--yes"]).exit_code == 0


def test_unauthorized_is_actionable(runner, fake, monkeypatch):
    monkeypatch.setenv("PRIME_API_KEY", "wrong")
    result = runner.invoke(cli, ["pods", "list"])
    assert result.exit_code != 0


def test_cli_startup_does_not_import_heavyweights():
    """`prime --help` must not drag in jax/flax or the SDK stacks."""
    import subprocess
    import sys

    code = (
        "import sys\n"
        "import prime_tpu.commands.main as m\n"
        "from click.testing import CliRunner\n"
        "r = CliRunner().invoke(m.cli, ['--help'])\n"
        "assert r.exit_code == 0\n"
        "heavy = ('jax', 'flax', 'optax', 'torch', 'transformers')\n"
        "bad = [mod for mod in heavy if mod in sys.modules]\n"
        "assert not bad, f'heavyweights imported at startup: {bad}'\n"
    )
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True, cwd=REPO_ROOT)
    assert proc.returncode == 0, proc.stderr


def test_compile_cache_is_placed_once_and_from_outside(tmp_path):
    """prime_tpu/__init__.py is the one place that places JAX's persistent
    compile cache: unset -> <checkout>/.jax_cache (whichever of jax and
    prime_tpu is imported first), set -> left alone."""
    import subprocess
    import sys

    def cache_dir(code: str, **env_overrides: str) -> str:
        env = {k: v for k, v in os.environ.items() if k != "JAX_COMPILATION_CACHE_DIR"}
        env.update(env_overrides)
        proc = subprocess.run(
            [sys.executable, "-c", code + "print(jax.config.jax_compilation_cache_dir)"],
            capture_output=True, text=True, cwd=REPO_ROOT, env=env,
        )
        assert proc.returncode == 0, proc.stderr
        return proc.stdout.strip().splitlines()[-1]

    default = os.path.join(REPO_ROOT, ".jax_cache")
    assert cache_dir("import prime_tpu, jax\n") == default
    assert cache_dir("import jax, prime_tpu\n") == default
    outside = str(tmp_path / "cache")
    assert cache_dir("import prime_tpu, jax\n", JAX_COMPILATION_CACHE_DIR=outside) == outside
    assert cache_dir("import jax, prime_tpu\n", JAX_COMPILATION_CACHE_DIR=outside) == outside


def test_api_errors_render_clean_not_traceback(runner, fake, monkeypatch):
    monkeypatch.setenv("PRIME_API_KEY", "wrong")
    result = runner.invoke(cli, ["pods", "list"])
    assert result.exit_code == 1
    assert result.exception is None or isinstance(result.exception, SystemExit)
    assert "Error:" in result.output and "Traceback" not in result.output


def test_pods_create_on_demand_never_picks_spot_offer(runner, fake):
    result = runner.invoke(
        cli, ["pods", "create", "--slice", "v5e-8", "--yes", "--output", "json"]
    )
    assert result.exit_code == 0, result.output
    pod = json.loads(result.output)
    # fake prices spot at 0.4x; on-demand create must not have matched it
    offer_ids = {o["offerId"]: o for o in fake.offers}
    assert pod["spot"] is False


def test_connect_all_workers_propagates_failures(runner, fake, monkeypatch):
    class R:
        def __init__(self, rc):
            self.returncode = rc

    rcs = iter([0, 1, 0, 0])
    monkeypatch.setattr("prime_tpu.commands.pods.ssh_runner", lambda args: R(next(rcs)))
    monkeypatch.setattr("prime_tpu.commands.pods.POLL_INTERVAL_S", 0)
    result = runner.invoke(cli, ["pods", "create", "--slice", "v5e-32", "--yes", "--output", "json"])
    pod_id = json.loads(result.output)["podId"]
    fake.make_pod_active(pod_id)
    result = runner.invoke(cli, ["pods", "connect", pod_id, "--all-workers", "--command", "x"])
    assert result.exit_code == 1


def test_eval_run_and_push_cli(runner, fake, tmp_path, monkeypatch):
    monkeypatch.chdir(tmp_path)
    result = runner.invoke(
        cli,
        [
            "eval", "run", "arith", "-m", "tiny-test",
            "-n", "4", "-b", "2", "--max-new-tokens", "8",
            "--output", "json",
        ],
    )
    assert result.exit_code == 0, result.output
    payload = json.loads(result.output)
    assert payload["metrics"]["num_samples"] == 4.0
    assert payload["evalId"].startswith("eval_")
    assert fake.evals_plane.evaluations[payload["evalId"]]["status"] == "FINALIZED"

    result = runner.invoke(cli, ["eval", "list", "--plain"])
    assert result.exit_code == 0 and "arith" not in result.output  # env shown as id
    result = runner.invoke(cli, ["eval", "samples", payload["evalId"], "--plain"])
    assert result.exit_code == 0

    # push again from the run dir on disk
    result = runner.invoke(cli, ["eval", "push", "--output", "json"])
    assert result.exit_code == 0, result.output


def test_prompt_pickers():
    """utils.prompt: single row short-circuits, assume_default skips I/O."""
    import pytest as _pytest

    from prime_tpu.utils.prompt import confirm, pick, pick_value, prompt_int

    assert pick("t", ["only"]) == "only"
    assert pick("t", ["a", "b", "c"], assume_default=True) == "a"
    assert pick("t", ["a", "b"], default=2, assume_default=True) == "b"
    assert pick_value("t", "given", ["a", "b"]) == "given"
    assert pick_value("t", None, ["a", "b"], assume_default=True) == "a"
    assert prompt_int("n", 7, assume_default=True) == 7
    assert confirm("ok?", assume_yes=True) is True
    with _pytest.raises(Exception, match="nothing to select"):
        pick("t", [])


def test_pods_create_wizard_runtime_and_disk_in_payload(runner, fake):
    result = runner.invoke(
        cli,
        ["pods", "create"],
        input="2\n4\n1\n2\n250\ny\n",  # runtime option 2, disk 250
    )
    assert result.exit_code == 0, result.output
    pod = next(iter(fake.pods.values()))
    assert pod["runtimeVersion"] == "v2-alpha-tpuv5-lite"
    assert pod["diskSizeGib"] == 250


def test_switch_by_slug_id_personal_and_miss(runner, fake):
    """Top-level `prime switch` (reference commands/switch.py): resolves a
    team by slug or id, 'personal' clears the team, unknown targets list
    what's available, and no argument prompts interactively."""
    assert runner.invoke(cli, ["switch", "research"]).exit_code == 0
    # the switch must actually persist: teams list marks team_1 active
    listed = runner.invoke(cli, ["teams", "list", "--plain"]).output
    assert "*" in listed
    assert "Switched to team 'research'" in runner.invoke(cli, ["switch", "team_1"]).output
    assert "personal" in runner.invoke(cli, ["switch", "personal"]).output
    missed = runner.invoke(cli, ["switch", "nope"])
    assert missed.exit_code != 0 and "research" in missed.output
    picked = runner.invoke(cli, ["switch"], input="1\n")
    assert picked.exit_code == 0 and "Switched to team 'research'" in picked.output
    picked = runner.invoke(cli, ["switch"], input="0\n")
    assert picked.exit_code == 0 and "personal" in picked.output
