"""Release E2E harness (shape of reference scripts/release_e2e.py).

Spins up the live-socket fake control plane, then exercises the release
candidate's CLI end-to-end as real subprocesses: identity, availability,
pods lifecycle, sandbox exec, env push/install, eval run+push, training
dispatch, inference chat. Exits non-zero on the first failure.

Run:  python scripts/release_e2e.py
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

from prime_tpu.testing.live_server import LiveControlPlane  # noqa: E402

PASS = 0
FAIL: list[str] = []


def run_cli(*args: str, env: dict[str, str], check: bool = True, input_text: str | None = None):
    proc = subprocess.run(
        [sys.executable, "-m", "prime_tpu.commands.main", *args],
        capture_output=True,
        text=True,
        env=env,
        input=input_text,
        cwd=str(REPO),
        timeout=300,
    )
    if check and proc.returncode != 0:
        raise RuntimeError(f"prime {' '.join(args)} failed ({proc.returncode}):\n{proc.stderr[-1500:]}")
    return proc


def step(name: str):
    def deco(fn):
        def wrapper(env):
            global PASS
            try:
                fn(env)
                PASS += 1
                print(f"  ok   {name}")
            except Exception as e:
                FAIL.append(name)
                print(f"  FAIL {name}: {e}")

        return wrapper

    return deco


@step("whoami")
def check_whoami(env):
    out = run_cli("whoami", "--output", "json", env=env).stdout
    assert json.loads(out)["email"] == "dev@example.com"


@step("availability list")
def check_availability(env):
    out = run_cli("availability", "list", "--tpu-type", "v5e", "--output", "json", env=env).stdout
    rows = json.loads(out)
    assert any(r["sliceName"] == "v5e-8" for r in rows)


@step("pods create/status/terminate")
def check_pods(env):
    out = run_cli("pods", "create", "--slice", "v5e-16", "--yes", "--output", "json", env=env).stdout
    pod_id = json.loads(out)["podId"]
    run_cli("pods", "status", pod_id, env=env)
    out = run_cli("pods", "status", pod_id, "--output", "json", env=env).stdout
    assert json.loads(out)["status"] == "ACTIVE"
    run_cli("pods", "terminate", pod_id, "--yes", env=env)


@step("sandbox create/exec/delete")
def check_sandbox(env):
    out = run_cli("sandbox", "create", "--name", "e2e", "--output", "json", env=env).stdout
    sid = json.loads(out)["sandboxId"]
    out = run_cli("sandbox", "run", sid, "echo e2e-works", env=env).stdout
    assert "e2e-works" in out
    run_cli("sandbox", "delete", sid, "--yes", env=env)


@step("env push/install")
def check_env(env):
    with tempfile.TemporaryDirectory() as tmp:
        env_dir = Path(tmp) / "e2e-env"
        run_cli("env", "init", "e2e-env", "--dir", str(env_dir), env=env)
        run_cli("env", "push", "--dir", str(env_dir), env=env)
        run_cli("env", "install", "e2e-env", env=env)


@step("eval run (tiny model) + hub push")
def check_eval(env):
    with tempfile.TemporaryDirectory() as tmp:
        out = run_cli(
            "eval", "run", "e2e-arith", "-m", "tiny-test", "-n", "2", "-b", "2",
            "--max-new-tokens", "4", "--output-dir", tmp, "--output", "json",
            env=env,
        ).stdout
        payload = json.loads(out)
        assert payload["metrics"]["num_samples"] == 2.0
        assert payload["evalId"].startswith("eval_")


@step("eval endpoint alias + preflights")
def check_eval_endpoints(env):
    """Round-4 surface: an alias with a base_url runs inference-backed (the
    fake endpoint echoes prompts), an unknown hosted model 402/404s BEFORE
    submission, and local-only flags hard-fail with --hosted."""
    with tempfile.TemporaryDirectory() as tmp:
        table = Path(tmp) / "endpoints.toml"
        table.write_text(
            f'[smoke]\nmodel = "llama3-8b"\nbase_url = "{env["PRIME_INFERENCE_URL"]}"\n'
        )
        out = run_cli(
            "eval", "run", "e2e-arith", "-m", "smoke", "-n", "2", "-b", "2",
            "--no-push", "--endpoints-path", str(table),
            "--output-dir", tmp, "--output", "json",
            env=env,
        ).stdout
        payload = json.loads(out[out.index("{"):])
        assert payload["metrics"]["num_samples"] == 2.0
        rows = [
            json.loads(line)
            for line in open(Path(payload["runDir"]) / "results.jsonl")
            if line.strip()
        ]
        assert all(r["completion"].startswith("echo: ") for r in rows)
        proc = run_cli(
            "eval", "run", "e2e-arith", "-m", "not-a-model", "--hosted",
            env=env, check=False,
        )
        assert proc.returncode != 0 and "Invalid model" in proc.stderr
        proc = run_cli(
            "eval", "run", "e2e-arith", "-m", "llama3-8b", "--hosted", "--kv-quant",
            env=env, check=False,
        )
        assert proc.returncode != 0 and "--kv-quant" in proc.stderr


@step("train dispatch + logs")
def check_train(env):
    with tempfile.TemporaryDirectory() as tmp:
        toml = Path(tmp) / "e2e.toml"
        run_cli("train", "init", "e2e-run", "--out", str(toml), env=env)
        out = run_cli("train", "run", str(toml), "--yes", "--output", "json", env=env).stdout
        run_id = json.loads(out)["runId"]
        out = run_cli("train", "logs", run_id, "--plain", env=env).stdout
        assert "trainer" in out


@step("inference chat")
def check_inference(env):
    out = run_cli(
        "inference", "chat", "llama3-8b", "-m", "ship it", "--no-stream", "--output", "json", env=env
    ).stdout
    assert json.loads(out)["choices"][0]["message"]["content"] == "echo: ship it"


@step("env execution protocol (hub resolve -> load_environment -> run)")
def check_env_execution(env):
    with tempfile.TemporaryDirectory() as tmp:
        run_cli("env", "push", "--dir", str(REPO / "examples" / "verifiers_example"), env=env)
        out = run_cli(
            "eval", "run", "arith-rl", "-m", "tiny-test", "--no-push", "-n", "2", "-b", "2",
            "--output-dir", tmp, "--plain", env=env,
        ).stdout
        assert "Resolved env arith-rl" in out
        out = run_cli("env", "inspect", "arith-rl", "--output", "json", env=env).stdout
        inspected = json.loads(out)
        assert inspected["loadEnvironment"] == "ok" and inspected["hasScorer"]
        actions = json.loads(run_cli("env", "actions", "list", "arith-rl", "--output", "json", env=env).stdout)
        logs = run_cli("env", "actions", "logs", "arith-rl", actions[0]["id"], "--plain", env=env).stdout
        assert "build finished" in logs


@step("images suite (build-vm, hf-cache, bulk, visibility)")
def check_images(env):
    with tempfile.TemporaryDirectory() as tmp:
        out = run_cli(
            "images", "build-vm", "--name", "e2e-vm", "--base-image", "tpu-base",
            "--output", "json", env=env,
        ).stdout
        image_id = json.loads(out)["imageId"]
        run_cli("images", "hf-cache", "--name", "e2e-cache", "--model", "m/llama", env=env)
        manifest = Path(tmp) / "m.json"
        manifest.write_text(json.dumps([{"name": "e2e-bulk", "dockerfileText": "FROM a\n"}]))
        out = run_cli("images", "bulk-push", "--manifest", str(manifest), "--plain", env=env).stdout
        assert "1/1 succeeded" in out
        run_cli("images", "visibility", "public", image_id, env=env)
        detail = json.loads(run_cli("images", "get", image_id, "--output", "json", env=env).stdout)
        assert detail["visibility"] == "public" and detail["artifacts"]


@step("train local (native trainer) + lab charts data")
def check_train_local(env):
    with tempfile.TemporaryDirectory() as tmp:
        out = run_cli(
            "train", "local", "-m", "tiny-test", "--steps", "3", "-b", "2", "--seq-len", "16",
            "--name", "e2e-local", "--output-dir", str(Path(tmp) / "outputs" / "train"),
            "--output", "json", env=env,
        ).stdout
        payload = json.loads(out)
        assert payload["steps"] == 3 and payload["tokens_per_sec"] > 0
        metrics = (Path(tmp) / "outputs" / "train" / "e2e-local" / "metrics.jsonl").read_text()
        assert len(metrics.splitlines()) == 3


@step("GRPO local RL + LoRA adapter -> eval --adapter round trip")
def check_local_rl_lora(env):
    with tempfile.TemporaryDirectory() as tmp:
        out = run_cli(
            "train", "local-rl", "arith", "-m", "tiny-test", "--steps", "2",
            "-g", "2", "-p", "2", "--max-prompt-len", "16", "--max-new-tokens", "4",
            "--lora", "--lora-r", "4", "--name", "e2e-rl",
            "--output-dir", str(Path(tmp) / "rl"), "--output", "json", env=env,
        ).stdout
        payload = json.loads(out)
        assert payload["steps"] == 2 and "adapterDir" in payload
        ev = run_cli(
            "eval", "run", "arith", "-m", "tiny-test", "--adapter", payload["adapterDir"],
            "--no-push", "-n", "2", "-b", "2", "--max-new-tokens", "4",
            "--output-dir", str(Path(tmp) / "evals"), "--plain", env=env,
        )
        assert "accuracy=" in ev.stdout


@step("speculative decoding through eval run")
def check_speculative(env):
    with tempfile.TemporaryDirectory() as tmp:
        out = run_cli(
            "eval", "run", "arith", "-m", "tiny-test", "--speculative", "--draft-len", "4",
            "--no-push", "-n", "2", "-b", "2", "--max-new-tokens", "4",
            "--output-dir", str(Path(tmp) / "evals"), "--plain", env=env,
        )
        assert "accuracy=" in out.stdout
        # sampled speculation (rejection sampling) runs the same surface at
        # a real temperature instead of hard-erroring
        sampled = run_cli(
            "eval", "run", "arith", "-m", "tiny-test", "--speculative", "-t", "0.5",
            "--no-push", "-n", "2", "-b", "2", "--max-new-tokens", "4",
            "--output-dir", str(Path(tmp) / "e2"), "--plain", env=env,
        )
        assert "accuracy=" in sampled.stdout


def _serve_round_trip(env, serve_kwargs: str, sentinel: str) -> None:
    """One OpenAI-compatible round trip against serve_model(<kwargs>)."""
    code = (
        "import httpx\n"
        "from prime_tpu.serve import serve_model\n"
        f"server = serve_model('tiny-test', port=0{serve_kwargs})\n"
        "with server:\n"
        "    r = httpx.post(server.url + '/v1/chat/completions',\n"
        "                   json={'messages': [{'role': 'user', 'content': 'hi'}], 'max_tokens': 3},\n"
        "                   timeout=240)\n"
        "    assert r.status_code == 200, r.text\n"
        "    assert r.json()['usage']['total_tokens'] >= 1\n"
        f"print('{sentinel}')\n"
    )
    proc = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, env=env,
        cwd=str(REPO), timeout=300,
    )
    if proc.returncode != 0:
        raise RuntimeError(proc.stderr[-800:])
    assert sentinel in proc.stdout


@step("serve round trip (OpenAI-compatible)")
def check_serve(env):
    _serve_round_trip(env, "", "serve-ok")


@step("continuous-batching serve with int8 KV cache")
def check_serve_continuous_int8(env):
    _serve_round_trip(
        env,
        ", continuous=True, kv_quant=True, max_slots=2, slot_capacity=256, chunk=4",
        "serve-int8-ok",
    )


def main() -> int:
    server = LiveControlPlane().start()
    with tempfile.TemporaryDirectory() as config_dir:
        env = {
            **os.environ,
            "PRIME_BASE_URL": server.url,
            "PRIME_INFERENCE_URL": f"{server.url}/v1",
            "PRIME_API_KEY": "test-key",
            "PRIME_CONFIG_DIR": config_dir,
            "PRIME_DISABLE_VERSION_CHECK": "1",
            "PYTHONPATH": str(REPO),
            # eval generation must not depend on TPU availability
            "JAX_PLATFORMS": "cpu",
        }
        print(f"release E2E against {server.url}")
        for check in (
            check_whoami,
            check_availability,
            check_pods,
            check_sandbox,
            check_env,
            check_eval,
            check_eval_endpoints,
            check_train,
            check_inference,
            check_env_execution,
            check_images,
            check_train_local,
            check_local_rl_lora,
            check_speculative,
            check_serve,
            check_serve_continuous_int8,
        ):
            check(env)
    server.stop()
    print(f"\n{PASS} passed, {len(FAIL)} failed" + (f": {FAIL}" if FAIL else ""))
    return 1 if FAIL else 0


if __name__ == "__main__":
    raise SystemExit(main())
