"""run.py without a TPU exits non-zero with no result line; the rest of a run,
driven past the look for a chip with the timed path broken underneath (a token
altered where it is produced), comes out not correct."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]


def _run(*args, timeout=600):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("BENCH_RUN", None)
    return subprocess.run([sys.executable, "benchmark/run.py", *args], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=timeout)


def test_no_tpu_no_result_line():
    done = _run("--workload", "qwen3-4b.chat", "--seed", "1", "--seconds", "2", "--trace", "0", timeout=120)
    assert done.returncode != 0
    assert '"correct"' not in done.stdout
    assert "no TPU" in done.stderr


def _would_be(stdout: str) -> dict:
    last = json.loads(stdout.strip().splitlines()[-1])
    assert last.get("rehearsal") is True and "correct" not in last   # never a result line
    return last["would_be"]


@pytest.mark.parametrize("workload", ["qwen3-4b.chat", "qwen3-4b.prefix-sessions"])
def test_rehearsal_runs_the_whole_command(workload):
    done = _run("--workload", workload, "--seed", str(2**31 + 11), "--seconds", "5", "--trace", "1", "--rehearse")
    assert done.returncode == 0, done.stderr[-2000:]
    result = _would_be(done.stdout)
    assert result["attempted"] > 0 and result["failed"] == 0
    assert {"device_idle_share", "slot_occupancy", "ttft_p50_ms"} <= set(result["metrics"])


def test_a_window_that_compiled_is_set_up_and_is_measured_again(tmp_path):
    """On an empty compile cache the first window builds programs inside itself: it is
    printed as discarded, the server starts anew and the window that counts compiles
    nothing. A second run there measures its first window."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache")}
    windows = []
    for seed in ("21", "22"):
        done = subprocess.run([sys.executable, "benchmark/run.py", "--workload", "qwen3-4b.prefix-sessions",
                               "--seed", seed, "--seconds", "5", "--trace", "0", "--rehearse"],
                              cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
        assert done.returncode == 0, done.stderr[-2000:]
        lines = [json.loads(line) for line in done.stdout.strip().splitlines()]
        windows.append([(w["discarded_as_warm_up"], w["compiles_in_window"]["compiles"] > 0, w["setup_s"])
                        for w in lines if "requests_in_window" in w])
        assert _would_be(done.stdout)["metrics"]["setup_s"]["value"] == windows[-1][-1][2]
    assert [w[:2] for w in windows[0]] == [(True, True), (False, False)]
    assert [w[:2] for w in windows[1]] == [(False, False)]
    assert windows[0][1][2] > windows[0][0][2] + 5    # the discarded window is inside setup_s


@pytest.mark.parametrize("degrade", ["int8-kv", "int8-weights"])
def test_the_programs_own_lower_precision_is_not_correct(degrade):
    """The control: the program with its int8 cache or int8 weights switched on holds
    bytes below the precision the configuration states, whatever the logits read."""
    done = _run("--workload", "qwen3-4b.chat", "--seed", "7", "--seconds", "4", "--trace", "0",
                "--rehearse", "--degrade", degrade)
    assert done.returncode == 0, done.stderr[-2000:]
    lines = [json.loads(line) for line in done.stdout.strip().splitlines()]
    assert lines[-1]["correct"] == [False] and "would_be" not in lines[-1]
    compared = {c["name"]: c for line in lines if "compared" in line for c in line["compared"]}
    assert compared["bytes_below_stated_precision"]["value"] > 0 == compared["bytes_below_stated_precision"]["limit"]
    assert compared["precision_unread"]["value"] == 0


def test_served_precision_walks_every_array_the_engine_holds():
    import jax.numpy as jnp

    from benchmark.systems.serve_http import served_precision

    class Engine:
        def __init__(self, cache_dtype):
            self.params = {"w": jnp.zeros((64, 128), jnp.bfloat16), "norm": jnp.ones((128,), jnp.float32)}
            self._anything = (jnp.zeros((2, 64, 64), cache_dtype), jnp.zeros((8192,), jnp.bool_), "text", 3)

    sound = served_precision(Engine(jnp.bfloat16), "bfloat16")
    assert sound["bytes_below_stated"] == 0 and sound["bytes_walked"] == 2 * (64 * 128 + 2 * 64 * 64)
    low = served_precision(Engine(jnp.int8), "bfloat16")
    assert low["narrower"] == {"int8": 2 * 64 * 64}


def test_a_broken_timed_path_is_not_correct(tmp_path):
    """The engine's emit is wrapped so that the last token of every chunk is altered where
    it is produced; limits that the sound rehearsal meets then fail."""
    script = tmp_path / "broken.py"
    script.write_text(f"""
import sys, json
sys.path.insert(0, {str(ROOT)!r}); sys.path.insert(0, {str(ROOT / 'benchmark')!r})
import run
from benchmark.harness import manifest
real = manifest.load_json
def loose(kind, name):
    out = real(kind, name)
    if kind == "configs":
        out["limits"].update(logit_gap_mean=2e-3, logit_gap_max=0.2)
    return out
manifest.load_json = loose
if sys.argv[1] == "broken":
    from prime_tpu.serve import engine
    emit = engine.ContinuousBatchingEngine._emit
    def altered(self, req, token_ids):
        return emit(self, req, [t + 1 if i == len(token_ids) - 1 else t for i, t in enumerate(token_ids)])
    engine.ContinuousBatchingEngine._emit = altered
sys.exit(run.main(["--workload", "qwen3-4b.chat", "--seed", "5", "--seconds", "5", "--trace", "0", "--rehearse"]))
""")
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    verdicts = {}
    for mode in ("sound", "broken"):
        done = subprocess.run([sys.executable, str(script), mode], cwd=ROOT, env=env,
                              capture_output=True, text=True, timeout=600)
        assert done.returncode == 0, done.stderr[-2000:]
        verdicts[mode] = _would_be(done.stdout)["correct"]
    assert verdicts == {"sound": True, "broken": False}
