"""What PR 39 added for Olmo-Hybrid's first pipeline stage: the roofline counts
against hand counts, the four readers on hand-made runs, the manifest's new
entries, the configuration file against the catalog's row, the reference's
causality and control, and the cell's rehearsal on the CPU."""
import importlib
import json
import subprocess
import sys

import numpy as np
import pytest

from benchmark.harness import manifest as mf
from benchmark.systems.serve_http import counter_delta

CELL = "olmo-hybrid-7b-pp2.prefix-sessions"
CONFIG = mf.load_json("configs", "olmo-hybrid-7b-pp2")
C = CONFIG["model"]
NEW_METRICS = ("state_prefix_hit_token_share", "prefix_match_beyond_snapshot_share",
               "prefix_snapshot_byte_share", "state_snapshot_start_share")


# ---- the roofline counts, by hand ------------------------------------------------


def test_parameters_by_hand():
    from benchmark.rooflines import olmo_hybrid as counts

    # ISSUE 39's arithmetic: 3840 x (2880 + 2880 + 5760 + 5760) in, 2 x 3840 x 30, 5760 x 3840 out
    assert counts.delta_params(C) == 3840 * 17280 + 2 * 3840 * 30 + 5760 * 3840 == 88_704_000
    assert counts.attention_params(C) == 4 * 3840 * 3840 == 58_982_400
    assert counts.feed_forward_params(C) == 3 * 3840 * 11008 == 126_812_160
    assert counts.layer_counts(C) == {"full": 4, "linear": 12} and counts.head_dim(C) == 128
    layers = counts.layer_params(C)
    assert layers == 12 * 88_704_000 + 4 * 58_982_400 + 16 * 126_812_160
    assert counts.head_params(C) == 3840 * 100352
    assert counts.weight_bytes(C) == 2 * (layers + 3840 * 100352) == pytest.approx(7.43e9, rel=0.01)
    # with the embedding and the norm and decay vectors: the preset's own count, 8.2 GB
    from prime_tpu.models import get_config

    vectors = 16 * 2 * 3840 + 3840 + 12 * (4 * 11520 + 2 * 30 + 192) + 4 * 2 * 3840
    assert layers + 2 * counts.head_params(C) + vectors == get_config("olmo-hybrid-7b-pp2").param_count


def test_bytes_by_hand():
    from benchmark.rooflines import olmo_hybrid as counts

    assert counts.kv_bytes_per_token(C) == 4 * 2 * 30 * 128 * 2 == 61_440      # ISSUE 39: 61.4 KB a token
    # 12 layers x (30 heads x 96 x 192 float32 + 3 x 11520 bf16): 26.5 MB of state, 0.83 MB of tail
    assert counts.state_bytes_per_row(C) == 12 * (2_211_840 + 69_120) == 27_371_520
    assert 16 * counts.state_bytes_per_row(C) == pytest.approx(0.44e9, rel=0.01)
    assert 16 * 2048 * counts.kv_bytes_per_token(C) == pytest.approx(2.0e9, rel=0.01)
    assert counts.decode_step_bytes(C, 20_000) == counts.weight_bytes(C) + 20_000 * 61_440
    assert counts.mechanism_bytes(C, 16) == 2 * 16 * 27_371_520 == pytest.approx(0.876e9, rel=0.01)
    assert counts.state_step_bytes(C, 16) == 2 * 16 * 2_211_840
    # the stride rule's ratio: one snapshot costs what 446 tokens of K/V cost
    assert counts.state_bytes_per_row(C) // counts.kv_bytes_per_token(C) == 445


# ---- the readers, on hand-made runs --------------------------------------------------


def _counter(name, series):
    return {name: {"series": [{"labels": labels, "value": value} for labels, value in series]}}


def _engine_counters(used, beyond, restored, zero, state_bytes=0.0, held=0.0):
    return {
        **_counter("serve_prefix_match_tokens_total", [({"kind": "used"}, used), ({"kind": "beyond_snapshot"}, beyond)]),
        **_counter("serve_state_starts_total", [({"from": "snapshot"}, restored), ({"from": "zero"}, zero)]),
        **_counter("serve_prefix_state_bytes", [({}, state_bytes)]),
        **_counter("serve_prefix_cache_bytes", [({}, held)]),
    }


def _read(metric, run):
    reader = importlib.import_module(f"benchmark.layer_metrics.{metric}")
    return reader.read({"counter_delta": counter_delta, "config": CONFIG, "model": C, **run})


def test_the_four_readers_on_a_hand_made_window():
    before = _engine_counters(4096.0, 0.0, 0.0, 4.0, 4 * 54.7e6, 4 * 117.6e6)
    after = _engine_counters(4096.0 + 30 * 1024, 30 * 200.0, 30.0, 4.0 + 2, 9 * 27.4e6, 9 * 27.4e6 + 0.6e9)
    records = [{"sent": 11.0 + i, "prompt_ids": [1] * 1300} for i in range(32)]
    records.append({"sent": 5.0, "prompt_ids": [1] * 999})      # before the window
    records.append({"sent": None, "prompt_ids": None})          # never sent
    run = {"counters": (before, after), "window": (10.0, 61.0), "records": records}
    assert _read("state_prefix_hit_token_share", run) == pytest.approx(100 * 30 * 1024 / (32 * 1300))
    assert _read("prefix_match_beyond_snapshot_share", run) == pytest.approx(100 * 200 / 1224)
    assert _read("prefix_snapshot_byte_share", run) == pytest.approx(100 * 9 * 27.4e6 / (9 * 27.4e6 + 0.6e9))
    assert _read("state_snapshot_start_share", run) == pytest.approx(100 * 30 / 32)
    # the parent of the PR that brought the counters: nothing to read, and nothing raised
    bare = {**run, "counters": ({}, {})}
    for metric in NEW_METRICS:
        assert _read(metric, bare) is None, metric
    # a window that admitted and matched nothing, an empty cache
    idle = {**run, "counters": (after, after)}
    assert _read("prefix_match_beyond_snapshot_share", idle) is None
    assert _read("state_snapshot_start_share", idle) is None
    assert _read("state_prefix_hit_token_share", {**run, "records": []}) is None
    empty = _engine_counters(0.0, 0.0, 0.0, 0.0)
    assert _read("prefix_snapshot_byte_share", {**run, "counters": (empty, empty)}) is None


# ---- the manifest ---------------------------------------------------------------------


def test_the_manifest_lists_the_configuration_the_cell_and_the_metrics():
    manifest = mf.load_manifest()
    config = next(c for c in manifest["configs"] if c["name"] == "olmo-hybrid-7b-pp2")
    assert config["reduced"] == ["num_hidden_layers"] == CONFIG["reduced"]
    assert config["source"] == CONFIG["source"] and config["file"].endswith("olmo-hybrid-7b-pp2.json")
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) == (
        CELL, "olmo-hybrid-7b-pp2", "prefix-sessions", 1)
    assert len(cell["why"]) <= 200 and len(config["why"]) <= 200
    listed = {m["name"]: m for m in mf.metrics_for(manifest, "per_layer", CELL)}
    names = [m["name"] for m in manifest["per_layer"]]
    first = names.index(NEW_METRICS[0])  # appended together, in ISSUE 39's order, after what was there
    assert names[first:first + 4] == list(NEW_METRICS) and first >= 43
    for name in NEW_METRICS:
        assert listed[name]["workloads"] == [CELL] and listed[name]["moves"] == "latency_mean_ms"
    assert {listed[name]["layer"] for name in NEW_METRICS[:3]} == {"prefix cache serve/prefix_cache.py kv_pool.py"}
    assert listed["state_snapshot_start_share"]["layer"] == "engine serve/engine.py"
    # the metrics without a list come to the cell by themselves; the other cells' do not
    assert {"decode_step_ms", "decode_hbm_roofline", "device_idle_share", "loop_host_share"} <= set(listed)
    assert not {"prefix_hit_token_share", "recurrent_state_byte_share", "moe_held_pick_share"} & set(listed)
    resolved = mf.resolve_cell(manifest, CELL)
    traffic = resolved["traffic"]
    # the traffic file as it stands (qwen3-4b.prefix-sessions' too); the rate and the cache are the cell's
    assert traffic["generator"] == "tenant_sessions" and traffic["system_tokens"] == 1024
    assert (traffic["tenants"], traffic["turns"], traffic["answer_pad"]) == (4, 3, 256)
    assert traffic["rate_sps"] == resolved["cell"]["traffic_overrides"]["rate_sps"]
    assert 3072 <= resolved["cell"]["serve"]["prefix_cache_mb"] <= 4096


def test_the_configuration_file_holds_the_catalog_rows_keys_as_published():
    period = ["linear_attention"] * 3 + ["full_attention"]
    catalog = {"model_type": "olmo_hybrid", "vocab_size": 100352, "hidden_size": 3840,
               "intermediate_size": 11008, "num_hidden_layers": 32, "num_attention_heads": 30,
               "num_key_value_heads": 30, "hidden_act": "silu", "max_position_embeddings": 65536,
               "attention_bias": False, "rms_norm_eps": 1e-06, "tie_word_embeddings": False,
               "layer_types": period * 8, "linear_num_key_heads": 30, "linear_num_value_heads": 30,
               "linear_key_head_dim": 96, "linear_value_head_dim": 192, "linear_conv_kernel_dim": 4,
               "linear_allow_neg_eigval": True, "rope_parameters": {"rope_theta": None}}
    reduced = {"num_hidden_layers": 16}
    for key, value in catalog.items():
        want = reduced.get(key, value)
        assert CONFIG[key] == want == C[key], key
        assert (key in CONFIG["reduced"]) == (key in reduced)
        if key in reduced:
            assert CONFIG["published"][key] == value and key in CONFIG["why_reduced"]
    assert C["num_hidden_layers_published"] == 32
    # both readings of what the config leaves open, each with the one not taken beside it
    assumed = CONFIG["assumed"]
    assert "NO rotary" in assumed["rotary"] and "not taken" in assumed["rotary"]
    assert "post-norm" in assumed["linear_residual"] and "not taken" in assumed["linear_residual"]
    assert "2 pipeline stages" in CONFIG["deployment"]
    from prime_tpu.models import get_config

    for where in (CONFIG, CONFIG["rehearsal"]):
        preset = get_config(where["preset"])
        for key, attr in CONFIG["preset_fields"].items():
            assert getattr(preset, attr) == where["model"][key], (where["preset"], key)
        assert (preset.rotary, preset.pre_norms, preset.post_norms, preset.qk_norm_full) == (False, False, True, True)
    assert set(CONFIG["preset_fields"]) <= set(C)
    assert CONFIG["serve"] == {"max_slots": 16, "slot_capacity": 2048, "chunk": 8, "warmup": True,
                               "mesh": None, "max_wave": 1}
    limits = CONFIG["limits"]
    assert 0 < limits["logit_gap_mean"] < limits["logit_gap_max"]


# ---- the reference --------------------------------------------------------------------


def test_the_reference_is_causal_and_its_control_differs():
    import jax

    from benchmark.reference import olmo_hybrid as ref

    tiny = CONFIG["rehearsal"]["model"]
    weights = ref.make_weights(tiny, 0, jax.devices())
    prompt = list(range(5, 45))
    a = np.asarray(ref.score(tiny, weights, prompt, [9, 10, 11], 64, 8))
    b = np.asarray(ref.score(tiny, weights, prompt, [9, 10, 400], 64, 8))
    assert a.shape == (3, tiny["vocab_size"])
    assert np.array_equal(a[:2], b[:2])          # a later token changes nothing before it
    # too long for the slot beside its answer: scored on its tail, as the server serves it
    long = list(range(3, 66))
    assert np.array_equal(np.asarray(ref.score(tiny, weights, long, [9, 10, 11], 64, 8)),
                          np.asarray(ref.score(tiny, weights, long[2:], [9, 10, 11], 64, 8)))
    low = np.asarray(ref.score(tiny, weights, prompt, [9, 10, 11], 64, 8, weight_round="int8"))
    assert 0.01 < np.abs(low - a).max() < 5.0
    assert "prime_tpu" not in open(ref.__file__).read().split('"""', 2)[2]  # imports nothing of the program
    # the layers held are the first of the published pattern: 6 linear and 2 full at the tiny size
    assert ref.full_layers(tiny) == (False, False, False, True) * 2


# ---- the rehearsal: the whole cell on the CPU at the tiny preset ------------------------


def test_the_rehearsal_prints_a_line_with_the_new_metrics(tmp_path):
    done = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed", "2147480003",
         "--seconds", "6", "--trace", "1", "--rehearse"],
        cwd=mf.REPO_ROOT, capture_output=True, text=True, timeout=900,
        env={**__import__("os").environ, "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache")},
    )
    assert done.returncode == 0, done.stderr[-2000:]
    last = json.loads(done.stdout.strip().splitlines()[-1])
    assert last["rehearsal"] is True
    line = last["would_be"]
    # `correct` is held to the limits of the PUBLISHED widths; the tiny model in bfloat16 reads
    # far above them, so the rehearsal's verdict is not asserted; what it compared is
    assert line["failed"] == 0 and line["attempted"] > 0
    compared = [json.loads(row) for row in done.stdout.splitlines() if '"compared"' in row][-1]["compared"]
    seen = {c["name"]: c["value"] for c in compared}
    assert all(seen[name] == 0 for name in (
        "bytes_below_stated_precision", "precision_unread", "malformed_streams", "unjoined_prompts",
        "requests_failed_by_engine", "sampled_too_few"))
    for name in NEW_METRICS:
        assert name in line["metrics"], name
    # every turn of the window starts from the tenant's system prompt or deeper
    assert line["metrics"]["state_snapshot_start_share"]["value"] > 50
    assert 0 < line["metrics"]["state_prefix_hit_token_share"]["value"] < 100
    assert 0 < line["metrics"]["prefix_snapshot_byte_share"]["value"] < 100
