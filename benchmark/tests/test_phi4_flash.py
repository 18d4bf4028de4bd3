"""What PR 30 added for Phi-4-mini-flash: the roofline counts against hand
counts, the three readers on hand-made runs, the manifest's new entries, the
reference's causality and control, and the cell's rehearsal on the CPU."""
import importlib
import json
import subprocess
import sys

import numpy as np
import pytest

from benchmark.harness import manifest as mf
from benchmark.systems.serve_http import counter_delta

CELL = "phi4-mini-flash.reasoning-long"
CONFIG = mf.load_json("configs", "phi4-mini-flash")
C = CONFIG["model"]
NEW_METRICS = ("cross_half_prefill_share", "cache_mechanism_byte_share", "state_resets_per_admission")


# ---- the roofline counts, by hand ------------------------------------------------


def test_parameters_by_hand():
    from benchmark.rooflines import phi4_flash as counts

    assert counts.feed_forward_params(C) == 3 * 2560 * 10240 == 78_643_200
    # in 2560x10240, x 5120x192, dt 160x5120, out 5120x2560
    assert counts.state_space_params(C) == 26_214_400 + 983_040 + 819_200 + 13_107_200
    assert counts.attention_params(C) == 2560 * 5120 + 2560 * 2560 == 19_660_800
    assert counts.attention_params(C, cross=True) == 2 * 2560 * 2560
    assert counts.memory_unit_params(C) == 2 * 2560 * 5120
    assert counts.layer_counts(C) == {"state_space": 9, "window": 8, "full": 1, "memory": 7, "cross": 7}
    self_half, cross_half = counts.self_half_params(C), counts.cross_half_params(C)
    assert self_half == 9 * 41_123_840 + 9 * 19_660_800 + 18 * 78_643_200
    assert cross_half == 7 * 26_214_400 + 7 * 13_107_200 + 14 * 78_643_200
    # ISSUE 30: the cross half is 1.38 of 3.34 B non-embedding parameters, 41%
    assert cross_half / (self_half + cross_half) == pytest.approx(0.41, abs=0.005)
    assert counts.weight_bytes(C) == pytest.approx(7.70e9, rel=3e-3)  # with the tied head


def test_bytes_and_flops_by_hand():
    from benchmark.rooflines import phi4_flash as counts

    assert counts.kv_bytes_per_token(C) == 5120
    assert counts.decode_step_bytes(C, 47_250) == counts.weight_bytes(C) + 47_250 * 5120
    # 45 live rows of 1050 tokens, rings full: ISSUE 30's 0.94 GB of ring and
    # 0.29 GB of state (read and written), and the row's seven further reads
    ring_and_state = counts.mechanism_bytes(C, 45, 45 * 512)
    assert ring_and_state == 8 * 45 * 512 * 5120 + 9 * 45 * 2 * (327_680 + 30_720)
    assert ring_and_state == pytest.approx(0.94e9 + 0.29e9, rel=0.01)
    with_row = counts.mechanism_bytes(C, 45, 45 * 512, live_tokens=47_250)
    assert with_row - ring_and_state == 7 * 47_250 * 5120
    # the scan's least bytes: ISSUE 30's "about 41 MB a thousand tokens a layer"
    assert counts.scan_bytes(C, 1000) == pytest.approx(41e6, rel=0.02)
    one = counts.prefill_flops(C, [1])
    assert one == pytest.approx(2 * (counts.self_half_params(C) + counts.cross_half_params(C)
                                     + counts.head_params(C)), rel=1e-3)
    # a long prompt pays the cross half once: far less than length x every layer
    long = counts.prefill_flops(C, [2048])
    assert long < 2048 * 2 * (counts.self_half_params(C) + 0.1 * counts.cross_half_params(C))
    assert long > 2048 * 2 * counts.self_half_params(C)


# ---- the readers, on hand-made runs --------------------------------------------------


def _counter(name, series):
    return {name: {"series": [{"labels": labels, "value": value} for labels, value in series]}}


def _read(metric, run):
    reader = importlib.import_module(f"benchmark.layer_metrics.{metric}")
    return reader.read({"counter_delta": counter_delta, "config": CONFIG, "model": C, **run})


def test_cross_half_share_reads_100_where_every_token_runs_every_layer():
    name = "serve_prefill_layer_tokens_total"
    before = _counter(name, [({"half": "self"}, 18.0), ({"half": "cross"}, 14.0)])
    every = _counter(name, [({"half": "self"}, 18.0 + 600 * 18), ({"half": "cross"}, 14.0 + 600 * 14)])
    last = _counter(name, [({"half": "self"}, 18.0 + 600 * 18), ({"half": "cross"}, 14.0 + 14)])
    assert _read("cross_half_prefill_share", {"counters": (before, every)}) == pytest.approx(100.0)
    assert _read("cross_half_prefill_share", {"counters": (before, last)}) == pytest.approx(100 / 600)
    assert _read("cross_half_prefill_share", {"counters": ({}, {})}) is None  # the parent: no counter


def test_state_resets_per_admission():
    def snap(resets, admitted):
        return {**_counter("serve_state_resets_total", [({}, resets)]),
                **_counter("serve_requests_admitted_total", [({}, admitted)])}

    assert _read("state_resets_per_admission", {"counters": (snap(3, 3), snap(160, 160))}) == 1.0
    parent = _counter("serve_requests_admitted_total", [({}, 9.0)])
    assert _read("state_resets_per_admission", {"counters": ({}, parent)}) is None


def test_cache_mechanism_share_prices_the_live_rows():
    from benchmark.rooflines import phi4_flash as counts

    # two requests in their slots for the whole span: 200 + 100/2 and 900 + 300/2 tokens held
    def timeline(prompt, tokens):
        return {"start_unix_s": 1000.0, "duration_s": 50.0, "prompt_tokens": prompt,
                "events": [{"event": "admitted", "t_s": 0.1}, {"event": "first_token", "t_s": 0.5},
                           {"event": "completed", "t_s": 50.0, "tokens": tokens}]}

    run = {"flights": {"a": timeline(200, 100), "b": timeline(900, 300)}, "clock_offset": 990.0,
           "trace_span": (20.0, 24.0), "counters": ({}, _counter("serve_cache_bytes", [({"kind": "ring"}, 1.0)]))}
    mechanism = counts.mechanism_bytes(C, 2.0, 250 + 512, 250 + 1050)
    want = 100.0 * mechanism / (counts.decode_step_bytes(C, 1300) + mechanism)
    assert _read("cache_mechanism_byte_share", run) == pytest.approx(want)
    assert 0.5 < want < 1.5  # two rows beside 7.7 GB of weights: about 1%
    assert _read("cache_mechanism_byte_share", {**run, "counters": ({}, {})}) is None  # the parent
    assert _read("cache_mechanism_byte_share", {**run, "flights": {}}) is None


# ---- the manifest ---------------------------------------------------------------------


def test_the_manifest_lists_the_configuration_the_cell_and_the_metrics():
    manifest = mf.load_manifest()
    config = next(c for c in manifest["configs"] if c["name"] == "phi4-mini-flash")
    assert config["reduced"] == [] == CONFIG["reduced"] and config["file"].endswith("phi4-mini-flash.json")
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("phi4-mini-flash", "reasoning-long", 1)
    assert len(cell["why"]) <= 200
    listed = {m["name"]: m for m in mf.metrics_for(manifest, "per_layer", CELL)}
    for name in NEW_METRICS:
        assert listed[name]["workloads"] == [CELL] and listed[name]["source"] == "program_counter"
    # the metrics without a list come to the cell by themselves
    assert {"decode_step_ms", "decode_hbm_roofline", "device_idle_share", "loop_host_share"} <= set(listed)
    resolved = mf.resolve_cell(manifest, CELL)
    traffic = resolved["traffic"]
    assert traffic["generator"] == "poisson_lognormal" and traffic["ramp_s"] == 25.0
    assert (traffic["prompt"], traffic["answer"], traffic["max_total"]) == (
        {"median": 512, "sigma": 0.6, "min": 128, "max": 2048},
        {"median": 768, "sigma": 0.5, "min": 256, "max": 2048}, 4096)
    assert resolved["cell"]["serve"] == {"prefix_cache_mb": 0}
    assert traffic["rate_rps"] == resolved["cell"]["traffic_overrides"]["rate_rps"]


def test_the_configuration_file_holds_the_catalog_rows_keys_as_published():
    published = {"embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560, "intermediate_size": 10240,
                 "layer_norm_eps": 1e-05, "max_position_embeddings": 262144, "mb_per_layer": 2,
                 "model_type": "phi4flash", "num_attention_heads": 40, "num_hidden_layers": 32,
                 "num_key_value_heads": 20, "resid_pdrop": 0, "sliding_window": 512,
                 "tie_word_embeddings": True, "mlp_bias": False, "lm_head_bias": False,
                 "vocab_size": 200064}
    for key, value in published.items():
        assert CONFIG[key] == value == C[key], key
    from prime_tpu.models import get_config

    preset = get_config(CONFIG["preset"])
    for key, attr in CONFIG["preset_fields"].items():
        assert getattr(preset, attr) == C[key], key
    assert preset.d_inner == C["ssm_expand"] * C["hidden_size"] and preset.param_count == 3_852_562_944
    limits = CONFIG["limits"]
    assert 0 < limits["logit_gap_mean"] < limits["logit_gap_max"]


# ---- the reference --------------------------------------------------------------------


def test_the_reference_is_causal_and_its_control_differs():
    import jax

    from benchmark.reference import phi4_flash as ref

    tiny = CONFIG["rehearsal"]["model"]
    weights = ref.make_weights(tiny, 0, jax.devices())
    prompt = list(range(5, 25))  # longer than the tiny window of 8
    a = np.asarray(ref.score(tiny, weights, prompt, [9, 10, 11], 32, 8))
    b = np.asarray(ref.score(tiny, weights, prompt, [9, 10, 400], 32, 8))
    assert a.shape == (3, tiny["vocab_size"])
    assert np.array_equal(a[:2], b[:2])          # a later token changes nothing before it
    low = np.asarray(ref.score(tiny, weights, prompt, [9, 10, 11], 32, 8, weight_round="int8"))
    assert 0.01 < np.abs(low - a).max() < 5.0
    assert "prime_tpu" not in open(ref.__file__).read().split('"""', 2)[2]  # imports nothing of the program


# ---- the rehearsal: the whole cell on the CPU at the tiny preset ------------------------


def test_the_rehearsal_prints_a_correct_line_with_the_new_metrics(tmp_path):
    done = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed", "2147480003",
         "--seconds", "5", "--trace", "1", "--rehearse"],
        cwd=mf.REPO_ROOT, capture_output=True, text=True, timeout=900,
        env={**__import__("os").environ, "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache")},
    )
    assert done.returncode == 0, done.stderr[-2000:]
    last = json.loads(done.stdout.strip().splitlines()[-1])
    assert last["rehearsal"] is True
    line = last["would_be"]
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    for name in NEW_METRICS:
        assert name in line["metrics"], name
    assert line["metrics"]["state_resets_per_admission"]["value"] == 1.0
    # the cross half ran at one position a prompt: far under 100
    assert 0 < line["metrics"]["cross_half_prefill_share"]["value"] < 20
