"""What PR 34 added for Qwen3-Next's share: the roofline counts against hand
counts, the five readers on hand-made runs, the manifest's new entries, the
configuration file against the catalog's row, the reference's causality and
control, and the cell's rehearsal on the CPU."""
import importlib
import json
import subprocess
import sys

import numpy as np
import pytest

from benchmark.harness import manifest as mf
from benchmark.systems.serve_http import counter_delta

CELL = "qwen3-next-ep2.document-chat"
CONFIG = mf.load_json("configs", "qwen3-next-ep2")
C = CONFIG["model"]
NEW_METRICS = ("recurrent_state_byte_share", "decode_mechanism_hbm_roofline", "moe_held_pick_share",
               "moe_rows_per_touched_expert", "prompt_path_busy_share")


# ---- the roofline counts, by hand ------------------------------------------------


def test_parameters_by_hand():
    from benchmark.rooflines import qwen3_next as counts

    # ISSUE 34's arithmetic: in_proj_qkvz 2048 x 12288, in_proj_ba 2048 x 64, out_proj 4096 x 2048
    assert counts.delta_params(C) == 25_165_824 + 131_072 + 8_388_608
    # q_proj 2048 x 8192 (query and gate), k_proj and v_proj 2048 x 512 each, o_proj 4096 x 2048
    assert counts.attention_params(C) == 16_777_216 + 2 * 1_048_576 + 8_388_608
    assert counts.expert_params(C) == 3 * 2048 * 512 == 3_145_728
    # the router at its PUBLISHED width, the shared expert, its gate vector
    assert counts.feed_forward_params_outside_experts(C) == 2048 * 512 + 3_145_728 + 2048
    assert counts.layer_counts(C) == {"full": 1, "linear": 3}
    outside = counts.params_outside_experts(C)
    assert outside == 3 * 33_685_504 + 27_262_976 + 4 * 4_196_352 == 145_104_896
    assert counts.head_params(C) == 2048 * 75968
    assert counts.weight_bytes(C) == 2 * (outside + 2048 * 75968) == pytest.approx(0.60e9, rel=0.01)
    # with the embedding and the 256 held experts a layer: the preset's own count
    from prime_tpu.models import get_config

    whole = outside + 2 * counts.head_params(C) + 4 * 256 * counts.expert_params(C)
    norms = 4 * 2 * 2048 + 2048 + 3 * (4 * 8192 + 2 * 32 + 128) + 2 * 256
    assert whole + norms == get_config("qwen3-next-ep2").param_count == 3_677_613_120


def test_bytes_by_hand():
    from benchmark.rooflines import qwen3_next as counts

    assert counts.kv_bytes_per_token(C) == 2 * 2 * 256 * 2 == 2048           # ISSUE 34: 2 KB a token
    # 3 layers x (32 heads x 128 x 128 float32 + 3 x 8192 bf16): 6.29 MB of state, 0.15 MB of tail a slot
    assert counts.state_bytes_per_row(C) == 3 * (2_097_152 + 49_152)
    assert 128 * counts.state_bytes_per_row(C) == pytest.approx(0.81e9 + 0.019e9, rel=0.01)
    assert counts.decode_step_bytes(C, 157_500) == counts.weight_bytes(C) + 157_500 * 2048
    assert counts.mechanism_bytes(C, 90) == 2 * 90 * counts.state_bytes_per_row(C) == pytest.approx(1.16e9, rel=0.01)
    assert counts.expert_bytes(C, 4 * 213) == pytest.approx(5.36e9, rel=0.01)  # ISSUE 34: 213 of 256 a layer


# ---- the readers, on hand-made runs --------------------------------------------------


def _counter(name, series):
    return {name: {"series": [{"labels": labels, "value": value} for labels, value in series]}}


def _moe(local, elsewhere, touched, calls, rows=0.0):
    phase = {"phase": "decode"}
    return {
        **_counter("serve_moe_pairs_total", [({"kind": "local", **phase}, local), ({"kind": "zero", **phase}, 0.0),
                                             ({"kind": "elsewhere", **phase}, elsewhere),
                                             ({"kind": "local", "phase": "prefill"}, 7e6)]),
        **_counter("serve_moe_experts_touched_total", [(phase, touched)]),
        **_counter("serve_moe_rows_computed_total", [(phase, rows)]),
        **_counter("serve_moe_layer_calls_total", [(phase, calls)]),
    }


def _read(metric, run):
    reader = importlib.import_module(f"benchmark.layer_metrics.{metric}")
    return reader.read({"counter_delta": counter_delta, "config": CONFIG, "model": C, **run})


def _timeline(prompt, tokens):
    return {"start_unix_s": 1000.0, "duration_s": 50.0, "prompt_tokens": prompt,
            "events": [{"event": "admitted", "t_s": 0.1}, {"event": "first_token", "t_s": 0.5},
                       {"event": "completed", "t_s": 50.0, "tokens": tokens}]}


def test_the_expert_counters_read_the_share_and_the_fill_of_a_group():
    run = {"counters": (_moe(100.0, 90.0, 40.0, 8.0), _moe(100.0 + 4500, 90.0 + 4500, 40.0 + 2130, 8.0 + 40))}
    assert _read("moe_held_pick_share", run) == pytest.approx(50.0)
    assert _read("moe_rows_per_touched_expert", run) == pytest.approx(4500 / 2130)
    assert _read("moe_held_pick_share", {"counters": ({}, {})}) is None  # the parent: no counters
    assert _read("moe_rows_per_touched_expert", {"counters": ({}, {})}) is None


def test_the_state_share_prices_the_live_rows():
    from benchmark.rooflines import qwen3_next as counts

    # two requests in their slots for the whole span: 1500 + 200/2 and 900 + 300/2 tokens held
    held = _counter("serve_cache_bytes", [({"kind": "full"}, 1.0e9), ({"kind": "state"}, 0.83e9)])
    run = {"flights": {"a": _timeline(1500, 200), "b": _timeline(900, 300)}, "clock_offset": 990.0,
           "trace_span": (20.0, 24.0), "counters": ({}, held)}
    mechanism = counts.mechanism_bytes(C, 2.0)
    want = 100.0 * mechanism / (counts.decode_step_bytes(C, 1600 + 1050) + mechanism)
    assert _read("recurrent_state_byte_share", run) == pytest.approx(want)
    assert 3.5 < want < 5.0  # two rows' 12.9 MB in and out beside 0.6 GB of weights
    no_state = _counter("serve_cache_bytes", [({"kind": "full"}, 1.0e9), ({"kind": "state"}, 0.0)])
    assert _read("recurrent_state_byte_share", {**run, "counters": ({}, no_state)}) is None
    assert _read("recurrent_state_byte_share", {**run, "counters": ({}, {})}) is None  # the parent
    assert _read("recurrent_state_byte_share", {**run, "flights": {}}) is None


def test_the_mechanism_roofline_counts_what_a_step_cannot_avoid():
    from benchmark.rooflines import qwen3_next as counts

    # a 4 s span: 30 decode events of 8 steps in 3.6 s, 15 ms a step; 240 steps x 4 layers of
    # expert calls that touched 200 experts each
    trace = {"devices": 1, "programs": {"decode": {"seconds": 3.6, "events": 30}}}
    run = {"flights": {"a": _timeline(1500, 200), "b": _timeline(900, 300)}, "clock_offset": 990.0,
           "trace_span": (20.0, 24.0), "trace": trace, "serve": {"chunk": 8},
           "peaks": {"hbm_bytes_per_s": 819e9},
           "trace_counters": (_moe(0.0, 0.0, 0.0, 0.0), _moe(9e4, 9e4, 240 * 4 * 200.0, 240 * 4.0))}
    least = (counts.decode_step_bytes(C, 2650) + counts.expert_bytes(C, 800) + counts.mechanism_bytes(C, 2.0))
    want = 100.0 * least / 819e9 / 15e-3
    assert _read("decode_mechanism_hbm_roofline", run) == pytest.approx(want)
    assert 40 < want < 50  # 5.0 GB of touched experts lead: 5.67 GB in 15 ms
    assert _read("decode_mechanism_hbm_roofline", {**run, "trace_counters": ({}, {})}) is None
    assert _read("decode_mechanism_hbm_roofline", {**run, "flights": {}}) is None
    assert _read("decode_mechanism_hbm_roofline", {**run, "trace": None}) is None


def test_the_prompt_paths_share_of_the_busy_device():
    programs = {"decode": {"seconds": 2.7, "events": 30}, "chunk_prefill": {"seconds": 0.9, "events": 12},
                "finalize": {"seconds": 0.02, "events": 9}}
    trace = {"programs": programs, "first_device": {"busy_s": 3.68}}
    assert _read("prompt_path_busy_share", {"trace": trace}) == pytest.approx(100 * 0.92 / 3.68)
    assert _read("prompt_path_busy_share", {"trace": None}) is None
    none = {"programs": {"chunk_prefill": {"seconds": 0.0, "events": 0}}, "first_device": {"busy_s": 3.0}}
    assert _read("prompt_path_busy_share", {"trace": none}) is None


# ---- the manifest ---------------------------------------------------------------------


def test_the_manifest_lists_the_configuration_the_cell_and_the_metrics():
    manifest = mf.load_manifest()
    config = next(c for c in manifest["configs"] if c["name"] == "qwen3-next-ep2")
    assert config["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"] == CONFIG["reduced"]
    assert config["source"] == CONFIG["source"] and config["file"].endswith("qwen3-next-ep2.json")
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("qwen3-next-ep2", "document-chat", 1)
    assert len(cell["why"]) <= 200
    listed = {m["name"]: m for m in mf.metrics_for(manifest, "per_layer", CELL)}
    assert [m["name"] for m in manifest["per_layer"][-5:]] == list(NEW_METRICS)  # appended, in ISSUE 34's order
    for name in NEW_METRICS:
        assert listed[name]["workloads"] == [CELL]
    # the metrics without a list come to the cell by themselves; the other cells' do not
    assert {"decode_step_ms", "decode_hbm_roofline", "device_idle_share", "loop_host_share"} <= set(listed)
    assert not {"moe_zero_pick_share", "cache_mechanism_byte_share", "prefill_flops_roofline"} & set(listed)
    resolved = mf.resolve_cell(manifest, CELL)
    traffic = resolved["traffic"]
    assert traffic["generator"] == "poisson_lognormal" and traffic["ramp_s"] == 15.0
    assert (traffic["shape_seed"], traffic["trace_offset_s"], traffic["trace_s"]) == (34, 2.0, 4.0)
    assert (traffic["prompt"], traffic["answer"], traffic["answer_pad"]) == (
        {"median": 1536, "sigma": 0.6, "min": 256, "max": 3072},
        {"median": 384, "sigma": 0.5, "min": 128, "max": 1024}, 1024)
    # ISSUE 34's letter; the two role words are outside the clip, and at the cell's rate this
    # shape_seed draws no request whose prompt, role words and answer pass the slot
    assert traffic["max_total"] == CONFIG["serve"]["slot_capacity"] == 4096
    from benchmark.generators import poisson_lognormal

    sessions = poisson_lognormal.generate(traffic, 1, 51.0, C["vocab_size"])["sessions"]
    assert len(sessions) == 396
    assert max(len(s["turns"][0]["new_messages"][0]["ids"]) + 2 + s["turns"][0]["max_tokens"]
               for s in sessions) == 4026
    assert resolved["cell"]["serve"] == {"prefix_cache_mb": 0} and CONFIG["serve"]["max_wave"] == 1
    assert traffic["rate_rps"] == resolved["cell"]["traffic_overrides"]["rate_rps"]


def test_the_configuration_file_holds_the_catalog_rows_keys_as_published():
    catalog = {"decoder_sparse_step": 1, "full_attention_interval": 4, "head_dim": 256, "hidden_act": "silu",
               "hidden_size": 2048, "intermediate_size": 5120, "linear_conv_kernel_dim": 4,
               "linear_key_head_dim": 128, "linear_num_key_heads": 16, "linear_num_value_heads": 32,
               "linear_value_head_dim": 128, "max_position_embeddings": 262144, "mlp_only_layers": [],
               "model_type": "qwen3_next", "moe_intermediate_size": 512, "norm_topk_prob": True,
               "num_attention_heads": 16, "num_experts": 512, "num_experts_per_tok": 10,
               "num_hidden_layers": 48, "num_key_value_heads": 2, "partial_rotary_factor": 0.25,
               "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 10000000,
               "shared_expert_intermediate_size": 512, "tie_word_embeddings": False,
               "use_sliding_window": False, "vocab_size": 151936}
    reduced = {"num_hidden_layers": 4, "num_experts": 256, "vocab_size": 75968}
    for key, value in catalog.items():
        want = reduced.get(key, value)
        assert CONFIG[key] == want == C[key], key
        assert (key in CONFIG["reduced"]) == (key in reduced)
        if key in reduced:
            assert CONFIG["published"][key] == value and key in CONFIG["why_reduced"]
    assert (C["num_experts_published"], C["vocab_size_published"], C["first_expert"]) == (512, 151936, 0)
    from prime_tpu.models import get_config

    for where in (CONFIG, CONFIG["rehearsal"]):
        preset = get_config(where["preset"])
        for key, attr in CONFIG["preset_fields"].items():
            assert getattr(preset, attr) == where["model"][key], (where["preset"], key)
    assert set(CONFIG["preset_fields"]) <= set(C)
    # ISSUE 34's serve block, and a prompt a wave (about_serve says what that is for)
    assert CONFIG["serve"] == {"max_slots": 128, "slot_capacity": 4096, "chunk": 8, "warmup": True,
                               "mesh": None, "max_wave": 1}
    limits = CONFIG["limits"]
    assert 0 < limits["logit_gap_mean"] < limits["logit_gap_max"]


# ---- the reference --------------------------------------------------------------------


def test_the_reference_is_causal_and_its_control_differs():
    import jax

    from benchmark.reference import qwen3_next as ref

    tiny = CONFIG["rehearsal"]["model"]
    weights = ref.make_weights(tiny, 0, jax.devices())
    prompt = list(range(5, 45))
    a = np.asarray(ref.score(tiny, weights, prompt, [9, 10, 11], 64, 8))
    b = np.asarray(ref.score(tiny, weights, prompt, [9, 10, 400], 64, 8))
    assert a.shape == (3, tiny["vocab_size"])
    assert np.array_equal(a[:2], b[:2])          # a later token changes nothing before it
    # two ids too long for the slot beside its answer: scored on its tail, as the server serves it
    long = list(range(3, 66))
    assert len(long) + 3 == 64 + 2
    assert np.array_equal(np.asarray(ref.score(tiny, weights, long, [9, 10, 11], 64, 8)),
                          np.asarray(ref.score(tiny, weights, long[2:], [9, 10, 11], 64, 8)))
    low = np.asarray(ref.score(tiny, weights, prompt, [9, 10, 11], 64, 8, weight_round="int8"))
    assert 0.01 < np.abs(low - a).max() < 5.0
    assert "prime_tpu" not in open(ref.__file__).read().split('"""', 2)[2]  # imports nothing of the program


# ---- the rehearsal: the whole cell on the CPU at the tiny preset ------------------------


def test_the_rehearsal_prints_a_correct_line_with_the_new_metrics(tmp_path):
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
    # `correct` is held to the limits of the PUBLISHED widths (logit_gap_mean 0.016): the tiny
    # model, hidden 64 and a top-3 of 8 experts in bfloat16, flips a pick every few tokens and
    # reads 0.05-0.15, so the rehearsal's verdict is not asserted; what it compared is
    assert line["failed"] == 0 and line["attempted"] > 0
    compared = [json.loads(row) for row in done.stdout.splitlines() if '"compared"' in row][-1]["compared"]
    seen = {c["name"]: c["value"] for c in compared}
    assert 0.0 < seen["logit_gap_mean"] < 0.5 and seen["logit_gap_max"] < 5.0
    assert all(seen[name] == 0 for name in (
        "bytes_below_stated_precision", "precision_unread", "malformed_streams", "unjoined_prompts",
        "requests_failed_by_engine", "sampled_too_few"))
    # the counters' readers always find something; the two that need decode steps inside the
    # traced span find them on the chip (on the CPU a 2 s span may hold a compile and nothing else)
    for name in ("recurrent_state_byte_share", "moe_held_pick_share", "moe_rows_per_touched_expert"):
        assert name in line["metrics"], name
    # 4 of 8 experts are held, from expert 2: about half of a top-3's picks, a few rows an expert
    assert 25 < line["metrics"]["moe_held_pick_share"]["value"] < 75
    assert 1.0 <= line["metrics"]["moe_rows_per_touched_expert"]["value"] < 4.0
