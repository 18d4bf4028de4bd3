"""What PR 42 added for Laguna-S-2.1's share: the roofline counts against hand
counts, the four readers on hand-made runs, the manifest's new entries (and
every ``why`` of the manifest held to the driver's rule), the configuration file
against the catalog's row, the reference's causality, window and control, and the
cell's rehearsal on the CPU."""
import importlib
import json
import subprocess
import sys

import numpy as np
import pytest

from benchmark.harness import manifest as mf
from benchmark.systems.serve_http import counter_delta

CELL = "laguna-s-ep2.repo-context"
CONFIG = mf.load_json("configs", "laguna-s-ep2")
C = CONFIG["model"]
NEW_METRICS = ("full_row_kv_byte_share", "window_columns_spared_share", "decode_touched_hbm_roofline")
# the review round's: the prompt path, which is most of this cell's busy chip (PERF.md section 5)
PROMPT_METRIC = "prefill_ms_per_chunk"
REDUCED = ["num_hidden_layers", "num_experts", "vocab_size", "layer_types", "mlp_layer_types",
           "gating_types", "num_attention_heads_per_layer"]


# ---- the roofline counts, by hand ------------------------------------------------


def test_parameters_by_hand():
    from benchmark.rooflines import laguna_s as counts

    # ISSUE 42's arithmetic. Full attention: wq, wo 3072 x 6144 each, wk, wv 3072 x 1024 each,
    # the head gate 3072 x 48; window attention: wq, wo 3072 x 9216 each, the gate 3072 x 72
    assert counts.attention_params(C, 48) == 2 * 18_874_368 + 2 * 3_145_728 + 147_456 == 44_187_648
    assert counts.attention_params(C, 72) == 2 * 28_311_552 + 2 * 3_145_728 + 221_184 == 63_135_744
    assert counts.dense_params(C) == 3 * 3072 * 12288 == 113_246_208
    assert counts.expert_params(C) == 3 * 3072 * 1024 == 9_437_184
    # the router at its PUBLISHED width and the shared expert
    assert counts.sparse_params_outside_experts(C) == 3072 * 256 + 9_437_184
    assert counts.layer_counts(C) == {"full": 2, "window": 3, "dense": 1, "sparse": 4}
    outside = counts.params_outside_experts(C)
    assert outside == 2 * 44_187_648 + 3 * 63_135_744 + 113_246_208 + 4 * 10_223_616 == 431_923_200
    assert counts.head_params(C) == 3072 * 50176 == 154_140_672
    assert counts.weight_bytes(C) == 2 * (outside + 154_140_672) == pytest.approx(1.17e9, rel=0.01)
    # with the embedding, the 128 held experts a layer and the norms: the preset's own count,
    # 5572 M parameters, 11.14 GB in bfloat16
    from prime_tpu.models import get_config

    assert counts.total_params(C) == get_config("laguna-s-ep2").param_count == 5_572_076_544
    assert 2 * counts.total_params(C) == pytest.approx(11.14e9, rel=0.001)
    # layers 1-3 are 1281.3 M each, layer 4 1262.4 M, layer 0 157.4 M (ISSUE 42)
    held = 128 * 9_437_184
    assert 63_135_744 + 10_223_616 + held == pytest.approx(1281.3e6, rel=1e-4)
    assert 44_187_648 + 10_223_616 + held == pytest.approx(1262.4e6, rel=1e-4)
    assert 44_187_648 + 113_246_208 == pytest.approx(157.4e6, rel=1e-3)


def test_bytes_by_hand():
    from benchmark.rooflines import laguna_s as counts

    assert counts.column_bytes(C) == 2 * 8 * 128 * 2 == 4096      # ISSUE 42: 4096 B of K/V a token a layer
    assert counts.kv_bytes_per_token(C) == 2 * 4096               # the two FULL layers' rows only
    assert 32 * 8192 * counts.kv_bytes_per_token(C) == pytest.approx(2.147e9, rel=0.001)
    assert counts.ring_bytes_per_row(C) == 3 * 512 * 4096
    assert 32 * counts.ring_bytes_per_row(C) == pytest.approx(0.20e9, rel=0.01)  # rings 0.20 GB at 32 slots
    assert counts.decode_step_bytes(C, 80_000) == counts.weight_bytes(C) + 80_000 * 8192
    assert counts.expert_bytes(C, 4 * 70) == pytest.approx(5.28e9, rel=0.01)     # ISSUE 42: 70 of 128 a layer
    assert counts.mechanism_bytes(C, 3 * 20 * 512) == 20 * counts.ring_bytes_per_row(C)


# ---- the readers, on hand-made runs --------------------------------------------------


def _counter(name, series):
    return {name: {"series": [{"labels": labels, "value": value} for labels, value in series]}}


def _columns(full, window, spared):
    return _counter("serve_decode_attn_columns_total", [
        ({"kind": "full"}, full), ({"kind": "window"}, window), ({"kind": "window_spared"}, spared)])


def _moe(touched, calls):
    phase = {"phase": "decode"}
    return {**_counter("serve_moe_experts_touched_total", [(phase, touched), ({"phase": "prefill"}, 9e5)]),
            **_counter("serve_moe_layer_calls_total", [(phase, calls), ({"phase": "prefill"}, 4e3)])}


def _read(metric, run):
    reader = importlib.import_module(f"benchmark.layer_metrics.{metric}")
    return reader.read({"counter_delta": counter_delta, "config": CONFIG, "model": C, **run})


def test_the_column_counters_read_the_rows_share_and_the_reach_past_the_window():
    # 1000 steps of 20 live rows at 4000 positions: 2 full layers see them all, 3 sliding layers 512
    full, window, spared = 2 * 20 * 4000 * 1000.0, 3 * 20 * 512 * 1000.0, 3 * 20 * 3488 * 1000.0
    run = {"counters": (_columns(5.0, 7.0, 9.0), _columns(5.0 + full, 7.0 + window, 9.0 + spared))}
    assert _read("full_row_kv_byte_share", run) == pytest.approx(100 * 8000 / (8000 + 1536))
    assert _read("window_columns_spared_share", run) == pytest.approx(100 * 3488 / 4000)
    assert full + window + spared == 5 * 20 * 4000 * 1000.0  # layers x live positions
    for metric in ("full_row_kv_byte_share", "window_columns_spared_share"):
        assert _read(metric, {"counters": ({}, {})}) is None  # the parent: no counter
        assert _read(metric, {"counters": (_columns(1, 1, 1), _columns(1, 1, 1))}) is None  # no decode step


def _traced(step_ms, steps, touched_a_layer, rows, positions):
    """A 4 s span of ``steps`` decode steps at ``step_ms`` each."""
    events = steps // 8
    trace = {"devices": 1, "programs": {"decode": {"seconds": steps * step_ms / 1e3, "events": events}}}
    full, window = 2 * rows * positions * steps, 3 * rows * min(positions, 512) * steps
    before = {**_moe(11.0, 13.0), **_columns(1.0, 2.0, 3.0)}
    after = {**_moe(11.0 + 4 * touched_a_layer * steps, 13.0 + 4 * steps), **_columns(1.0 + full, 2.0 + window, 3.0)}
    return {"trace": trace, "serve": {"chunk": 8}, "peaks": {"hbm_bytes_per_s": 819e9},
            "trace_counters": (before, after)}


def test_the_touched_roofline_counts_what_a_step_really_read():
    from benchmark.rooflines import laguna_s as counts

    # 240 steps of 14 ms; 70 touched experts a layer, 20 live rows of 4000 positions
    run = _traced(14.0, 240, 70, 20, 4000)
    least = (counts.weight_bytes(C) + counts.expert_bytes(C, 4 * 70)
             + (2 * 20 * 4000 + 3 * 20 * 512) * counts.column_bytes(C))
    want = 100.0 * least / 819e9 / 14e-3
    assert _read("decode_touched_hbm_roofline", run) == pytest.approx(want)
    assert 60 < want < 65  # 5.3 GB of touched experts lead: 7.2 GB in 14 ms
    # a step that touched EVERY held expert, every slot live and full, at the time the chip's
    # bandwidth allows for those bytes and no faster: 100%, and under it for any slower step
    most = (counts.weight_bytes(C) + counts.expert_bytes(C, 4 * 128)
            + 32 * (2 * 8192 + 3 * 512) * counts.column_bytes(C))
    floor_ms = 1e3 * most / 819e9
    assert 15.0 < floor_ms < 16.5  # 12.9 GB: what the chip holds, nearly
    assert _read("decode_touched_hbm_roofline", _traced(floor_ms, 240, 128, 32, 8192)) == pytest.approx(100.0)
    assert _read("decode_touched_hbm_roofline", _traced(floor_ms * 1.25, 240, 128, 32, 8192)) == pytest.approx(80.0)
    assert _read("decode_touched_hbm_roofline", {**run, "trace_counters": ({}, {})}) is None  # the parent
    assert _read("decode_touched_hbm_roofline", {**run, "trace": None}) is None
    no_columns = {**run, "trace_counters": tuple({k: v for k, v in side.items() if "columns" not in k}
                                                 for side in run["trace_counters"])}
    assert _read("decode_touched_hbm_roofline", no_columns) is None


def test_the_chunk_reader_divides_the_programs_seconds_by_their_events():
    programs = {"decode": {"seconds": 1.68, "events": 15}, "chunk_prefill": {"seconds": 12.1, "events": 118}}
    assert _read(PROMPT_METRIC, {"trace": {"programs": programs}}) == pytest.approx(12100 / 118)
    assert _read(PROMPT_METRIC, {"trace": None}) is None  # an untraced run
    for silent in ({}, {"chunk_prefill": {"seconds": 0.0, "events": 0}}):
        assert _read(PROMPT_METRIC, {"trace": {"programs": silent}}) is None  # a span without a chunk


# ---- the manifest ---------------------------------------------------------------------


def _every_why():
    manifest = json.loads((mf.REPO_ROOT / "BENCHMARK.json").read_text())
    return [(f"{section}:{entry['name']}", entry["why"])
            for section in ("configs", "workloads") for entry in manifest[section]]


@pytest.mark.parametrize("where, why", _every_why(), ids=[where for where, _ in _every_why()])
def test_every_why_is_1_to_200_characters_of_printable_ascii(where, why):
    """The driver's rule, over EVERY entry: PR 41 was refused for a character
    outside it in one ``why`` (``manifest_invalid``) that no test here read."""
    assert isinstance(why, str) and 1 <= len(why) <= 200 and why.isascii() and why.isprintable(), where


def test_the_manifest_lists_the_configuration_the_cell_and_the_metrics():
    manifest = mf.load_manifest()
    config = next(c for c in manifest["configs"] if c["name"] == "laguna-s-ep2")
    assert config["reduced"] == REDUCED == CONFIG["reduced"]
    assert config["source"] == CONFIG["source"] == "https://huggingface.co/poolside/Laguna-S-2.1/blob/main/config.json"
    assert config["file"] == "benchmark/configs/laguna-s-ep2.json"
    # appended after what was there (later PRs append after these in their turn)
    names = {section: [e["name"] for e in manifest[section]] for section in ("configs", "workloads", "per_layer")}
    assert names["configs"].index("laguna-s-ep2") > names["configs"].index("olmo-hybrid-7b-pp2")
    assert names["workloads"].index(CELL) > names["workloads"].index("olmo-hybrid-7b-pp2.prefix-sessions")
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("laguna-s-ep2", "repo-context", 1)
    assert sum(w["config"] == "laguna-s-ep2" for w in manifest["workloads"]) == 1  # no second cell
    listed = {m["name"]: m for m in mf.metrics_for(manifest, "per_layer", CELL)}
    assert [n for n in names["per_layer"] if n in NEW_METRICS] == list(NEW_METRICS)  # in ISSUE 42's order
    assert names["per_layer"].index(PROMPT_METRIC) > names["per_layer"].index(NEW_METRICS[-1])
    prompt = listed[PROMPT_METRIC]
    assert (prompt["workloads"], prompt["moves"], prompt["source"]) == ([CELL], "latency_mean_ms", "device_trace")
    assert (prompt["unit"], prompt["better"], prompt["layer"]) == ("ms", "lower", "model step models/llama.py")
    assert names["per_layer"].index(NEW_METRICS[0]) > names["per_layer"].index("column_write_spared_share")
    for name in NEW_METRICS:
        assert listed[name]["workloads"] == [CELL] and listed[name]["moves"] == "tpot_p50_ms"
        assert (listed[name]["unit"], listed[name]["better"]) == ("%", "higher")
    assert listed["decode_touched_hbm_roofline"]["source"] == "device_trace"
    # the metrics without a list come to the cell by themselves; the other cells' do not
    assert {"decode_step_ms", "decode_hbm_roofline", "device_idle_share", "prefill_tokens_per_chunk"} <= set(listed)
    assert not {"moe_held_pick_share", "cache_mechanism_byte_share", "recurrent_state_byte_share"} & set(listed)
    # no list of a metric that was there names the cell
    assert all(CELL not in m.get("workloads", []) for m in manifest["per_layer"]
               if m["name"] not in (*NEW_METRICS, PROMPT_METRIC))
    resolved = mf.resolve_cell(manifest, CELL)
    traffic = resolved["traffic"]
    assert traffic["generator"] == "poisson_lognormal" and traffic["ramp_s"] == 15.0 and traffic["loop"] == "open"
    assert (traffic["shape_seed"], traffic["answer_pad"]) == (42, 768)
    assert (traffic["prompt"], traffic["answer"]) == (
        {"median": 3072, "sigma": 0.7, "min": 512, "max": 7168},
        {"median": 256, "sigma": 0.5, "min": 64, "max": 768})
    assert traffic["max_total"] == CONFIG["serve"]["slot_capacity"] == 8192
    from benchmark.generators import poisson_lognormal

    sessions = poisson_lognormal.generate(traffic, 1, 51.0, C["vocab_size"])["sessions"]
    lengths = [len(s["turns"][0]["new_messages"][0]["ids"]) for s in sessions]
    # the longest request, its role words and its answer stay inside the slot
    assert max(n + 2 + s["turns"][0]["max_tokens"] for n, s in zip(lengths, sessions)) <= 8192
    assert min(lengths) >= 512 and max(lengths) <= 7168 and min(lengths) < 1300 and max(lengths) > 7000
    assert resolved["cell"]["serve"] == {"prefix_cache_mb": 0}
    assert traffic["rate_rps"] == resolved["cell"]["traffic_overrides"]["rate_rps"]
    assert f"{traffic['rate_rps']:g} req/s" in cell["why"]


def test_the_configuration_file_holds_the_catalog_rows_keys_as_published():
    period = ["full_attention"] + ["sliding_attention"] * 3
    catalog = {
        "model_type": "laguna", "vocab_size": 100352, "hidden_size": 3072, "intermediate_size": 12288,
        "num_hidden_layers": 48, "num_attention_heads": 48, "num_key_value_heads": 8, "head_dim": 128,
        "max_position_embeddings": 1048576, "attention_bias": False, "rms_norm_eps": 1e-06,
        "num_experts": 256, "num_experts_per_tok": 10, "moe_intermediate_size": 1024,
        "shared_expert_intermediate_size": 1024, "norm_topk_prob": True, "decoder_sparse_step": 1,
        "mlp_only_layers": [0], "tie_word_embeddings": False, "gating": "per-head", "sliding_window": 512,
        "rope_parameters": {
            "full_attention": {"rope_theta": 500000, "rope_type": "yarn", "factor": 128,
                               "original_max_position_embeddings": 8192, "beta_slow": 1, "beta_fast": 32,
                               "attention_factor": 1.4852030263919618, "partial_rotary_factor": 0.5},
            "sliding_attention": {"rope_type": "default", "rope_theta": 10000, "partial_rotary_factor": 1}},
        "layer_types": period * 12, "moe_apply_router_weight_on_input": False,
        "mlp_layer_types": ["dense"] + ["sparse"] * 47, "gating_types": ["per_head"] * 48,
        "moe_routed_scaling_factor": 2.5, "num_attention_heads_per_layer": [48, 72, 72, 72] * 12,
        "moe_router_logit_softcapping": 0,
    }
    reduced = {"num_hidden_layers": 5, "num_experts": 128, "vocab_size": 50176,
               **{key: catalog[key][:5] for key in REDUCED[3:]}}
    assert sorted(reduced) == sorted(REDUCED)
    for key, value in catalog.items():
        want = reduced.get(key, value)
        assert CONFIG[key] == want == C[key], key
        assert (key in CONFIG["reduced"]) == (key in reduced)
        if key in reduced:
            assert key in CONFIG["published"] and key in CONFIG["why_reduced"]
    assert (CONFIG["published"]["num_hidden_layers"], CONFIG["published"]["num_experts"],
            CONFIG["published"]["vocab_size"]) == (48, 256, 100352)
    assert (C["num_experts_published"], C["vocab_size_published"], C["first_expert"],
            C["num_hidden_layers_published"]) == (256, 100352, 0, 48)
    from prime_tpu.models import get_config

    for where in (CONFIG, CONFIG["rehearsal"]):
        preset = get_config(where["preset"])
        for key, attr in CONFIG["preset_fields"].items():
            assert getattr(preset, attr) == where["model"][key], (where["preset"], key)
        # the lists and the rotary settings are no scalar field: read off the preset here
        model = where["model"]
        assert list(preset.layer_types) == model["layer_types"]
        assert list(preset.mlp_layer_types) == model["mlp_layer_types"]
        assert list(preset.heads_per_layer) == model["num_attention_heads_per_layer"]
        full, sliding = (model["rope_parameters"][k] for k in ("full_attention", "sliding_attention"))
        assert (preset.rope_theta, preset.partial_rotary, preset.rope_local_theta) == (
            full["rope_theta"], full["partial_rotary_factor"], sliding["rope_theta"])
        assert preset.rope_yarn == (full["factor"], full["beta_fast"], full["beta_slow"],
                                    full["original_max_position_embeddings"], full["attention_factor"])
    assert set(CONFIG["preset_fields"]) <= set(C)
    assert {"routing", "shared_expert", "qk_norm", "head_gate", "window", "weights", "tokenizer",
            "context"} <= set(CONFIG["assumed"])
    assert CONFIG["serve"] == {"max_slots": 32, "slot_capacity": 8192, "chunk": 8, "warmup": True, "mesh": None}
    assert CONFIG["precision"]["stated"] == "bfloat16"
    limits = CONFIG["limits"]
    assert 0 < limits["logit_gap_mean"] < limits["logit_gap_max"] and limits["sample"] in (8, 12)


# ---- the reference --------------------------------------------------------------------


def test_the_reference_is_causal_windowed_and_its_control_differs():
    import jax

    from benchmark.reference import laguna_s as ref

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
    # a sliding layer alone sees its window and no further: a token 8 positions back moves nothing
    sliding = dict(tiny, num_hidden_layers=1, layer_types=["sliding_attention"], mlp_layer_types=["sparse"],
                   num_attention_heads_per_layer=[6])
    near, far = list(prompt), list(prompt)
    far[-9], near[-8] = 77, 77
    at = [len(prompt) - 1]
    base, moved_far, moved_near = (np.asarray(ref.logits_at(sliding, weights, ids, at)) for ids in (prompt, far, near))
    assert np.array_equal(base, moved_far) and not np.array_equal(base, moved_near)
    # the yarn blend: fast pairs keep theta's frequencies, slow pairs are divided by the factor
    inv, scale = ref.rope_frequencies(C["rope_parameters"]["full_attention"], 128)
    plain = 500000.0 ** (-np.arange(0, 64, 2) / 64)
    assert inv.shape == (32,) and scale == pytest.approx(0.1 * np.log(128) + 1)
    assert np.allclose(inv[:10], plain[:10]) and np.allclose(inv[18:], plain[18:] / 128)
    assert ref.rope_frequencies(C["rope_parameters"]["sliding_attention"], 128)[0].shape == (64,)
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
    # `correct` is held to the limits of the PUBLISHED widths: the tiny model, hidden 64 and a
    # top-3 of 8 experts in bfloat16, flips a pick every few tokens, so the rehearsal's verdict is
    # not asserted; what it compared is
    assert isinstance(line["correct"], bool) and line["failed"] == 0 and line["attempted"] > 0
    compared = [json.loads(row) for row in done.stdout.splitlines() if '"compared"' in row][-1]["compared"]
    seen = {c["name"]: c["value"] for c in compared}
    assert 0.0 < seen["logit_gap_mean"] < 0.5 and seen["logit_gap_max"] < 5.0
    assert all(seen[name] == 0 for name in (
        "bytes_below_stated_precision", "precision_unread", "malformed_streams", "unjoined_prompts",
        "requests_failed_by_engine", "sampled_too_few"))
    # the counters' readers always find something; the one that needs decode steps inside the
    # traced span finds them on the chip (on the CPU a short span may hold a compile and nothing else)
    for name in ("full_row_kv_byte_share", "window_columns_spared_share"):
        assert name in line["metrics"], name
    # prompts of 16-128 tokens over a window of 8: most positions lie beyond it, and the 3 full
    # layers' rows carry most of the K/V bytes a step reads
    assert 50 < line["metrics"]["window_columns_spared_share"]["value"] < 100
    assert 50 < line["metrics"]["full_row_kv_byte_share"]["value"] < 100
