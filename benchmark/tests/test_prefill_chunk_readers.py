"""The three readers of the chunk plan, each on a hand-made run (two registry
snapshots): tokens a chunk program, the pad share, the rows a touched expert's
read served in prefill; a program without the counters (the parent of the PR
that brought them) reads nothing, and the manifest lists all three."""
import importlib

from benchmark.harness.manifest import load_manifest
from benchmark.systems.serve_http import counter_delta


def family(series: dict) -> dict:
    return {"type": "counter", "series": [
        {"labels": dict(labels), "value": value} for labels, value in series.items()]}


def snapshot(chunks, prompt, pad, local, touched) -> dict:
    return {
        "serve_prefill_chunks_total": family({(): chunks}),
        "serve_prefill_chunk_tokens_total": family(
            {(("kind", "prompt"),): prompt, (("kind", "pad"),): pad}),
        "serve_moe_pairs_total": family({
            (("kind", "local"), ("phase", "prefill")): local,
            (("kind", "local"), ("phase", "decode")): 7 * local,
            (("kind", "elsewhere"), ("phase", "prefill")): local}),
        "serve_moe_experts_touched_total": family({
            (("phase", "prefill"),): touched, (("phase", "decode"),): 3 * touched}),
    }


def read(metric: str, pair):
    reader = importlib.import_module(f"benchmark.layer_metrics.{metric}")
    return reader.read({"counters": pair, "counter_delta": counter_delta})


WINDOW = (snapshot(19, 9000, 728, 45000, 4864), snapshot(119, 137000, 28728, 685000, 30464))


def test_the_readers_divide_the_windows_deltas():
    # 100 chunk programs: 128000 prompt tokens and 28000 pads; 640000 local pairs on 25600 touched
    assert read("prefill_tokens_per_chunk", WINDOW) == 1280.0
    assert read("prefill_pad_token_share", WINDOW) == 100.0 * 28000 / 156000
    assert read("moe_prefill_rows_per_touched_expert", WINDOW) == 25.0


def test_a_program_without_the_counters_reads_nothing():
    parent = tuple({name: fam for name, fam in snap.items() if name.startswith("serve_moe_")}
                   for snap in WINDOW)
    assert read("prefill_tokens_per_chunk", parent) is None
    assert read("prefill_pad_token_share", parent) is None
    assert read("moe_prefill_rows_per_touched_expert", parent) == 25.0  # the parent's counters
    assert read("moe_prefill_rows_per_touched_expert", ({}, {})) is None
    assert read("prefill_tokens_per_chunk", (WINDOW[1], WINDOW[1])) is None  # no chunk ran


def test_the_manifest_lists_the_three():
    per_layer = {m["name"]: m for m in load_manifest()["per_layer"]}
    for name in ("prefill_tokens_per_chunk", "prefill_pad_token_share"):
        assert per_layer[name]["moves"] == "tpot_p50_ms" and "workloads" not in per_layer[name]
        assert per_layer[name]["layer"] == "engine serve/engine.py"
    rows = per_layer["moe_prefill_rows_per_touched_expert"]
    assert rows["layer"] == "expert layer ops/moe.py"
    assert rows["workloads"] == ["longcat-flash-ep32.reasoning", "qwen3-next-ep2.document-chat"]
