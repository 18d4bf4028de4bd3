"""Kernels (ops/ + XLA fusions), decode, with what a step of THIS configuration
really touched in the bound: the weights outside the experts and the head once,
the held experts the traced span's DECODE programs touched
(``serve_moe_experts_touched_total{phase="decode"}``, each once a layer and
step), the full layers' K/V columns and the rings' columns the live rows
attended over (``serve_decode_attn_columns_total{kind="full" | "window"}``), all
a step (rooflines/<config.roofline>), over the chip's bandwidth, against the
measured step. ``decode_hbm_roofline`` beside it leaves the experts and the
rings out. Every term is a count of what was read at least once, so the share
cannot pass 100% unless a count or the step's time is wrong. A configuration
whose roofline module prices no column, a program without the counters, or a run
without a traced span gives nothing."""
import importlib

from benchmark.layer_metrics import decode_step_ms


def read(run):
    step_ms = decode_step_ms.read(run)
    if step_ms is None or "trace_counters" not in run:
        return None
    counts = importlib.import_module(f"benchmark.rooflines.{run['config']['roofline']}")
    if not hasattr(counts, "column_bytes") or not hasattr(counts, "expert_bytes"):
        return None
    delta, pair, phase = run["counter_delta"], run["trace_counters"], {"phase": "decode"}
    calls = delta(pair, "serve_moe_layer_calls_total", phase)
    if calls <= 0:
        return None
    steps = calls / counts.layer_counts(run["model"])["sparse"]  # a call an expert layer and step
    touched = delta(pair, "serve_moe_experts_touched_total", phase) / steps
    columns = sum(delta(pair, "serve_decode_attn_columns_total", {"kind": kind})
                  for kind in ("full", "window")) / steps
    if columns <= 0:
        return None
    model = run["model"]
    least = (counts.weight_bytes(model) + counts.expert_bytes(model, touched)
             + columns * counts.column_bytes(model))
    chips = run["trace"]["devices"]
    return 100.0 * least / (run["peaks"]["hbm_bytes_per_s"] * chips) / (step_ms / 1e3)
