"""Kernels (ops/ + XLA fusions), decode, with everything a step of THIS
configuration cannot avoid in the bound: the weights outside the experts and the
head once, the held experts the traced span's steps really touched
(``serve_moe_experts_touched_total``, decode phase, a step), the matrix state and
conv tail of the live rows in and out, and the live K/V once
(rooflines/<config.roofline>), over the chip's bandwidth, against the measured
step. ``decode_hbm_roofline`` beside it leaves the experts and the state out. A
configuration whose roofline module prices neither, a program without the
expert counters, or a run without timelines gives nothing."""
import importlib

from benchmark.layer_metrics import decode_step_ms
from benchmark.layer_metrics.recurrent_state_byte_share import live_rows_and_tokens


def read(run):
    step_ms = decode_step_ms.read(run)
    if step_ms is None or not run["flights"] or "trace_counters" not in run:
        return None
    counts = importlib.import_module(f"benchmark.rooflines.{run['config']['roofline']}")
    if not hasattr(counts, "state_bytes_per_row") or not hasattr(counts, "expert_bytes"):
        return None
    delta, pair, phase = run["counter_delta"], run["trace_counters"], {"phase": "decode"}
    calls = delta(pair, "serve_moe_layer_calls_total", phase)
    if calls <= 0:
        return None
    steps = calls / run["model"]["num_hidden_layers"]
    touched = delta(pair, "serve_moe_experts_touched_total", phase) / steps
    rows, tokens = live_rows_and_tokens(run)
    least = (counts.decode_step_bytes(run["model"], tokens) + counts.expert_bytes(run["model"], touched)
             + counts.mechanism_bytes(run["model"], rows))
    chips = run["trace"]["devices"]
    return 100.0 * least / (run["peaks"]["hbm_bytes_per_s"] * chips) / (step_ms / 1e3)
