"""Kernels (ops/ + XLA fusions), decode: bound by HBM bandwidth. Least bytes a
step must read (weights once + the live cache once, rooflines/<config.roofline>)
over the chip's bandwidth (all chips' under a mesh), against the measured step."""
import importlib

from benchmark.layer_metrics import decode_step_ms
from benchmark.layer_metrics._flight import traced_live_tokens


def read(run):
    step_ms = decode_step_ms.read(run)
    if step_ms is None or not run["flights"]:
        return None
    counts = importlib.import_module(f"benchmark.rooflines.{run['config']['roofline']}")
    chips = run["trace"]["devices"]
    least_s = counts.decode_step_bytes(run["model"], traced_live_tokens(run)) / (
        run["peaks"]["hbm_bytes_per_s"] * chips)
    return 100.0 * least_s / (step_ms / 1e3)
