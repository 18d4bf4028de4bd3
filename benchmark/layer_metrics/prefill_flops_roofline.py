"""Kernels, prefill: bound by compute. Least FLOPs to prefill the prompts whose
first token fell in the traced span (rooflines/<config.roofline>) over the
chip's bf16 peak (all chips' under a mesh), against the chunk-prefill program's
device time in the span."""
import importlib

from benchmark.layer_metrics._flight import traced_prefill


def read(run):
    prefill = (run["trace"] or {}).get("programs", {}).get("chunk_prefill")
    if not prefill or not prefill["events"] or not run["flights"]:
        return None
    lengths, cached = traced_prefill(run)
    if sum(lengths) - cached <= 0:
        return None
    counts = importlib.import_module(f"benchmark.rooflines.{run['config']['roofline']}")
    chips = run["trace"]["devices"]
    least_s = counts.prefill_flops(run["model"], lengths, cached) / (run["peaks"]["bf16_flops"] * chips)
    return 100.0 * least_s / prefill["seconds"]
