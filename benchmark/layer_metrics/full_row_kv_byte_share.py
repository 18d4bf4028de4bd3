"""Cache: of the K/V bytes the decode steps read for their live rows, the share
that lies in the full layers' rows (the rest in the sliding layers' rings):
``serve_decode_attn_columns_total{kind="full" | "window"}`` over the window, a
column priced by rooflines/<config.roofline> ``column_bytes``. It rises with the
contexts' length: a ring stops at the window, a row does not. A program without
the counter, a roofline module that prices no column, or a window in which no
decode step ran gives nothing."""
import importlib


def read(run):
    counts = importlib.import_module(f"benchmark.rooflines.{run['config']['roofline']}")
    if not hasattr(counts, "column_bytes"):
        return None
    pair, delta = run["counters"], run["counter_delta"]
    column = counts.column_bytes(run["model"])
    full = column * delta(pair, "serve_decode_attn_columns_total", {"kind": "full"})
    ring = column * delta(pair, "serve_decode_attn_columns_total", {"kind": "window"})
    if full + ring <= 0:
        return None
    return 100.0 * full / (full + ring)
