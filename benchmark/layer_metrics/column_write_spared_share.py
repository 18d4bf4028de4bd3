"""Kernels (ops/pallas_attention.py ``write_columns``): the rows of the decode
steps' column-write calls that belonged to nobody and were not written, as a
share of the rows the calls were offered
(``serve_decode_column_writes_total{kind="written" | "spared"}``). It follows the
dispatch mask, so it reads near the retired share of ``serve_decode_rows_total``
and a few points under 1 - ``slot_occupancy``. A program without the counter,
or a window whose decode wrote through XLA (0 / 0), gives nothing."""


def read(run):
    pair, delta = run["counters"], run["counter_delta"]
    written = delta(pair, "serve_decode_column_writes_total", {"kind": "written"})
    spared = delta(pair, "serve_decode_column_writes_total", {"kind": "spared"})
    if written + spared <= 0:
        return None
    return 100.0 * spared / (written + spared)
