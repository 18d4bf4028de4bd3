"""Kernels (ops/attention.py ``cache_prefill_attention``): the row columns the
chunk prefills' attentions did not visit, as a share of the columns of the rows
they ran over (``serve_prefill_attn_columns_total{kind="visited" | "spared"}``,
counted on the host where a chunk prefill is dispatched: wave rows x layers that
attend over a row x chunks, a row's capacity each). The walk takes a row in
column blocks and stops at the last one a chunk's queries can see, so an n-chunk
prompt spares about (n - 1) / 2n of its row and a prompt of one chunk nothing. A
program without the counter, or a window with no prefill (0 / 0), gives nothing."""


def read(run):
    pair, delta = run["counters"], run["counter_delta"]
    visited = delta(pair, "serve_prefill_attn_columns_total", {"kind": "visited"})
    spared = delta(pair, "serve_prefill_attn_columns_total", {"kind": "spared"})
    if visited + spared <= 0:
        return None
    return 100.0 * spared / (visited + spared)
