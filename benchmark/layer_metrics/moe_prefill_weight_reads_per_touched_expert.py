"""Expert layer: times the chunk prefills' three grouped products (gate, up,
down) fetched an expert's matrix, over 3 x the held experts that had a token
(``serve_moe_weight_reads_total`` / (3 x ``serve_moe_experts_touched_total``),
prefill phase, over the window). 1.0 where every product walks its contraction
in one tile, so that a group's row-tile visits keep the weight block it has
(``prime_tpu/ops/moe.py grouped_matmul_tiling``); above it where a product
fetches the expert again at every row tile its group spans or straddles (1.37 at
20 rows an expert with gate and up walked in three tiles and groups laid end to
end). The counts come from the counters alone. A program without the counter
(the parent of the PR that added it), or a window with no prefill, gives nothing."""


def read(run):
    delta, pair = run["counter_delta"], run["counters"]
    phase = {"phase": "prefill"}
    reads = delta(pair, "serve_moe_weight_reads_total", phase)
    touched = delta(pair, "serve_moe_experts_touched_total", phase)
    if reads <= 0 or touched <= 0:
        return None
    return reads / (3 * touched)
