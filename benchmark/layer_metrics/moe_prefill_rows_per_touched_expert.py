"""Expert layer: token-expert pairs a held expert that had any computed, averaged
over the chunk prefills' expert-layer calls (a layer and token group each): the
rows one read of an expert's weights served in a prompt's chunk. ``chunk x
experts_per_token / n_experts`` where a chunk is one group and every held expert
is touched (512 x 10 / 512 = 10). Program counters, prefill phase, over the
window; a program without them gives nothing."""


def read(run):
    delta, pair = run["counter_delta"], run["counters"]
    phase = {"phase": "prefill"}
    touched = delta(pair, "serve_moe_experts_touched_total", phase)
    if touched <= 0:
        return None
    return delta(pair, "serve_moe_pairs_total", {"kind": "local", **phase}) / touched
