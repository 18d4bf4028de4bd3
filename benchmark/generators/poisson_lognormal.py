"""Independent users, open loop: arrivals at ``rate_rps``, log-normal lengths.

Parameters (traffic file): rate_rps, ramp_s, shape_seed, prompt {median, sigma,
min, max}, answer {median, sigma, min, max}, max_total. No two prompts share
more than the chat template's role words, so the prefix cache is bypassed.
"""

from __future__ import annotations

from benchmark.generators._common import clipped_lognormal, fixed_arrivals, streams, token_ids


def _phase(traffic, shape_rng, run_rng, start_s, span_s, vocab_size):
    count = int(round(traffic["rate_rps"] * span_s))
    p, a = traffic["prompt"], traffic["answer"]
    answers = clipped_lognormal(shape_rng, count, a["median"], a["sigma"], a["min"], a["max"])
    prompts = clipped_lognormal(shape_rng, count, p["median"], p["sigma"], p["min"], p["max"])
    arrivals = fixed_arrivals(shape_rng, count, start_s, span_s)
    sessions = []
    for i, at in enumerate(arrivals):
        n_answer = int(answers[i])
        n_prompt = int(min(prompts[i], traffic["max_total"] - n_answer))
        sessions.append({"start_s": at, "turns": [{
            "new_messages": [{"role": "user", "ids": token_ids(run_rng, n_prompt, vocab_size)}],
            "max_tokens": n_answer, "think_s": 0.0}]})
    return sessions


def generate(traffic: dict, seed: int, seconds: float, vocab_size: int) -> dict:
    shape_rng, run_rng = streams(traffic["shape_seed"], seed)
    ramp = _phase(traffic, shape_rng, run_rng, 0.0, traffic["ramp_s"], vocab_size)
    window = _phase(traffic, shape_rng, run_rng, traffic["ramp_s"], seconds, vocab_size)
    return {"primer": [], "sessions": ramp + window}
