"""Tenants with a fixed system prompt; sessions of a few turns that grow.

Parameters (traffic file): rate_sps, ramp_s, shape_seed, tenants, system_tokens,
turns, user {min, max}, answer {min, max}, think_s {min, max}, primer_user,
primer_answer. Turn k's prompt is the system prompt, the earlier user messages
with the answers the server gave (the client keeps the history), and a new
user message. The primer sends each tenant's system prompt once during set-up,
so the window starts with the cache as a running deployment has it.
"""

from __future__ import annotations

from benchmark.generators._common import fixed_arrivals, streams, token_ids


def _phase(traffic, shape_rng, run_rng, systems, start_s, span_s, vocab_size):
    count = int(round(traffic["rate_sps"] * span_s))
    turns, u, a, th = traffic["turns"], traffic["user"], traffic["answer"], traffic["think_s"]
    user = shape_rng.integers(u["min"], u["max"] + 1, size=(count, turns))
    answer = shape_rng.integers(a["min"], a["max"] + 1, size=(count, turns))
    think = shape_rng.uniform(th["min"], th["max"], size=(count, turns))
    tenant = shape_rng.integers(0, len(systems), size=count)
    arrivals = fixed_arrivals(shape_rng, count, start_s, span_s)
    sessions = []
    for i, at in enumerate(arrivals):
        session_turns = []
        for k in range(turns):
            new = [{"role": "user", "ids": token_ids(run_rng, user[i, k], vocab_size)}]
            if k == 0:
                new.insert(0, {"role": "system", "ids": systems[int(tenant[i])]})
            session_turns.append({"new_messages": new, "max_tokens": int(answer[i, k]),
                                  "think_s": 0.0 if k == 0 else float(think[i, k])})
        sessions.append({"start_s": at, "turns": session_turns})
    return sessions


def generate(traffic: dict, seed: int, seconds: float, vocab_size: int) -> dict:
    shape_rng, run_rng = streams(traffic["shape_seed"], seed)
    systems = [token_ids(run_rng, traffic["system_tokens"], vocab_size)
               for _ in range(traffic["tenants"])]
    primer = [{"start_s": 0.0, "turns": [{
        "new_messages": [{"role": "system", "ids": ids},
                         {"role": "user", "ids": token_ids(run_rng, traffic["primer_user"], vocab_size)}],
        "max_tokens": int(traffic["primer_answer"]), "think_s": 0.0}]} for ids in systems]
    ramp = _phase(traffic, shape_rng, run_rng, systems, 0.0, traffic["ramp_s"], vocab_size)
    window = _phase(traffic, shape_rng, run_rng, systems, traffic["ramp_s"], seconds, vocab_size)
    return {"primer": primer, "sessions": ramp + window}
