"""Decides ``correct``: the served tokens against the plain reference.

With random weights the largest logit flips on rounding, so tokens cannot be
compared with tokens. For a sample of the window's finished requests, drawn
from the seed with the longest in it, the reference is run once over each prompt
with its served tokens, and at every served position the gap is read by which
the served token's reference logit lies below the reference's best. Compared
with their limits (configuration file, ``limits``): the mean gap, which is
steady from seed to seed, and the widest gap, which swings by its nature.
"""

from __future__ import annotations

import importlib

import numpy as np


def sample(records: list[dict], seed: int, count: int) -> list[dict]:
    """``count`` finished requests, the longest (prompt + answer) among them."""
    done = [r for r in records if r["phase"] == "run" and r["status"] == 200 and r["done"] and r["ids"]]
    if not done:
        return []
    longest = max(done, key=lambda r: len(r["numeric_prompt"]) + len(r["ids"]))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng([int(seed), 0xC4EC])
    picks = rng.choice(len(rest), size=min(count - 1, len(rest)), replace=False) if rest else []
    return [longest] + [rest[i] for i in sorted(picks)]


def gaps(config: dict, weights, sequences: list[dict], pad_to: int, answer_pad: int,
         eos_id: int, control: str | None = None) -> dict:
    """Per sequence ``{"prompt": ids, "answer": ids, "max_tokens": n}``: the gaps
    of the served tokens, and with ``control`` the gaps of the tokens that the
    lower precision puts first at the same positions."""
    reference = importlib.import_module(f"benchmark.reference.{config['reference']}")
    served, controlled = [], []
    for seq in sequences:
        answer = list(seq["answer"])
        if len(answer) < seq["max_tokens"]:
            answer.append(eos_id)  # a short stream has to have ended on eos
        logits = np.asarray(reference.score(config["model"], weights, seq["prompt"], answer,
                                            pad_to, answer_pad))
        best = logits.max(axis=-1)
        served.append(best - logits[np.arange(len(answer)), answer])
        if control:
            low = np.asarray(reference.score(config["model"], weights, seq["prompt"], answer,
                                             pad_to, answer_pad, weight_round=control))
            controlled.append(best - logits[np.arange(len(answer)), low.argmax(axis=-1)])
    out = {"tokens": int(sum(len(g) for g in served))}
    for name, parts in (("served", served), ("control", controlled)):
        if parts:
            flat = np.concatenate(parts)
            out[name] = {"gap_mean": float(flat.mean()), "gap_max": float(flat.max()),
                         "mismatch_share": float((flat > 0).mean())}
    return out


def verdict(compared: list[tuple[str, float, float]]) -> bool:
    """``(name, value, limit)`` rows: correct when every value is within its limit."""
    return all(value <= limit for _, value, limit in compared)
