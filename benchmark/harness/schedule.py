"""seed -> fully materialised schedule -> digest.

A schedule is plain JSON (see README.md): ``primer`` and ``sessions`` are lists
of sessions; a session has ``start_s`` (offset from the schedule's start) and
``turns``; a turn has ``new_messages`` (role + token ids), ``max_tokens`` and
``think_s`` (gap after the previous turn's answer ended). A single request is a
session of one turn; a closed loop is a few long sessions with ``think_s`` 0; a
training step would be a turn whose system knows what to do with it.
"""

from __future__ import annotations

import hashlib
import importlib
import json


def materialise(traffic: dict, seed: int, seconds: float, vocab_size: int) -> dict:
    generator = importlib.import_module(f"benchmark.generators.{traffic['generator']}")
    schedule = generator.generate(traffic, int(seed), float(seconds), int(vocab_size))
    schedule["ramp_s"] = float(traffic["ramp_s"])
    schedule["seconds"] = float(seconds)
    return schedule


def digest(schedule: dict) -> str:
    blob = json.dumps(schedule, sort_keys=True, separators=(",", ":")).encode()
    return hashlib.sha256(blob).hexdigest()


def count_turns(schedule: dict) -> int:
    return sum(len(s["turns"]) for s in schedule["sessions"])
