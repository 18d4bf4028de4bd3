"""Every benchmark configuration's engine-shaped decode step and widest chunk
prefill lower to the recorded text (``scripts/program_text_hash.py``,
``tests/program_text_hashes.json``): a change under ``models/`` or to the rule
from shapes that moves ANOTHER configuration's device program fails here, before
any chip is asked. PR 42 recorded them with the five older configurations'
hashes equal to the parent commit's."""
import importlib.util
import json
from pathlib import Path

import pytest

_spec = importlib.util.spec_from_file_location(
    "program_text_hash", Path(__file__).resolve().parents[1] / "scripts" / "program_text_hash.py"
)
texts = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(texts)

RECORDED = json.loads(texts.RECORD.read_text())


def test_the_record_covers_the_benchmarks_configurations():
    assert sorted(RECORDED) == sorted(texts.served())


@pytest.mark.parametrize("name", sorted(RECORDED))
def test_a_configurations_programs_lower_to_the_recorded_text(name):
    with texts.chip_gates():
        now = texts.hashes(name, texts.served()[name])
    assert now == RECORDED[name], (
        f"{name}'s device program changed: if that is meant, record it "
        "(python scripts/program_text_hash.py --write) and say so in CHANGES.md"
    )
