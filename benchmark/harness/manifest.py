"""Loads BENCHMARK.json and resolves a cell to its data files, by name.

Everything that belongs to one configuration, one traffic mix, one cell or one
per-layer metric lives in a file of its own, found from the names here:

    configs/<config>.json     traffic/<traffic>.json     cells/<workload>.json
    generators/<traffic.generator>.py   layer_metrics/<metric>.py
    reference/<config.reference>.py     systems/<config.system>.py

so a later PR adds a cell by adding files and one entry, never by editing.
"""

from __future__ import annotations

import json
import re
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent.parent
REPO_ROOT = BENCH_DIR.parent

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


class ManifestError(ValueError):
    pass


def _check_name(value, what: str) -> None:
    if not isinstance(value, str) or not NAME_RE.match(value):
        raise ManifestError(f"{what} {value!r} is not a name (letters, digits, _ . -; at most 64)")


def _check_metric(metric: dict, end_to_end: bool) -> None:
    _check_name(metric.get("name"), "metric name")
    unit = metric.get("unit")
    if not isinstance(unit, str) or not UNIT_RE.match(unit):
        raise ManifestError(f"unit {unit!r} of {metric['name']} has a character outside A-Za-z0-9_/%.-")
    if metric.get("better") not in ("lower", "higher"):
        raise ManifestError(f"{metric['name']}: better must be lower or higher")
    if metric.get("source") not in SOURCES:
        raise ManifestError(f"{metric['name']}: unknown source {metric.get('source')!r}")
    if end_to_end and metric["source"] not in ("host_clock", "device_trace"):
        raise ManifestError(f"{metric['name']}: an end-to-end metric is host_clock or device_trace")


def validate(manifest: dict) -> dict:
    """Refuse what the contract refuses about names, units and references."""
    for section in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [entry.get("name") for entry in manifest.get(section, [])]
        if not names:
            raise ManifestError(f"{section} is empty")
        if len(set(names)) != len(names):
            raise ManifestError(f"{section} has a name twice")
    configs = {c["name"]: c for c in manifest["configs"]}
    for config in configs.values():
        _check_name(config["name"], "config name")
        for key in config.get("reduced", []):
            _check_name(key, "reduced key")
    for cell in manifest["workloads"]:
        _check_name(cell["name"], "workload name")
        _check_name(cell["traffic"], "traffic name")
        if cell["config"] not in configs:
            raise ManifestError(f"{cell['name']} names the unknown config {cell['config']!r}")
        if cell.get("chips") not in (1, 4):
            raise ManifestError(f"{cell['name']}: chips must be 1 or 4")
    e2e = {m["name"] for m in manifest["end_to_end"]}
    for metric in manifest["end_to_end"]:
        _check_metric(metric, end_to_end=True)
    if "setup_s" not in e2e:
        raise ManifestError("end_to_end lacks setup_s")
    cells = {c["name"] for c in manifest["workloads"]}
    for metric in manifest["per_layer"]:
        _check_metric(metric, end_to_end=False)
        if metric.get("moves") not in e2e:
            raise ManifestError(f"{metric['name']} moves the unknown metric {metric.get('moves')!r}")
    for metric in manifest["end_to_end"] + manifest["per_layer"]:
        for name in metric.get("workloads", []):
            if name not in cells:
                raise ManifestError(f"{metric['name']} lists the unknown workload {name!r}")
    return manifest


def load_manifest(path: Path | None = None) -> dict:
    path = path or REPO_ROOT / "BENCHMARK.json"
    return validate(json.loads(Path(path).read_text()))


def load_json(kind: str, name: str) -> dict:
    _check_name(name, f"{kind} name")
    path = BENCH_DIR / kind / f"{name}.json"
    if not path.is_file():
        raise ManifestError(f"no {kind} file {path}")
    return json.loads(path.read_text())


def metrics_for(manifest: dict, section: str, workload: str) -> list[dict]:
    """The metrics of ``section`` that cell ``workload`` reports."""
    return [
        m for m in manifest[section]
        if "workloads" not in m or workload in m["workloads"]
    ]


def resolve_cell(manifest: dict, workload: str) -> dict:
    """One cell's entry, configuration, traffic and cell parameters. A cell that
    is not (yet) in ``workloads`` resolves from ``cells/<workload>.json`` alone,
    which is how a sweep or a rehearsal drives a cell before it is listed."""
    entry = next((c for c in manifest["workloads"] if c["name"] == workload), None)
    cell = load_json("cells", workload)
    if entry is None:
        entry = {"name": workload, "config": cell["config"], "traffic": cell["traffic"],
                 "chips": cell["chips"]}
    config = load_json("configs", entry["config"])
    traffic = load_json("traffic", entry["traffic"])
    traffic = {**traffic, **cell.get("traffic_overrides", {})}
    return {"entry": entry, "cell": cell, "config": config, "traffic": traffic}
