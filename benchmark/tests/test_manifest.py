"""The manifest loader refuses what the contract refuses; the committed
manifest and every file it names load."""
import copy
import importlib

import pytest

from benchmark.harness import manifest as mf


def test_the_committed_manifest_resolves_every_cell_and_reader():
    manifest = mf.load_manifest()
    for cell in manifest["workloads"]:
        resolved = mf.resolve_cell(manifest, cell["name"])
        importlib.import_module(f"benchmark.generators.{resolved['traffic']['generator']}")
        importlib.import_module(f"benchmark.systems.{resolved['config']['system']}")
        assert mf.metrics_for(manifest, "end_to_end", cell["name"])
    for metric in manifest["per_layer"]:
        reader = importlib.import_module(f"benchmark.layer_metrics.{metric['name']}")
        assert callable(reader.read)


@pytest.mark.parametrize("path, value", [
    (("end_to_end", 0, "name"), "ttft p50"),
    (("end_to_end", 0, "name"), "ttft,p50"),
    (("end_to_end", 0, "unit"), "tokens per second"),
    (("end_to_end", 0, "unit"), "µs"),
    (("workloads", 0, "name"), "a/b"),
    (("workloads", 0, "chips"), 2),
    (("per_layer", 0, "moves"), "no_such_metric"),
    (("per_layer", 0, "source"), "server_histogram"),
    (("end_to_end", 0, "source"), "program_counter"),
])
def test_refuses(path, value):
    manifest = copy.deepcopy(mf.load_manifest())
    section, index, key = path
    manifest[section][index][key] = value
    with pytest.raises(mf.ManifestError):
        mf.validate(manifest)


def test_an_unknown_cell_file_is_an_error():
    with pytest.raises(mf.ManifestError):
        mf.resolve_cell(mf.load_manifest(), "no-such.cell")
