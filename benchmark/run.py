"""Runs one cell of the benchmark once and prints one result line last.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A new process loads, warms up, measures for ``--seconds`` and exits (a window in
which something compiled counts as warm-up and is measured again). Everything
about a cell is data found by name (harness/manifest.py); this file holds no
cell, model or metric name. Without a TPU that ``peaks.json`` knows, it exits
non-zero and prints no result. Not driver options: ``--rehearse`` (the same code
on the CPU at the configuration's rehearsal size; prints no result line),
``--seeds a,b,c`` (several windows in one process, one set-up; no result line),
``--rates r1,r2`` (a window at each rate in one process: the sweep; no result
line), ``--degrade int8-weights|int8-kv`` (the program's own lower-precision
paths, as the control of ``correct``), ``--control int8`` (the reference's own),
``--dump DIR`` (records, timelines and the trace's inventory, to read by hand) and
``--drain S`` (how long after the window an unfinished request is waited for).
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse
import importlib
import json
import os
import sys
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO_ROOT))


def parse(argv=None) -> argparse.Namespace:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--rehearse", action="store_true")
    parser.add_argument("--seeds", default=None)
    parser.add_argument("--degrade", default=None)
    parser.add_argument("--control", default=None)
    parser.add_argument("--rates", default=None)
    parser.add_argument("--dump", default=None)
    parser.add_argument("--drain", type=float, default=90.0)
    args = parser.parse_args(argv)
    args.rates = [float(r) for r in args.rates.split(",")] if args.rates else None
    args.seeds = [int(s) for s in args.seeds.split(",")] if args.seeds else [args.seed]
    return args


def device_gate(chips: int, rehearse: bool) -> tuple[dict, dict]:
    """No chip, no number: the device has to be a TPU that peaks.json knows."""
    import jax

    from benchmark.harness.manifest import BENCH_DIR

    peaks = json.loads((BENCH_DIR / "peaks.json").read_text())["devices"]
    first = jax.devices()[0]
    device = {"platform": first.platform, "kind": first.device_kind, "count": jax.device_count()}
    if rehearse:  # stand-in peaks so that the readers run; nothing here is a result
        return device, peaks.get(first.device_kind, next(iter(peaks.values())))
    if first.platform != "tpu":
        raise SystemExit(f"benchmark: no TPU here (platform {first.platform!r}); no number without the chip")
    if first.device_kind not in peaks:
        raise SystemExit(f"benchmark: device kind {first.device_kind!r} is not in peaks.json")
    if jax.device_count() < chips:
        raise SystemExit(f"benchmark: the cell asks for {chips} chips, JAX finds {jax.device_count()}")
    return device, peaks[first.device_kind]


def main(argv=None) -> int:
    args = parse(argv)
    from benchmark.harness.manifest import load_manifest, resolve_cell

    manifest = load_manifest()
    resolved = resolve_cell(manifest, args.workload)
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ.setdefault("PRIME_TPU_PALLAS_INTERPRET", "1")
    import prime_tpu  # noqa: F401  places the compile cache (JAX_COMPILATION_CACHE_DIR, else <checkout>/.jax_cache)
    import jax

    # every program the run builds goes to the persistent cache, the small ones
    # too: a later run of the cell in this checkout then compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    device, peaks = device_gate(resolved["entry"]["chips"], args.rehearse)
    system = importlib.import_module(f"benchmark.systems.{resolved['config']['system']}")
    result = system.main({"resolved": resolved, "manifest": manifest, "args": args,
                          "peaks": peaks, "t_start": T_START, "device": device})
    if result is not None:
        print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
