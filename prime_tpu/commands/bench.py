"""`prime bench` — the perf trajectory and the loadgen harness from the CLI.

`delta` renders the committed BENCH_*.json rounds into the per-PR table
(stdlib-only — safe on machines without jax); `smoke` runs the CPU loadgen
fleet smoke and writes its SLO report + BENCH-schema record (docs/
benchmarking.md). The chip is measured by `python3 benchmark/run.py`
(BENCHMARK.json, PERF.md), not here.
"""

from __future__ import annotations

import json

import click


@click.group(name="bench")
def bench_group() -> None:
    """Benchmark trajectory tools (see docs/benchmarking.md)."""


@bench_group.command("delta")
@click.option("--root", default=".", help="Directory holding BENCH_*.json.")
@click.option("--pattern", default=None,
              help="Restrict to one round-file glob (default: BENCH_*.json "
                   "and MULTICHIP_*.json merged — multichip rounds render "
                   "their own mc-prefixed rows, never cross-backend deltas).")
@click.option("--output", "as_json", is_flag=False, flag_value="json", default=None,
              help="Set to 'json' for machine-readable output.")
@click.option("--min-rounds", type=int, default=2,
              help="Exit nonzero below this many parseable rounds.")
def bench_delta(root: str, pattern: str | None, as_json: str | None, min_rounds: int) -> None:
    """Render the per-PR perf delta table across committed bench rounds."""
    from prime_tpu.loadgen.perf_delta import (
        delta_json,
        delta_table,
        load_all_rounds,
        load_rounds,
    )

    rounds = load_rounds(root, pattern) if pattern else load_all_rounds(root)
    if as_json == "json":
        click.echo(json.dumps(delta_json(rounds), indent=2))
    else:
        click.echo(delta_table(rounds, min_rounds=min_rounds))
    if len(rounds) < min_rounds:
        raise SystemExit(1)


@bench_group.command("smoke")
@click.option("--output", default="loadgen-smoke", help="Artifact directory.")
@click.option("--scenario", default="smoke",
              help="Loadgen scenario name (prime_tpu.loadgen.SCENARIOS).")
@click.option("--seed", type=int, default=None,
              help="Schedule seed. Default: 0 (PRIME_LOADGEN_SEED).")
@click.option("--replicas", type=int, default=2, help="In-process fleet size.")
@click.option("--mesh", default=None, metavar="SPEC",
              help="Sharded-replica mesh spec (e.g. 'dp=1,fsdp=2,tp=2'): "
                   "each replica spans that mesh (MULTICHIP rounds).")
@click.option("--time-scale", type=float, default=1.0,
              help="Multiplier on scheduled arrival/cancel offsets.")
def bench_smoke(
    output: str, scenario: str, seed: int | None, replicas: int,
    mesh: str | None, time_scale: float
) -> None:
    """Run the CPU loadgen fleet smoke end to end (no TPU required)."""
    from prime_tpu.loadgen.smoke import run_smoke

    outcome = run_smoke(
        output, scenario=scenario, seed=seed, replicas=replicas, mesh=mesh,
        time_scale=time_scale, log=click.echo,
    )
    if not outcome["ok"]:
        raise SystemExit(1)


@bench_group.command("sentinel")
@click.option("--root", default=".", help="Directory holding BENCH_*.json.")
@click.option("--report", default=None, type=click.Path(exists=True),
              help="Fresh loadgen SLO report (slo_report.json) appended as "
                   "the candidate round — what CI gates before a record is "
                   "committed.")
@click.option("--band-pct", type=float, default=None,
              help="Regression band in percent (default: "
                   "PRIME_SENTINEL_BAND_PCT, 50).")
@click.option("--min-history", type=int, default=None,
              help="Prior rounds a metric needs before it gates (default: "
                   "PRIME_SENTINEL_MIN_HISTORY, 3).")
@click.option("--all-metrics", is_flag=True,
              help="Gate every delta-table row instead of the curated "
                   "headline set (CPU-smoke latency percentiles are noisy; "
                   "see docs/observability.md).")
@click.option("--output", "as_json", is_flag=False, flag_value="json", default=None,
              help="Set to 'json' for machine-readable output.")
def bench_sentinel(
    root: str, report: str | None, band_pct: float | None,
    min_history: int | None, all_metrics: bool, as_json: str | None,
) -> None:
    """Gate the perf trajectory: exit nonzero when the newest round (or a
    fresh --report) regresses beyond the configured bands. Same
    implementation as the delta table's `sentinel verdict` row
    (obs/sentinel.trajectory_verdicts) — stdlib-only, no jax."""
    from prime_tpu.loadgen.perf_delta import load_all_rounds, round_from_report
    from prime_tpu.obs.sentinel import trajectory_gate

    rounds: list = list(load_all_rounds(root))
    if report is not None:
        with open(report) as fh:
            rounds.append(round_from_report(json.load(fh), label="candidate"))
    gate = trajectory_gate(
        rounds,
        band_pct=band_pct,
        min_history=min_history,
        gate_metrics="all" if all_metrics else None,
    )
    if as_json == "json":
        click.echo(json.dumps(gate, indent=2))
    else:
        for verdict in gate["verdicts"]:
            line = f"{verdict['label']:<24} {verdict['verdict']}"
            if verdict["checked"]:
                line += f" ({verdict['checked']} gated metrics)"
            click.echo(line)
            for reg in verdict["regressions"]:
                click.echo(
                    f"    {reg['metric']}: {reg['value']:g} vs baseline "
                    f"{reg['baseline']:g} ({reg['delta_pct']:+.1f}%)"
                )
        latest = gate["latest"]
        click.echo(
            "sentinel: "
            + ("no rounds" if latest is None else f"latest={latest['label']} verdict={latest['verdict']}")
        )
    if not gate["ok"]:
        raise SystemExit(1)


@bench_group.command("autotune")
@click.option("--kernel", "kernels", multiple=True,
              help="Restrict the sweep to named kernels (repeatable; "
                   "default: all of ops/autotune.CANDIDATES).")
@click.option("--output", default=None, metavar="DIR",
              help="Artifact directory (default: the kernel_configs "
                   "resolution dir — PRIME_TPU_KERNEL_CONFIG_DIR or the "
                   "in-package registry).")
@click.option("--repeats", type=int, default=3,
              help="Timed runs per candidate (best-of).")
@click.option("--dry-run", is_flag=True,
              help="Tiny shapes, interpret mode, trimmed grids: proves the "
                   "sweep -> artifact -> resolution round-trip on CPU. "
                   "Timings are meaningless; point --output somewhere "
                   "disposable.")
def bench_autotune(
    kernels: tuple[str, ...], output: str | None, repeats: int, dry_run: bool
) -> None:
    """Time candidate pallas block configs and persist this device kind's
    winners (docs/kernels.md "Kernel campaign & autotune")."""
    from prime_tpu.ops import kernel_configs
    from prime_tpu.ops.autotune import run_autotune

    kind = kernel_configs.device_kind()
    click.echo(f"autotune: device_kind={kind} dry_run={dry_run}")
    winners = run_autotune(
        kernels=list(kernels) or None, dry_run=dry_run, repeats=repeats,
        log=click.echo,
    )
    if not winners:
        click.echo("no kernel produced a viable candidate; nothing saved")
        raise SystemExit(1)
    path = kernel_configs.save_artifact(winners, directory=output, kind=kind)
    click.echo(f"saved {len(winners)} kernel config(s) -> {path}")
    # prove the artifact round-trips through the resolution path the
    # kernels actually use (fails loudly here instead of silently
    # degrading to defaults at first dispatch)
    if output:
        import os

        os.environ["PRIME_TPU_KERNEL_CONFIG_DIR"] = output
        kernel_configs.invalidate_cache()
    loaded = kernel_configs.load_tuned(kind)
    if loaded is None:
        raise SystemExit("artifact failed to load back through kernel_configs")
    for name, params in loaded.items():
        resolved = {p: kernel_configs.resolve(name, p) for p in params}
        click.echo(f"  {name}: resolves {resolved}")
    click.echo(f"config source now: {kernel_configs.source()}")
