"""The system under test: ``prime serve`` brought up in this process.

``serve_model()`` is the call ``prime serve`` makes, so the HTTP server, the
generator, the continuous-batching engine, the prefix cache and the mesh are the
product's, built the product's way. The one thing set afterwards is the backend's
tokenizer (the yardstick's numeric one). Because the server lives in the process
that holds the chip, a traced run can wrap part of the window in
``jax.profiler.trace`` and read the device planes itself.
"""

from __future__ import annotations

import gc
import json
import subprocess
import sys
import tempfile
import threading
import time
from pathlib import Path

from benchmark.harness import check, records as rec, schedule as sched, xplane
from benchmark.harness.manifest import BENCH_DIR
from benchmark.harness.tokenizer import NumericTokenizer

FIRST_RUN_LIMIT_S = 1200.0  # what a cell's first run in a checkout may take
RESTART_S, CHECK_S, SPARE_S = 80.0, 40.0, 60.0  # drain, stop, bring-up from the cache; the reference; room

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


def log(**fields) -> None:
    """An earlier (non-final) output line."""
    print(json.dumps(fields), flush=True)


class CompileCounter:
    """Programs built (or loaded from the persistent cache) since the start."""

    def __init__(self) -> None:
        import jax.monitoring as monitoring

        self.built = 0
        self.loaded = 0
        self.seconds = 0.0
        self.names: list[str] = []  # every program built or loaded, in order
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, duration: float, **kwargs) -> None:
        if event == COMPILE_EVENT:
            self.built += 1
            self.seconds += duration
            self.names.append(f"{kwargs.get('fun_name', '?')}:{duration:.3f}")

    def _on_event(self, event: str, **kwargs) -> None:
        if event == CACHE_HIT_EVENT:
            self.loaded += 1

    def read(self) -> dict:
        return {"requests": self.built, "cache_loads": self.loaded,
                "compiles": self.built - self.loaded, "seconds": self.seconds}


class FlightCollector(threading.Thread):
    """Copies finished request timelines out of the program's flight recorder
    (a ring of 256) while the run goes on. Traced runs only."""

    def __init__(self, flight) -> None:
        super().__init__(daemon=True)
        self.flight, self.by_trace, self._seen, self._halt = flight, {}, set(), threading.Event()

    def poll(self) -> None:
        for summary in self.flight.summaries(limit=256)["recent"]:
            if summary["id"] in self._seen:
                continue
            timeline = self.flight.get(summary["id"])
            if timeline is not None and timeline.get("trace_id"):
                self._seen.add(summary["id"])
                self.by_trace[timeline["trace_id"]] = timeline

    def run(self) -> None:
        while not self._halt.wait(0.5):
            self.poll()

    def finish(self) -> dict:
        self._halt.set()
        self.join(timeout=5)
        self.poll()
        return self.by_trace


def check_preset(model_sizes: dict, preset: str, fields: dict) -> None:
    """The program's preset of this name has to have the published sizes."""
    from prime_tpu.models import get_config

    program = get_config(preset)
    for key, attr in fields.items():
        ours, theirs = model_sizes[key], getattr(program, attr)
        if ours != theirs:
            raise SystemExit(f"preset {preset}: {attr}={theirs!r} but the configuration file says {key}={ours!r}")


def serve_args(resolved: dict, rehearse: bool, degrade: str | None) -> dict:
    config, cell = resolved["config"], resolved["cell"]
    args = {**config["serve"], **cell.get("serve", {})}
    if rehearse:
        args.update(config["rehearsal"]["serve"])
    if degrade == "int8-weights":
        args.update(weight_quant=True, warmup=False)
    elif degrade == "int8-kv":
        args.update(kv_quant=True, warmup=False)
    elif degrade:
        raise SystemExit(f"unknown --degrade {degrade!r}")
    return args


def bring_up(resolved: dict, rehearse: bool, degrade: str | None):
    from prime_tpu.serve.server import serve_model

    config = resolved["config"]
    model = config["rehearsal"] if rehearse else config
    check_preset(model["model"], model["preset"], config["preset_fields"])
    args = serve_args(resolved, rehearse, degrade)
    server = serve_model(model["preset"], continuous=True, port=0, **args).start()
    backend = server.generator
    backend.tokenizer = NumericTokenizer(eos_id=backend.engine.eos_id, pad_id=backend.engine.pad_id)
    return server, backend, args, model


def served_precision(engine, stated: str, at_least: int = 4096) -> dict:
    """Bytes the engine holds in arrays narrower than the precision the
    configuration states (int8 weights, an int8 or int4 cache), by dtype. Every
    pytree among the engine's attributes is walked, so no attribute is named;
    masks and arrays of fewer than ``at_least`` elements are left out."""
    import jax
    import jax.numpy as jnp

    def bits(dtype) -> int:
        return (jnp.iinfo if jnp.issubdtype(dtype, jnp.integer) else jnp.finfo)(dtype).bits

    floor = bits(jnp.dtype(stated))
    narrow: dict[str, int] = {}
    walked = 0
    for value in vars(engine).values():
        for leaf in jax.tree_util.tree_leaves(value):
            if not isinstance(leaf, jax.Array) or leaf.size < at_least or leaf.dtype == jnp.bool_:
                continue
            walked += leaf.nbytes
            if bits(leaf.dtype) < floor:
                narrow[leaf.dtype.name] = narrow.get(leaf.dtype.name, 0) + leaf.nbytes
    return {"stated": stated, "bytes_walked": walked, "narrower": narrow,
            "bytes_below_stated": sum(narrow.values())}


def memory_stats() -> dict:
    import jax

    stats = [d.memory_stats() or {} for d in jax.local_devices()]
    return {"in_use": max((s.get("bytes_in_use", 0) for s in stats), default=0),
            "peak": max((s.get("peak_bytes_in_use", 0) for s in stats), default=0),
            "limit": max((s.get("bytes_limit", 0) for s in stats), default=0)}


def drive(schedule: dict, url: str, model_id: str, drain_s: float, workdir: Path, tag: str):
    """Start the load generator; returns (process, t0, records path)."""
    schedule_path, records_path = workdir / f"schedule-{tag}.json", workdir / f"records-{tag}.jsonl"
    schedule_path.write_text(json.dumps(schedule))
    child = subprocess.Popen(
        [sys.executable, str(BENCH_DIR / "harness" / "client.py"), str(schedule_path),
         str(records_path), url, model_id, str(drain_s)],
        stdout=subprocess.PIPE, text=True)
    line = child.stdout.readline()
    if not line:
        child.wait()
        raise SystemExit("the load generator died before its first line")
    return child, float(json.loads(line)["t0"]), records_path


def sleep_until(t: float) -> None:
    delay = t - time.monotonic()
    if delay > 0:
        time.sleep(delay)


def run_traffic(resolved: dict, rehearse: bool) -> dict:
    traffic = resolved["traffic"]
    return {**traffic, **traffic.get("rehearsal", {})} if rehearse else traffic


def one_window(resolved, backend, server, args, model, seed, seconds, trace, workdir,
               counter, t_start, rehearse=False, drain_s=90.0, tag="") -> dict:
    """Schedule -> child -> window -> records, for one seed. No check yet."""
    import jax

    traffic = run_traffic(resolved, rehearse)
    schedule = sched.materialise(traffic, seed, seconds, model["model"]["vocab_size"])
    log(schedule_digest=sched.digest(schedule), seed=seed, turns=sched.count_turns(schedule),
        primer=len(schedule["primer"]))
    engine = backend.engine
    collector = None
    if trace:
        collector = FlightCollector(engine.flight)
        collector.start()
    child, t0, records_path = drive(schedule, server.url, server.model_id, drain_s, workdir, f"{seed}{tag}")
    w0 = t0 + schedule["ramp_s"]
    w1 = w0 + seconds
    run = {"seed": seed, "seconds": seconds, "window": (w0, w1), "serve": args,
           "model": model["model"], "schedule": schedule}
    try:
        sleep_until(w0)
        run["setup_s"] = time.monotonic() - t_start
        before, compiles_before = engine.registry.snapshot(), counter.read()
        names_before = len(counter.names)
        if trace:
            trace_dir = workdir / f"trace-{seed}{tag}"
            offset = float(traffic.get("trace_offset_s", 2.0))
            span = min(float(traffic.get("trace_s", 4.0)), max(0.5, seconds - offset - 0.5))
            sleep_until(w0 + offset)
            trace_before = engine.registry.snapshot()
            jax.profiler.start_trace(str(trace_dir))
            t_trace0 = time.monotonic()
            sleep_until(t_trace0 + span)
            t_trace1 = time.monotonic()
            jax.profiler.stop_trace()
            run["trace_span"] = (t_trace0, t_trace1)
            run["trace_counters"] = (trace_before, engine.registry.snapshot())
            run["trace_dir"] = trace_dir
        sleep_until(w1)
        run["counters"] = (before, engine.registry.snapshot())
        after = counter.read()
        run["compiles"] = {k: after[k] - compiles_before[k] for k in after}
        run["compiles"]["slowest"] = sorted(counter.names[names_before:],
                                            key=lambda n: -float(n.rsplit(":", 1)[1]))[:6]
        child.wait(timeout=drain_s + 30.0)
    finally:
        if child.poll() is None:
            child.kill()
            child.wait()
        child.stdout.close()
    run["memory"] = memory_stats()
    run["records"] = rec.load(records_path)
    run["flights"] = collector.finish() if collector else {}
    run["clock_offset"] = time.time() - time.monotonic()
    run["after_drain"] = engine.registry.snapshot()
    tokenizer = backend.tokenizer
    for record in run["records"]:
        record["prompt_ids"] = tokenizer.prompt_ids_for(record["numeric_prompt"])
    return run


def reduce_trace(run: dict, platform: str, keep_inventory: bool = False) -> None:
    if "trace_dir" not in run:
        run["trace"] = None
        return
    layout = json.loads((BENCH_DIR / "layer_metrics" / "programs.json").read_text())[platform]
    events = xplane.read_events(run["trace_dir"])
    run["trace"] = xplane.reduce(events, layout)
    if keep_inventory:
        run["trace_inventory"] = xplane.inventory(events)


def window_line(run: dict, discarded: bool = False) -> dict:
    """Prints the window's line; returns its end-to-end numbers."""
    w0, w1 = run["window"]
    e2e = rec.end_to_end(run["records"], w0, w1)
    mid = (w0 + w1) / 2
    lag = [(r["sent"] - r["due"]) * 1e3 for r in rec.in_window(run["records"], w0, w1)
           if r["sent"] is not None]
    log(seed=run["seed"], rate=run["rate"], discarded_as_warm_up=discarded,
        ttft_p50_ms_by_half=[rec.end_to_end(run["records"], a, b)["ttft_p50_ms"]
                             for a, b in ((w0, mid), (mid, w1))],
        longest_silence=rec.longest_silence(run["records"], w0, w1),
        requests_in_window=e2e["attempted"], failed=e2e["failed"],
        generator_lag_p90_ms=rec.percentile(lag, 90), compiles_in_window=run["compiles"],
        prefix_cache={name: counter_delta(run["counters"], f"serve_prefix_{name}_total")
                      for name in ("hits", "paged_seeds", "assembles", "evictions")},
        memory=run["memory"], setup_s=run["setup_s"],
        e2e={k: v for k, v in e2e.items() if k not in ("attempted", "failed")})
    return e2e


def dump(run: dict, where: Path) -> None:
    """What a person looks at by hand: records, timelines, the trace's inventory."""
    where.mkdir(parents=True, exist_ok=True)
    tag = f"{run['seed']}-{run.get('rate', 'cell')}"
    slim = [{k: v for k, v in r.items() if k not in ("numeric_prompt", "prompt_ids", "ids")}
            for r in run["records"]]
    (where / f"records-{tag}.json").write_text(json.dumps(slim))
    (where / f"flights-{tag}.json").write_text(json.dumps(list(run["flights"].values())[:400]))
    if run.get("trace_inventory"):
        (where / f"trace-inventory-{tag}.json").write_text(json.dumps(run["trace_inventory"], indent=1))
        (where / f"trace-reduced-{tag}.json").write_text(json.dumps(run["trace"], indent=1))


def tear_down(server, backend) -> dict:
    """Stop the server and free what the program held on the device."""
    import jax

    server.stop()
    server.generator = None
    backend.engine = None
    gc.collect()
    jax.clear_caches()
    gc.collect()
    return memory_stats()


def run_check(resolved, model, args, runs: list[dict], control: str | None, eos_id: int,
              rehearse: bool = False) -> dict:
    """The reference over a sample of every run's finished requests."""
    import importlib

    import jax

    config = resolved["config"]
    reference = importlib.import_module(f"benchmark.reference.{config['reference']}")
    t = time.monotonic()
    weights = reference.make_weights(model["model"], config["assumed"]["weights_seed"],
                                     jax.local_devices())
    jax.block_until_ready(weights)
    out = {"weights_s": time.monotonic() - t, "per_run": []}
    answer_pad = int(run_traffic(resolved, rehearse)["answer_pad"])
    for run in runs:
        picks = check.sample(run["records"], run["seed"], int(config["limits"]["sample"]))
        sequences = [{"prompt": r["prompt_ids"], "answer": r["ids"], "max_tokens": r["max_tokens"]}
                     for r in picks if r["prompt_ids"]]
        missing = len(picks) - len(sequences)
        # a finished stream carries max_tokens ids, or fewer (then it has to have
        # ended on eos, which the reference checks for the sampled ones); never more
        malformed = sum(1 for r in run["records"] if r["phase"] == "run" and r["done"]
                        and not 0 < len(r["ids"]) <= r["max_tokens"])
        t = time.monotonic()
        result = check.gaps({**config, "model": model["model"]}, weights, sequences,
                            args["slot_capacity"], answer_pad, eos_id, control)
        result.update(sampled=len(sequences), unjoined=missing, malformed_streams=malformed,
                      seconds=time.monotonic() - t)
        out["per_run"].append(result)
    return out


def counter_delta(pair, name: str, labels: dict | None = None) -> float:
    def total(snapshot):
        family = snapshot.get(name) or {}
        value = 0.0
        for series in family.get("series", []):
            if labels is not None and series.get("labels", {}) != labels:
                continue
            value += series["sum"] if "sum" in series else series.get("value", 0.0)
        return value
    return total(pair[1]) - total(pair[0])


def read_layer_metrics(manifest_metrics: list[dict], run: dict, resolved: dict, peaks: dict) -> dict:
    """Each per-layer metric has a reader of its own, found by its name."""
    import importlib

    context = {**run, "config": resolved["config"], "cell": resolved["cell"], "peaks": peaks,
               "counter_delta": counter_delta}
    out = {}
    for metric in manifest_metrics:
        reader = importlib.import_module(f"benchmark.layer_metrics.{metric['name'].replace('.', '_').replace('-', '_')}")
        value = reader.read(context)
        if value is not None:
            out[metric["name"]] = {"value": float(value), "unit": metric["unit"]}
    return out


def compare(run: dict, seen: dict, limits: dict, weights_s: float, precision: dict) -> bool:
    """Prints each number compared beside its limit; True when all hold."""
    served = seen.get("served", {"gap_mean": float("inf"), "gap_max": float("inf")})
    failed_counter = counter_delta((run["counters"][0], run["after_drain"]), "serve_requests_failed_total")
    compared = [
        ("logit_gap_mean", served["gap_mean"], limits["logit_gap_mean"]),
        ("logit_gap_max", served["gap_max"], limits["logit_gap_max"]),
        ("bytes_below_stated_precision", precision["bytes_below_stated"], 0),
        ("precision_unread", float(precision["bytes_walked"] == 0), 0),
        ("malformed_streams", seen["malformed_streams"], 0),
        ("unjoined_prompts", seen["unjoined"], 0),
        ("requests_failed_by_engine", failed_counter, 0),
        ("sampled_too_few", float(seen["sampled"] < 1), 0),
    ]
    log(seed=run["seed"], rate=run["rate"],
        compared=[{"name": n, "value": v, "limit": l} for n, v, l in compared],
        check=seen, reference_weights_s=weights_s)
    return check.verdict(compared)


def main(ctx: dict) -> dict | None:
    """One run of one cell (or, with several seeds, the readings of each: no
    result line then). ``ctx``: resolved, manifest, args, peaks, t_start, device."""
    from benchmark.harness.manifest import metrics_for

    resolved, args_ns, t_start = ctx["resolved"], ctx["args"], ctx["t_start"]
    counter = CompileCounter()
    server, backend, args, model = bring_up(resolved, args_ns.rehearse, args_ns.degrade)
    eos_id = backend.engine.eos_id
    precision = served_precision(backend.engine, resolved["config"]["precision"]["stated"])
    log(device=ctx["device"], memory_at_ready=memory_stats(), serve=args, served_precision=precision,
        ready_s=time.monotonic() - t_start,
        warmup_programs=counter_delta(({}, backend.engine.registry.snapshot()), "serve_warmup_programs"))
    runs = []
    single = len(args_ns.seeds) == 1 and not args_ns.rates and not args_ns.degrade
    with tempfile.TemporaryDirectory(prefix="bench-") as tmp:
        workdir = Path(tmp)
        for seed, rate in [(s, r) for r in (args_ns.rates or [None]) for s in args_ns.seeds]:
            if rate is not None:
                key = next(k for k in resolved["traffic"] if k.startswith("rate_"))
                resolved["traffic"][key] = rate
            run = one_window(resolved, backend, server, args, model, seed, args_ns.seconds,
                             args_ns.trace, workdir, counter, t_start, args_ns.rehearse,
                             drain_s=args_ns.drain)
            run["rate"] = rate
            another_s = run["schedule"]["ramp_s"] + args_ns.seconds + RESTART_S + CHECK_S
            if (single and run["compiles"]["compiles"] > 0
                    and time.monotonic() - t_start + another_s < FIRST_RUN_LIMIT_S - SPARE_S):
                # the first run in a checkout: programs the engine builds on first
                # use were compiled inside the window. That window was warm-up and
                # is set-up; the server starts anew, as a later run finds it, and
                # the same schedule is measured with every program in the cache.
                # Once, and only where it ends inside the time a first run may take
                window_line(run, discarded=True)
                tear_down(server, backend)
                server, backend, args, model = bring_up(resolved, args_ns.rehearse, args_ns.degrade)
                run = one_window(resolved, backend, server, args, model, seed, args_ns.seconds,
                                 args_ns.trace, workdir, counter, t_start, args_ns.rehearse,
                                 drain_s=args_ns.drain, tag="-again")
                run["rate"] = rate
            reduce_trace(run, ctx["device"]["platform"], keep_inventory=bool(args_ns.dump))
            run["e2e"] = window_line(run)
            runs.append(run)
            if args_ns.dump:
                dump(run, Path(args_ns.dump))
        memory = runs[-1]["memory"]
        # read again once the windows are over: what served them, not what was built
        precision = served_precision(backend.engine, resolved["config"]["precision"]["stated"])
        freed = tear_down(server, backend)
        del server, backend
        log(memory_after_free=freed)
        verdicts = run_check(resolved, model, args, runs, args_ns.control, eos_id, args_ns.rehearse)
    results = [(run, compare(run, seen, resolved["config"]["limits"], verdicts["weights_s"], precision))
               for run, seen in zip(runs, verdicts["per_run"])]
    if len(runs) != 1 or args_ns.degrade or args_ns.rates:
        log(rehearsal=bool(args_ns.rehearse), degrade=args_ns.degrade,
            correct=[ok for _, ok in results])
        return None
    run, correct = results[0]
    manifest, workload = ctx["manifest"], resolved["entry"]["name"]
    device = {**ctx["device"], "memory_peak_bytes": memory["peak"]}
    if args_ns.trace:
        metrics = read_layer_metrics(metrics_for(manifest, "per_layer", workload), run, resolved, ctx["peaks"])
        trace = run["trace"]
        if trace is None:
            raise SystemExit("traced run, but no operation ran on a device plane")
        device.update(busy_s=trace["busy_s"], window_s=trace["window_s"])
        extra = {"breakdown": {"device_ops": trace["device_ops"], "idle_gaps": trace["idle_gaps"]}}
        log(trace_programs=trace["programs"], module_seconds=trace["module_seconds"])
    else:
        values = {**run["e2e"], "setup_s": run["setup_s"]}
        metrics = {m["name"]: {"value": float(values[m["name"]]), "unit": m["unit"]}
                   for m in metrics_for(manifest, "end_to_end", workload)
                   if values.get(m["name"]) is not None}
        extra = {}
    result = {"correct": bool(correct), "attempted": run["e2e"]["attempted"],
              "failed": run["e2e"]["failed"], "metrics": metrics, "device": device, **extra}
    if args_ns.rehearse:
        log(rehearsal=True, would_be=result)
        return None
    return result
