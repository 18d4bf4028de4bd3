"""`prime serve` — OpenAI-compatible inference on the local TPU slice.

The TPU-native counterpart of the platform's hosted inference endpoint
(reference api/inference.py consumes api.pinference.ai): serve a model —
optionally pjit-sharded over the slice with --slice/--tp — and point any
OpenAI client (including this CLI's own `prime inference chat`, via
PRIME_INFERENCE_URL) at it.
"""

from __future__ import annotations

import signal

import click

from prime_tpu.utils.render import Renderer, output_options


def _raise_keyboard_interrupt(signum, frame):
    raise KeyboardInterrupt


def _echo_device_memory(when: str) -> None:
    """One line per local device with the allocator's view (TPU only: the
    CPU backend reports no memory_stats) — what a replica holds once ready,
    and its peak when it stops."""
    import jax

    for device in jax.local_devices():
        stats = device.memory_stats()
        if stats:
            click.echo(
                f"  device {device.id} ({device.device_kind}) memory at {when}: "
                f"bytes_in_use={stats.get('bytes_in_use', 0)} "
                f"peak_bytes_in_use={stats.get('peak_bytes_in_use', 0)} "
                f"bytes_limit={stats.get('bytes_limit', 0)}"
            )


@click.group(name="serve", invoke_without_command=True)
@click.option("--model", "-m", default=None, help="Model preset or local HF checkpoint dir.")
@click.option("--checkpoint", default=None, help="Local HF checkpoint dir for weights.")
@click.option("--tokenizer", default=None)
@click.option("--slice", "slice_name", default=None, help="Shard over this TPU slice's mesh.")
@click.option(
    "--mesh", "mesh_spec", default=None, metavar="SPEC",
    help="Sharded replica (--continuous): declarative serving-mesh axes, "
         "e.g. 'dp=1,fsdp=2,tp=2' or 'dp,fsdp,tp' (the last unsized axis "
         "absorbs remaining devices). One engine spans the whole mesh: "
         "params and paged KV shard onto it, decode runs the shard_mapped "
         "flash kernel when eligible. Default: unset (PRIME_SERVE_MESH). "
         "Mutually exclusive with --slice.",
)
@click.option("--tp", "tensor_parallel", type=int, default=None)
@click.option("--sp", "sequence_parallel", type=click.IntRange(min=2), default=None,
              help="Sequence-parallel axis for --slice: shard the KV cache's "
                   "slot dimension across the slice (long-context serving).")
@click.option("--kv-quant", is_flag=True, help="int8 KV cache (halved decode HBM traffic).")
@click.option("--weight-quant", is_flag=True, help="Quantized weights (halved+ weight HBM traffic).")
@click.option(
    "--weight-bits", type=click.Choice(["8", "4"]), default="8",
    help="Weight quantization width for --weight-quant: 8 = W8A16 "
         "per-channel, 4 = W4A16 group-wise (another 2x fewer weight bytes).",
)
@click.option("--adapter", default=None, type=click.Path(exists=True),
              help="LoRA adapter dir (from train local --lora) to merge into the model.")
@click.option(
    "--adapters", "adapters_spec", default=None, metavar="NAME=DIR,...",
    help="Batched multi-LoRA serving (--continuous): comma-separated "
         "name=artifact-dir entries loaded UNMERGED into a device-resident "
         "bank — the OpenAI `model` field selects the adapter per request "
         "and a mixed-adapter batch decodes as one program. "
         "Default: unset (PRIME_SERVE_ADAPTERS).",
)
@click.option("--host", default="127.0.0.1")
@click.option("--port", type=int, default=8000)
@click.option(
    "--continuous", is_flag=True,
    help="Continuous batching: concurrent requests share the chip via KV-cache "
    "slots; streaming emits tokens as they decode.",
)
@click.option("--slots", type=int, default=8, help="Max concurrent requests (--continuous).")
@click.option(
    "--slot-capacity", type=int, default=2048,
    help="Per-request KV capacity in tokens (--continuous).",
)
@click.option(
    "--chunk", type=int, default=8,
    help="Decode steps per dispatch — lower admits new requests sooner (--continuous).",
)
@click.option(
    "--speculative/--no-speculative", "speculative", default=None,
    help="Prompt-lookup speculative decoding (greedy: exact tokens; sampled: "
         "exact distribution). With --continuous, draft proposal + verify "
         "run device-resident and ride the overlap pipeline and the --mesh "
         "sharded replica. Default: off (PRIME_SERVE_SPEC).",
)
@click.option("--draft-len", type=click.IntRange(min=1), default=None,
              help="Speculative draft tokens per verify window. "
                   "Default: 4 (PRIME_SERVE_DRAFT_LEN).")
@click.option(
    "--overlap/--no-overlap", "overlap", default=None,
    help="Overlapped decode pipeline (--continuous): dispatch chunk N+1 "
         "before syncing chunk N so host bookkeeping hides inside device "
         "compute. Default: on (PRIME_SERVE_OVERLAP).",
)
@click.option(
    "--warmup/--no-warmup", "warmup", default=None,
    help="Compile the engine's full program set at startup so no cold XLA "
         "compile lands mid-request (--continuous). Default: off "
         "(PRIME_SERVE_WARMUP).",
)
@click.option(
    "--profile/--no-profile", "profile", default=None,
    help="Sampled device-time step clock (--continuous): fence 1-of-N "
         "dispatches per phase into serve_device_step_seconds{phase=...} "
         "plus XLA-compile, HBM, and cost-model MFU accounting; "
         "/admin/profile and `prime serve profile` capture a Perfetto "
         "trace window. Default: off (PRIME_SERVE_PROFILE).",
)
@click.option(
    "--prefix-cache-mb", type=float, default=None,
    help="Byte budget (MiB) of the radix prefix-KV cache: shared prompt "
         "blocks are cached once and reused across admissions; 0 disables "
         "(--continuous). Default: 256 (PRIME_SERVE_PREFIX_CACHE_MB). A model "
         "whose slots hold recurrent state has snapshots of it kept beside the "
         "K/V, at a stride from its shapes, inside the same budget: such a model "
         "gets no default and asks by size (a snapshot is megabytes), without a "
         "host tier. Refused: a model whose slots hold a ring (a window layer's "
         "last columns); a stride at or over the slots' length (no prompt would "
         "reach a snapshot); --speculative over any state that is not K/V columns.",
)
@click.option(
    "--prefix-cache-host-mb", type=float, default=None,
    help="Byte budget (MiB) of the prefix cache's host-RAM spill tier "
         "(--continuous): the device LRU demotes cold KV segments to pinned "
         "host buffers instead of freeing them, and a later hit re-uploads "
         "through the same one-dispatch assemble path; 0 disables. "
         "Default: 0 (PRIME_SERVE_PREFIX_CACHE_HOST_MB). Refused for a model "
         "whose prefix cache keeps snapshots of state.",
)
@click.option(
    "--adapter-max-inflight", type=int, default=None,
    help="Per-tenant fair admission (--adapters): max admitted slots one "
         "adapter (base included) may hold; queued overflow waits in its "
         "own bucket while other tenants admit. 0 = uncapped. "
         "Default: 0 (PRIME_SERVE_ADAPTER_MAX_INFLIGHT).",
)
@click.option(
    "--adapter-weight", "adapter_weight_entries", multiple=True, metavar="NAME=K",
    help="Weighted admission shares (--adapters, repeatable): give tenant "
         "NAME K admission slots per fair-rotation instead of 1 ('base' is "
         "the base model's tenant). Unlisted tenants keep weight 1. "
         "Default: uniform (PRIME_SERVE_ADAPTER_WEIGHTS).",
)
@click.option(
    "--max-queue", type=int, default=None,
    help="Bound the engine's pending queue (--continuous): submissions past "
         "it get 429 + Retry-After instead of queueing unboundedly. "
         "0 = unbounded. Default: 0 (PRIME_SERVE_MAX_QUEUE).",
)
@click.option(
    "--max-wave", type=click.IntRange(min=1), default=None,
    help="Widest batched admission wave (--continuous), a power of two. The "
         "warm-up builds a program a prompt-row capacity and wave width, so "
         "fewer widths start sooner; an expert model's prompt chunks grow as "
         "long as the narrower wave's score budget allows. Default: the engine's "
         "rule from shapes.",
)
@click.option(
    "--role", type=click.Choice(["prefill", "decode", "any"]), default=None,
    help="Phase role in a disaggregated fleet, advertised in /healthz: a "
         "`prime serve fleet` router with both explicit roles present "
         "prefills on a prefill replica and migrates the KV to a decode "
         "replica over /admin/kv. Pair with --mesh role:prefill / "
         "role:decode for the role-preset mesh layout. Default: any "
         "(PRIME_SERVE_ROLE).",
)
@click.option(
    "--replica-of", default=None, metavar="ROUTER_URL",
    help="Register this server with a running `prime serve fleet` router "
         "(POST ROUTER_URL/admin/join) once the model is loaded.",
)
@click.option(
    "--advertise-url", default=None, metavar="URL",
    help="URL the fleet router should reach this replica at (--replica-of). "
         "Required when binding 0.0.0.0: the bind address is not reachable "
         "from another host, so it cannot be advertised.",
)
@click.option(
    "--fleet-token", default=None, envvar="PRIME_FLEET_ADMIN_TOKEN",
    help="Bearer token for the router's admin surface (--replica-of against "
         "a router started with --admin-token).",
)
@click.pass_context
def serve_cmd(
    ctx: click.Context,
    model: str | None,
    checkpoint: str | None,
    tokenizer: str | None,
    slice_name: str | None,
    mesh_spec: str | None,
    tensor_parallel: int | None,
    sequence_parallel: int | None,
    kv_quant: bool,
    weight_quant: bool,
    weight_bits: str,
    adapter: str | None,
    adapters_spec: str | None,
    host: str,
    port: int,
    continuous: bool,
    slots: int,
    slot_capacity: int,
    chunk: int,
    speculative: bool | None,
    draft_len: int | None,
    overlap: bool | None,
    warmup: bool | None,
    profile: bool | None,
    prefix_cache_mb: float | None,
    prefix_cache_host_mb: float | None,
    adapter_max_inflight: int | None,
    adapter_weight_entries: tuple[str, ...],
    max_queue: int | None,
    max_wave: int | None,
    role: str | None,
    replica_of: str | None,
    advertise_url: str | None,
    fleet_token: str | None,
) -> None:
    """Serve MODEL over an OpenAI-compatible HTTP API (blocks until Ctrl-C)."""
    if ctx.invoked_subcommand is not None:
        return  # `prime serve metrics` — the subcommand runs instead
    if model is None:
        raise click.UsageError("Missing option '--model' / '-m'.")
    from prime_tpu.serve import serve_model

    if mesh_spec and slice_name:
        raise click.UsageError(
            "--mesh and --slice both describe the serving mesh; pass one"
        )
    if mesh_spec and not continuous:
        raise click.UsageError("--mesh requires --continuous (the sharded replica is engine-only)")
    if adapters_spec and not continuous:
        raise click.UsageError(
            "--adapters requires --continuous (batched multi-LoRA serving "
            "is engine-only; --adapter merges one adapter for the one-shot path)"
        )
    if adapters_spec and adapter:
        raise click.UsageError(
            "--adapter and --adapters are mutually exclusive (merged base "
            "weights would fail the bank's base-fingerprint check)"
        )
    if weight_bits == "4" and not weight_quant:
        # silently serving bf16 at 4x the expected HBM footprint would be a
        # nasty surprise; make the dependency explicit
        raise click.UsageError("--weight-bits 4 requires --weight-quant")
    if replica_of and advertise_url is None and host in ("0.0.0.0", "::"):
        # pure CLI-argument error: fail BEFORE minutes of checkpoint loading.
        # The bind-any address is meaningless to a remote router — it would
        # route traffic to itself (or nowhere).
        raise click.UsageError(
            "--replica-of with --host 0.0.0.0 requires --advertise-url "
            "(the URL the router can reach this replica at)"
        )

    # SIGTERM (a supervisor's stop) takes the same clean path as Ctrl-C:
    # finish serve_forever, stop the engine thread, exit 0
    signal.signal(signal.SIGTERM, _raise_keyboard_interrupt)
    try:
        server = serve_model(
            model,
            checkpoint=checkpoint,
            tokenizer=tokenizer,
            slice_name=slice_name,
            tensor_parallel=tensor_parallel,
            sequence_parallel=sequence_parallel,
            kv_quant=kv_quant,
            weight_quant=("int4" if weight_bits == "4" else True) if weight_quant else False,
            adapter=adapter,
            adapters=adapters_spec,
            host=host,
            port=port,
            continuous=continuous,
            mesh=mesh_spec,
            max_slots=slots,
            slot_capacity=slot_capacity,
            chunk=chunk,
            speculative=speculative,
            draft_len=draft_len,
            overlap=overlap,
            warmup=warmup,
            profile=profile,
            prefix_cache_mb=prefix_cache_mb,
            prefix_cache_host_mb=prefix_cache_host_mb,
            adapter_max_inflight=adapter_max_inflight,
            # joined back to the "name=K,..." env-spec shape; None defers
            # to PRIME_SERVE_ADAPTER_WEIGHTS inside the engine
            adapter_weights=",".join(adapter_weight_entries) or None,
            max_queue=max_queue,
            max_wave=max_wave,
            role=role,
        )
    except (ValueError, OSError) as e:
        raise click.ClickException(str(e)) from None
    if replica_of:
        import httpx

        try:
            response = httpx.post(
                f"{replica_of.rstrip('/')}/admin/join",
                json={"url": advertise_url or server.url},
                headers=(
                    {"Authorization": f"Bearer {fleet_token}"} if fleet_token else None
                ),
                timeout=5,
            )
            response.raise_for_status()
            click.echo(f"Joined fleet at {replica_of} as {response.json().get('joined')}")
        except (httpx.HTTPError, ValueError) as e:
            # serve anyway: the operator can join manually once the router
            # is up (POST /admin/join {"url": ...})
            click.echo(f"warning: could not join fleet at {replica_of}: {e}", err=True)
    click.echo(f"Serving {model} at {server.url}/v1 (Ctrl-C to stop)")
    click.echo(
        f"  e.g. PRIME_INFERENCE_URL={server.url}/v1 prime inference chat {model} -m 'hi'"
    )
    click.echo(f"  metrics: {server.url}/metrics  (prometheus: {server.url}/metrics?format=prometheus)")
    click.echo(f"  health:  {server.url}/healthz")
    _echo_device_memory("ready")
    try:
        server.serve_forever()
    except KeyboardInterrupt:
        _echo_device_memory("stopping")
        click.echo("\nStopped.")
        server.stop()


@serve_cmd.command(name="fleet")
@click.option(
    "--replica", "replicas", multiple=True, metavar="URL",
    help="Upstream replica base URL (repeatable). Replicas can also join "
         "later via `prime serve --replica-of` or POST /admin/join.",
)
@click.option("--host", default="127.0.0.1", show_default=True)
@click.option("--port", type=int, default=8080, show_default=True)
@click.option("--model", "model_id", default=None,
              help="Model id for /v1/models when no replica is reachable.")
@click.option(
    "--max-inflight", type=click.IntRange(min=1), default=64, show_default=True,
    help="Admission control: chat requests proxied concurrently before the "
         "router answers 429 + Retry-After.",
)
@click.option(
    "--queue-wait", "queue_wait_s", type=float, default=0.25, show_default=True,
    help="Seconds a request may wait for an in-flight permit before 429.",
)
@click.option(
    "--affinity-blocks", type=click.IntRange(min=1), default=2, show_default=True,
    help="Leading MIN_BUCKET-aligned prompt blocks hashed for prefix "
         "affinity (same block size as the engines' prefix-KV cache).",
)
@click.option(
    "--health-interval", "poll_interval", type=float, default=1.0, show_default=True,
    help="Seconds between /healthz polls of each replica.",
)
@click.option(
    "--fail-threshold", type=click.IntRange(min=1), default=3, show_default=True,
    help="Consecutive connect failures before a replica's breaker opens.",
)
@click.option(
    "--cooldown", type=float, default=5.0, show_default=True,
    help="Seconds an open breaker waits before a half-open probe.",
)
@click.option(
    "--admin-token", default=None, envvar="PRIME_FLEET_ADMIN_TOKEN",
    help="Require `Authorization: Bearer <token>` on the mutating admin "
         "surface (/admin/join, /admin/drain). Unset = open (loopback only!).",
)
@click.option(
    "--model-alias", "model_aliases", multiple=True, metavar="MODEL=ADAPTER",
    help="Router model registry (repeatable): map an OpenAI `model` name to "
         "an adapter id for multi-LoRA placement ('base' pins it to base "
         "routing). Names not aliased resolve against what replicas "
         "advertise in /healthz.",
)
@click.option(
    "--autoscale/--no-autoscale", "autoscale", default=None,
    help="Elastic fleet actuator (docs/architecture.md \"Elastic fleet\"): "
         "consume the observatory's scale signals each poll cycle and "
         "spawn/retire replicas via --launch, under the min/max bounds, "
         "per-direction cooldowns, and safety interlocks (drain-before-"
         "kill, inflight guard, breaker-storm pause). "
         "Default: off (PRIME_FLEET_AUTOSCALE).",
)
@click.option(
    "--min-replicas", type=click.IntRange(min=0), default=None,
    help="Autoscale floor: never retire below this many replicas. "
         "Default: 1 (PRIME_FLEET_AUTOSCALE_MIN).",
)
@click.option(
    "--max-replicas", type=click.IntRange(min=1), default=None,
    help="Autoscale ceiling: never spawn past this many replicas. "
         "Default: 4 (PRIME_FLEET_AUTOSCALE_MAX).",
)
@click.option(
    "--scale-cooldown", "scale_cooldown", type=click.FloatRange(min=0), default=None,
    help="Seconds between scale-UP actions (scale-downs wait "
         "PRIME_FLEET_AUTOSCALE_DOWN_COOLDOWN_S, 3x longer by default). "
         "Default: 10 (PRIME_FLEET_AUTOSCALE_COOLDOWN_S).",
)
@click.option(
    "--launch", "launch_cmd", default=None, metavar="CMD",
    help="Replica launch command template for --autoscale, with {host} "
         "{port} {router} placeholders — e.g. \"prime serve -m MODEL "
         "--continuous --port {port} --replica-of {router}\". The spawned "
         "process must answer /healthz on {host}:{port}.",
)
def serve_fleet_cmd(
    replicas: tuple[str, ...],
    host: str,
    port: int,
    model_id: str | None,
    max_inflight: int,
    queue_wait_s: float,
    affinity_blocks: int,
    poll_interval: float,
    fail_threshold: int,
    cooldown: float,
    admin_token: str | None,
    model_aliases: tuple[str, ...],
    autoscale: bool | None,
    min_replicas: int | None,
    max_replicas: int | None,
    scale_cooldown: float | None,
    launch_cmd: str | None,
) -> None:
    """Route an OpenAI-compatible endpoint across N engine replicas:
    prefix-affinity scheduling (shared-prefix traffic lands on the replica
    whose KV cache is warm), health-gated failover with circuit breaking,
    and fleet-level admission control. See docs/architecture.md
    "Serve fleet"."""
    from prime_tpu.core.config import env_flag
    from prime_tpu.serve.fleet import FleetRouter

    registry: dict[str, str | None] = {}
    for entry in model_aliases:
        name, eq, target = entry.partition("=")
        if not eq or not name or not target:
            raise click.UsageError(f"--model-alias {entry!r} must be MODEL=ADAPTER")
        registry[name] = None if target == "base" else target
    if autoscale is None:
        autoscale = env_flag("PRIME_FLEET_AUTOSCALE", False)
    if autoscale and not launch_cmd:
        # pure CLI-argument error: an actuator with no way to create
        # capacity can only ever refuse its own decisions
        raise click.UsageError(
            "--autoscale needs --launch (the replica launch command "
            "template the supervisor spawns scale-ups with)"
        )
    try:
        router = FleetRouter(
            replicas,
            host=host,
            port=port,
            model_id=model_id,
            max_inflight=max_inflight,
            queue_wait_s=queue_wait_s,
            affinity_blocks=affinity_blocks,
            poll_interval=poll_interval,
            fail_threshold=fail_threshold,
            cooldown=cooldown,
            admin_token=admin_token,
            model_registry=registry or None,
        )
    except OSError as e:
        raise click.ClickException(str(e)) from None
    if autoscale:
        from prime_tpu.serve.fleet import (
            AutoscalerConfig,
            FleetAutoscaler,
            LocalProcessLauncher,
            ReplicaSupervisor,
        )

        try:
            config = AutoscalerConfig.from_env(
                min_replicas=min_replicas,
                max_replicas=max_replicas,
                up_cooldown_s=scale_cooldown,
            )
        except ValueError as e:
            raise click.UsageError(str(e)) from None
        # replicas spawn on loopback: the launcher runs them on THIS host,
        # and a 0.0.0.0 router bind is not a reachable replica address
        launcher = LocalProcessLauncher(launch_cmd, router_url=router.url)
        router.attach_autoscaler(
            FleetAutoscaler(ReplicaSupervisor(launcher, membership=router.membership), config)
        )
    click.echo(f"Fleet router at {router.url}/v1 over {len(replicas)} replica(s)")
    if autoscale:
        click.echo(
            f"  autoscale: {router.autoscaler.config.min_replicas}"
            f"..{router.autoscaler.config.max_replicas} replicas "
            f"(status: GET {router.url}/admin/autoscaler, pause/resume: POST)"
        )
    click.echo(f"  join:    POST {router.url}/admin/join  {{\"url\": ...}}")
    click.echo(f"  drain:   POST {router.url}/admin/drain?replica=<id>")
    click.echo(f"  fleet:   {router.url}/admin/fleet")
    click.echo(f"  metrics: {router.url}/metrics  (prometheus: {router.url}/metrics?format=prometheus)")
    # SIGTERM (systemd/k8s stop) takes the same clean path as Ctrl-C: with
    # an autoscaler attached, router.stop() must run so the supervisor
    # reaps the replica subprocesses it launched — a bare SIGTERM death
    # would orphan them
    import signal

    def _sigterm(_signo, _frame):
        raise KeyboardInterrupt

    signal.signal(signal.SIGTERM, _sigterm)
    try:
        router.serve_forever()
    except KeyboardInterrupt:
        click.echo("\nStopped.")
        router.stop()


@serve_cmd.command(name="top")
@click.option(
    "--url", default="http://127.0.0.1:8080", show_default=True,
    help="Base URL of a `prime serve fleet` router (or a single `prime "
         "serve` instance — the single-replica view renders too).",
)
@click.option(
    "--interval", type=click.FloatRange(min=0.1), default=2.0, show_default=True,
    help="Seconds between refreshes (ignored with --once).",
)
@click.option(
    "--once", is_flag=True,
    help="Render one view and exit (with --output json: the raw view JSON, "
         "for scripts).",
)
@click.option(
    "--admin-token", default=None, envvar="PRIME_FLEET_ADMIN_TOKEN",
    help="Bearer token when the target gates /admin/observatory.",
)
@output_options
def serve_top_cmd(
    render: "Renderer",
    url: str,
    interval: float,
    once: bool,
    admin_token: str | None,
) -> None:
    """Live fleet SLO view: GET /admin/observatory rendered as a plain-text
    table — windowed rates/percentiles, burn alerts, the current scale
    signal, and the per-replica split — refreshed every --interval seconds.
    See docs/observability.md "Observatory"."""
    import time as _time

    import httpx

    base = url.rstrip("/")
    headers = {"Authorization": f"Bearer {admin_token}"} if admin_token else None
    first = True
    while True:
        try:
            response = httpx.get(
                f"{base}/admin/observatory", headers=headers, timeout=10
            )
            if response.status_code == 403:
                raise click.ClickException(
                    f"{base}/admin/observatory requires an admin token "
                    "(--admin-token / PRIME_FLEET_ADMIN_TOKEN)"
                )
            response.raise_for_status()
            view = response.json()
        except (httpx.HTTPError, ValueError) as e:
            if once or first:
                raise click.ClickException(
                    f"could not read {base}/admin/observatory: {e}"
                ) from None
            # a live dashboard survives a router restart or one slow
            # scrape: show the miss and retry at the next tick
            click.echo(f"(scrape failed: {e}; retrying in {interval}s)", err=True)
            _time.sleep(interval)
            continue
        if render.is_json:
            render.json(view)
            return  # one machine-readable view; scripts loop themselves
        if not once and not first:
            click.clear()
        first = False
        _render_observatory_view(render, view)
        if once:
            return
        _time.sleep(interval)


def _fmt(value, digits: int = 3) -> str:
    if value is None:
        return "-"
    if isinstance(value, float):
        return f"{value:.{digits}f}".rstrip("0").rstrip(".") or "0"
    return str(value)


def _render_observatory_view(render: "Renderer", view: dict) -> None:
    """Plain-text tables for one /admin/observatory payload — fleet-router
    shape (``replicas``/``fleet``) and single-replica shape
    (``replica``/``serving``) both render."""
    signal = view.get("signal") or {}
    click.echo(
        f"signal: {signal.get('direction', '?')} — {signal.get('reason', '')}"
    )
    autoscaler = view.get("autoscaler") or {}
    if autoscaler.get("enabled"):
        last = autoscaler.get("last_action") or {}
        last_desc = (
            f"{last.get('direction')}/{last.get('outcome')}"
            + (f" x{last.get('count')}" if last.get("count") else "")
            if last
            else "none yet"
        )
        config = autoscaler.get("config") or {}
        click.echo(
            f"autoscaler: {autoscaler.get('state', '?')} "
            f"[{config.get('min_replicas', '?')}..{config.get('max_replicas', '?')}] "
            f"— last action: {last_desc}"
        )
    breached = [
        v for v in view.get("slo", []) if isinstance(v, dict) and v.get("breached")
    ]
    for verdict in breached:
        fast, slow = verdict.get("fast", {}), verdict.get("slow", {})
        click.echo(
            f"  BURN {verdict.get('policy')}: "
            f"{_fmt(fast.get('burn'), 2)}x fast / {_fmt(slow.get('burn'), 2)}x slow "
            f"(objective {_fmt(verdict.get('objective'))})"
        )
    incidents = view.get("incidents") or {}
    if incidents.get("total"):
        click.echo(f"incidents: {incidents.get('total')} recorded")
        for row in (incidents.get("recent") or [])[:3]:
            click.echo(
                f"  INCIDENT {row.get('id')} {row.get('rule')} "
                f"[{row.get('severity')}] scope={row.get('scope')} "
                f"value={_fmt(row.get('value'))} "
                f"baseline={_fmt(row.get('baseline'))}"
            )
    windows = view.get("fleet") or view.get("serving") or {}
    window_rows = [
        [
            f"{label} {int(entry.get('window_s', 0))}s",
            _fmt(entry.get("span_s"), 1),
            _fmt(entry.get("tok_s")),
            _fmt(entry.get("admitted_per_s")),
            _fmt(entry.get("ttft_p95_s")),
            _fmt(entry.get("queue_wait_p95_s")),
            _fmt(entry.get("reject_rate"), 4),
        ]
        for label, entry in windows.items()
        if isinstance(entry, dict)
    ]
    render.table(
        ["window", "span_s", "tok/s", "adm/s", "ttft p95", "queue p95", "429 rate"],
        window_rows,
        title="Fleet windows" if "fleet" in view else "Serving windows",
    )
    replicas = view.get("replicas")
    if replicas is None and isinstance(view.get("replica"), dict):
        replicas = [view["replica"]]
    rows = [
        [
            r.get("id") or r.get("model", "?"),
            r.get("role", "-"),
            r.get("state", "?"),
            # autoscaler lifecycle for supervisor-managed replicas;
            # operator-joined rows show "-" (the actuator never touches them)
            r.get("managed") or "-",
            r.get("breaker", "-"),
            r.get("queue_depth", 0),
            f"{r.get('active_slots', 0)}/{r.get('max_slots', 0)}",
            _fmt(r.get("tok_s")),
            r.get("samples", 0),
            r.get("resets", 0),
        ]
        for r in replicas or []
    ]
    render.table(
        ["replica", "role", "state", "managed", "breaker", "queue", "slots",
         "tok/s", "samples", "resets"],
        rows,
        title="Replicas",
    )


@serve_cmd.command(name="profile")
@click.option(
    "--url", default="http://127.0.0.1:8000", show_default=True,
    help="Base URL of a running `prime serve --continuous` instance OR a "
         "`prime serve fleet` router (the capture fans out to every "
         "routable replica).",
)
@click.option(
    "--seconds", type=click.FloatRange(min=0.1), default=2.0, show_default=True,
    help="Capture window length: every dispatch in the window is fenced "
         "and lands in the trace (sampling is bypassed while capturing).",
)
@click.option(
    "--trace-out", default="trace.json", show_default=True,
    type=click.Path(dir_okay=False, writable=True),
    help="Where to write the merged Chrome-trace timeline (host spans + "
         "device step samples + XLA compiles). Load it in Perfetto or "
         "chrome://tracing. Router captures write one file per replica "
         "(trace-<replica>.json).",
)
@click.option(
    "--admin-token", default=None, envvar="PRIME_FLEET_ADMIN_TOKEN",
    help="Bearer token when the target gates /admin/profile.",
)
@output_options
def serve_profile_cmd(
    render: "Renderer",
    url: str,
    seconds: float,
    trace_out: str,
    admin_token: str | None,
) -> None:
    """Capture a device-time window from a live server: POST /admin/profile
    start, wait --seconds while traffic flows, stop, then render the
    per-phase breakdown (step seconds, compiles, cost-model MFU) and write
    the Perfetto-loadable trace.json. See docs/observability.md
    "Device time"."""
    import json as _json
    import os as _os
    import time as _time

    import httpx

    base = url.rstrip("/")
    headers = {"Authorization": f"Bearer {admin_token}"} if admin_token else None

    def _post(action: str) -> dict:
        try:
            response = httpx.post(
                f"{base}/admin/profile",
                json={"action": action},
                headers=headers,
                timeout=30,
            )
        except httpx.HTTPError as e:
            raise click.ClickException(
                f"could not reach {base}/admin/profile: {e}"
            ) from None
        if response.status_code == 403:
            raise click.ClickException(
                f"{base}/admin/profile requires an admin token "
                "(--admin-token / PRIME_FLEET_ADMIN_TOKEN)"
            )
        if response.status_code == 404:
            raise click.ClickException(
                f"{base} has no device profiler (serve with --continuous)"
            )
        if response.status_code >= 400:
            try:
                message = response.json().get("error", {}).get("message", "")
            except ValueError:
                message = ""
            raise click.ClickException(
                f"{base}/admin/profile: {message or f'status {response.status_code}'}"
            )
        try:
            return response.json()
        except ValueError as e:
            raise click.ClickException(
                f"{base}/admin/profile returned non-JSON: {e}"
            ) from None

    _post("start")
    click.echo(f"capturing {_fmt(seconds, 2)}s from {base} ...", err=True)
    _time.sleep(seconds)
    result = _post("stop")
    if render.is_json:
        render.json(result)
    # single-replica stop returns the capture itself; the router returns
    # {"replicas": {id: capture}} — normalize to one iterable shape
    replicas = result.get("replicas")
    captures = (
        replicas.items() if isinstance(replicas, dict) else [("", result)]
    )
    stem, ext = _os.path.splitext(trace_out)
    wrote_any = False
    for rid, capture in captures:
        if not isinstance(capture, dict) or "summary" not in capture:
            message = "no capture"
            if isinstance(capture, dict):
                message = (capture.get("error") or {}).get("message", message)
            click.echo(f"warning: {rid or base}: {message}", err=True)
            continue
        summary = capture.get("summary") or {}
        if not render.is_json:
            _render_profile_summary(render, capture, summary, rid or base)
        trace = capture.get("trace")
        if trace is not None:
            path = f"{stem}-{rid}{ext or '.json'}" if rid else trace_out
            with open(path, "w", encoding="utf-8") as f:
                _json.dump(trace, f)
            wrote_any = True
            if not render.is_json:
                click.echo(
                    f"  trace: {path} (load in Perfetto / chrome://tracing)"
                )
    if not wrote_any and not render.is_json:
        raise click.ClickException(
            "no replica returned a capture (was any traffic flowing, and "
            "was a capture already stopped?)"
        )


@serve_cmd.command(name="incidents")
@click.option(
    "--url", default="http://127.0.0.1:8080", show_default=True,
    help="Base URL of a `prime serve fleet` router (merged fleet view) or "
         "a single `prime serve` replica.",
)
@click.option(
    "--id", "incident_id", default=None,
    help="Fetch one incident bundle (full forensics: flight timelines, "
         "registry deltas, journal tail) instead of the summary list.",
)
@click.option(
    "--admin-token", default=None, envvar="PRIME_FLEET_ADMIN_TOKEN",
    help="Bearer token when the target gates /admin/incidents.",
)
@output_options
def serve_incidents_cmd(
    render: "Renderer",
    url: str,
    incident_id: str | None,
    admin_token: str | None,
) -> None:
    """Sentinel incidents: GET /admin/incidents[/{id}] rendered as a table
    (or the full bundle JSON with --id / --output json). See
    docs/observability.md "Sentinel & incidents"."""
    import httpx

    base = url.rstrip("/")
    headers = {"Authorization": f"Bearer {admin_token}"} if admin_token else None
    path = f"/admin/incidents/{incident_id}" if incident_id else "/admin/incidents"
    try:
        response = httpx.get(f"{base}{path}", headers=headers, timeout=10)
    except httpx.HTTPError as e:
        raise click.ClickException(f"could not reach {base}{path}: {e}") from None
    if response.status_code == 403:
        raise click.ClickException(
            f"{base}{path} requires an admin token "
            "(--admin-token / PRIME_FLEET_ADMIN_TOKEN)"
        )
    if response.status_code == 404:
        raise click.ClickException(f"no incident {incident_id!r} at {base}")
    response.raise_for_status()
    try:
        payload = response.json()
    except ValueError as e:
        raise click.ClickException(f"{base}{path} returned non-JSON: {e}") from None
    if render.is_json or incident_id:
        render.json(payload)
        return
    # fleet shape ({"router": [...], "replicas": {id: {...}}}) and
    # single-replica shape ({"incidents": [...]}) both flatten to one table
    rows = []
    for scope, summaries in [("router", payload.get("router"))] + [
        (rid, (entry or {}).get("incidents"))
        for rid, entry in (payload.get("replicas") or {}).items()
    ] + [("", payload.get("incidents"))]:
        for row in summaries or []:
            rows.append(
                [
                    row.get("id", "?"),
                    scope or row.get("scope", "?"),
                    row.get("rule", "?"),
                    row.get("severity", "?"),
                    _fmt(row.get("value")),
                    _fmt(row.get("baseline")),
                    _fmt(row.get("ratio"), 2),
                    row.get("flights", 0),
                ]
            )
    render.table(
        ["id", "scope", "rule", "severity", "value", "baseline", "ratio",
         "flights"],
        rows,
        title="Incidents",
    )
    if not rows:
        click.echo("no incidents recorded")


def _render_profile_summary(
    render: "Renderer", capture: dict, summary: dict, target: str
) -> None:
    """The per-phase breakdown table for one /admin/profile stop payload."""
    rows = [
        [
            phase,
            entry.get("samples", 0),
            _fmt(
                entry.get("mean_s") * 1e3
                if entry.get("mean_s") is not None
                else None,
            ),
            _fmt(entry.get("total_s"), 4),
            _fmt(entry.get("achieved_tflops"), 2),
            _fmt(entry.get("mfu"), 4),
            _fmt(entry.get("achieved_gbps"), 2),
        ]
        for phase, entry in sorted((summary.get("phases") or {}).items())
    ]
    render.table(
        ["phase", "samples", "mean_ms", "total_s", "TFLOP/s", "MFU", "GB/s"],
        rows,
        title=f"Device time @ {target}",
    )
    compiles = summary.get("compiles") or {}
    peak = summary.get("peak_tflops")
    roofline = (
        f"peak {_fmt(peak, 1)} bf16 TFLOP/s"
        if peak is not None
        # MFU needs a peak-FLOPs roofline; the table only knows TPU
        # generations (docs/observability.md "Device time")
        else "no roofline for this backend (MFU/TFLOP columns empty)"
    )
    click.echo(
        f"  window {_fmt(capture.get('duration_s'), 2)}s: "
        f"{capture.get('samples', 0)} device samples, "
        f"{capture.get('host_spans', 0)} host spans, "
        f"{compiles.get('total', 0)} compiles "
        f"({_fmt(compiles.get('seconds'), 3)}s) — {roofline}"
    )


@serve_cmd.command(name="metrics")
@click.option(
    "--url", default="http://127.0.0.1:8000", show_default=True,
    help="Base URL of a running `prime serve` instance OR a "
         "`prime serve fleet` router (router-specific series render too).",
)
@click.option(
    "--prometheus", is_flag=True,
    help="Dump the raw Prometheus text exposition instead of a table.",
)
@click.option(
    "--debug-url", default=None, metavar="URL",
    help="Print the flight-recorder view (GET /debug/requests) of a server "
         "or router instead of scraping metrics. See docs/observability.md.",
)
@click.option(
    "--request", "request_id", default=None, metavar="ID",
    help="With --debug-url: print one request's full timeline "
         "(engine request id or W3C trace id).",
)
@click.option(
    "--admin-token", default=None, envvar="PRIME_FLEET_ADMIN_TOKEN",
    help="Bearer token for /debug/requests when the target gates it.",
)
@click.option(
    "--watch", "watch_s", type=click.FloatRange(min=0.01), default=None,
    metavar="SECONDS",
    help="Repeat the scrape every SECONDS, adding a windowed per-second "
         "rate column for every counter (computed through the observatory "
         "timeseries ring, not ad-hoc subtraction — "
         "docs/observability.md \"Observatory\").",
)
@click.option(
    "--count", type=click.IntRange(min=0), default=0,
    help="With --watch: refreshes before exiting (0 = until Ctrl-C).",
)
@output_options
def serve_metrics_cmd(
    render: "Renderer",
    url: str,
    prometheus: bool,
    debug_url: str | None,
    request_id: str | None,
    admin_token: str | None,
    watch_s: float | None,
    count: int,
) -> None:
    """Scrape a running server's metrics registry: counters, gauges, and
    latency histograms (TTFT, queue wait, prefill/decode) with estimated
    p50/p95 — or, with --debug-url, the flight-recorder request timelines.
    See docs/architecture.md "Observability" and docs/observability.md."""
    import time as _time

    import httpx

    if prometheus and render.is_json:
        # the exposition IS a text format; silently emitting it where a
        # script asked for JSON would break a downstream `| jq`
        raise click.UsageError(
            "--prometheus emits text exposition format; drop it or use "
            "--output json without it for the registry JSON"
        )
    if request_id and not debug_url:
        raise click.UsageError("--request requires --debug-url")
    if watch_s is not None and (prometheus or debug_url or render.is_json):
        raise click.UsageError(
            "--watch renders live tables; it does not compose with "
            "--prometheus, --debug-url, or --output json (scripts should "
            "poll /metrics?format=registry, or `prime serve top --once`)"
        )
    if debug_url:
        _render_flight_view(render, debug_url, request_id, admin_token)
        return
    base = url.rstrip("/")
    if prometheus:
        try:
            response = httpx.get(
                f"{base}/metrics", params={"format": "prometheus"}, timeout=10
            )
            response.raise_for_status()
        except httpx.HTTPError as e:
            raise click.ClickException(f"could not scrape {base}/metrics: {e}") from None
        click.echo(response.text, nl=False)
        return
    if watch_s is not None:
        from prime_tpu.obs.timeseries import SnapshotRing

        # one client-side ring per scraped section: each refresh appends the
        # scrape and reads the windowed rate back out — the SAME query the
        # observatory serves, so the delta column can never drift from it
        rings: dict[str, SnapshotRing] = {}
        iteration = 0
        while True:
            payload = _scrape_registry(base)
            for section, snapshot in payload.items():
                rings.setdefault(section, SnapshotRing()).append(snapshot)
            if iteration:
                click.clear()
            _render_registry_tables(
                render, payload, rings=rings, rate_window_s=watch_s * 3
            )
            iteration += 1
            if count and iteration >= count:
                return
            _time.sleep(watch_s)
    payload = _scrape_registry(base)
    if render.is_json:
        render.json(payload)
        return
    _render_registry_tables(render, payload)


def _scrape_registry(base: str) -> dict:
    """GET ``/metrics?format=registry`` and validate the snapshot shape."""
    import httpx

    try:
        response = httpx.get(
            f"{base}/metrics", params={"format": "registry"}, timeout=10
        )
        response.raise_for_status()
        payload = response.json()
    except (httpx.HTTPError, ValueError) as e:
        raise click.ClickException(f"could not scrape {base}/metrics: {e}") from None
    if not isinstance(payload, dict) or not all(
        isinstance(registry, dict)
        and all(isinstance(family, dict) and "series" in family for family in registry.values())
        for registry in payload.values()
    ):
        # e.g. a pre-telemetry server that answered the bare /metrics JSON
        raise click.ClickException(
            f"{base}/metrics?format=registry did not return registry snapshots "
            "(is the server running this repo's serve build?)"
        )
    return payload


def _render_registry_tables(
    render: "Renderer",
    payload: dict,
    rings=None,
    rate_window_s: float | None = None,
) -> None:
    """The registry scrape rendered as tables. With ``rings`` (watch mode),
    counters gain a windowed per-second rate column read from the
    per-section timeseries ring."""
    from prime_tpu.obs.metrics import quantile_from_snapshot

    value_rows: list[list] = []
    hist_rows: list[list] = []
    for section, registry in payload.items():
        ring = rings.get(section) if rings else None
        for name, family in registry.items():
            for series in family["series"]:
                labels = ",".join(f"{k}={v}" for k, v in series["labels"].items())
                if family["type"] == "histogram":
                    count = series["count"]
                    mean = series["sum"] / count if count else 0.0
                    p50 = quantile_from_snapshot(series["buckets"], series["counts"], 0.5)
                    p95 = quantile_from_snapshot(series["buckets"], series["counts"], 0.95)
                    hist_rows.append(
                        [section, name, labels, count,
                         round(mean, 6), round(p50, 6), round(p95, 6)]
                    )
                else:
                    row = [section, name, labels, family["type"], series["value"]]
                    if rings is not None:
                        rate = None
                        if family["type"] == "counter" and ring is not None:
                            rate = ring.rate(
                                name, rate_window_s or 1.0, series["labels"]
                            )
                        row.append(round(rate, 3) if rate is not None else "-")
                    value_rows.append(row)
    headers = ["section", "metric", "labels", "type", "value"]
    if rings is not None:
        headers.append("per_s")
    render.table(headers, value_rows, title="Counters & gauges")
    render.table(
        ["section", "metric", "labels", "count", "mean", "p50", "p95"], hist_rows,
        title="Histograms (seconds unless named otherwise)",
    )
    if "router" in payload:
        # fleet-router scrape: condense the router-specific families
        # (fleet_requests_total by replica/outcome, breaker-state gauges,
        # the affinity ratio) into one per-replica table — the series render
        # in the generic tables above too, but the routing question is
        # always "who got the traffic and who is broken"
        router = payload["router"]

        def series_of(name: str) -> list[dict]:
            return router.get(name, {}).get("series", [])

        per_replica: dict[str, dict[str, int]] = {}
        for series in series_of("fleet_requests_total"):
            labels = series["labels"]
            per_replica.setdefault(labels.get("replica", "?"), {})[
                labels.get("outcome", "?")
            ] = int(series["value"])
        breakers = {
            series["labels"].get("replica", "?"): {0: "closed", 1: "half-open", 2: "open"}.get(
                int(series["value"]), str(series["value"])
            )
            for series in series_of("fleet_breaker_state")
        }
        fleet_rows = [
            [
                rid,
                breakers.get(rid, "?"),
                sum(outcomes.values()),
                ", ".join(f"{k}={v}" for k, v in sorted(outcomes.items())) or "-",
            ]
            for rid, outcomes in sorted(per_replica.items())
        ]
        ratio = next(
            (s["value"] for s in series_of("fleet_affinity_hit_ratio")), None
        )
        render.table(
            ["replica", "breaker", "requests", "outcomes"], fleet_rows,
            title="Fleet routing"
            + (f" (affinity hit ratio {ratio})" if ratio is not None else ""),
        )


def _render_flight_view(
    render: "Renderer", debug_url: str, request_id: str | None, admin_token: str | None
) -> None:
    """`prime serve metrics --debug-url`: the flight-recorder view of a
    server or router — recent + in-flight request summaries, or one full
    timeline with --request."""
    import httpx

    base = debug_url.rstrip("/")
    path = f"/debug/requests/{request_id}" if request_id else "/debug/requests"
    headers = {"Authorization": f"Bearer {admin_token}"} if admin_token else None
    try:
        response = httpx.get(f"{base}{path}", headers=headers, timeout=10)
        if response.status_code == 403:
            raise click.ClickException(
                f"{base}{path} requires an admin token (--admin-token / "
                "PRIME_FLEET_ADMIN_TOKEN)"
            )
        if response.status_code == 404:
            raise click.ClickException(f"no request {request_id!r} at {base}")
        response.raise_for_status()
        payload = response.json()
    except (httpx.HTTPError, ValueError) as e:
        raise click.ClickException(f"could not read {base}{path}: {e}") from None
    if render.is_json:
        render.json(payload)
        return
    if request_id:
        # one timeline (server shape) or {"router": ..., "replica": ...}
        sections = (
            payload.items() if "router" in payload else [("request", payload)]
        )
        for section, timeline in sections:
            if not isinstance(timeline, dict):
                continue
            header = ", ".join(
                f"{k}={v}" for k, v in timeline.items() if k != "events"
            )
            click.echo(f"--- {section}: {header}")
            for event in timeline.get("events", []):
                detail = ", ".join(
                    f"{k}={v}" for k, v in event.items() if k not in ("t_s", "event")
                )
                click.echo(
                    f"{event['t_s'] * 1e3:>10.2f} ms  {event['event']}"
                    + (f" ({detail})" if detail else "")
                )
        return
    summaries = payload.get("router", payload)
    rows = [
        [
            entry.get("id", "?")[:16],
            entry.get("state", "?"),
            entry.get("outcome") or "-",
            round(entry.get("duration_s", 0.0), 3),
            entry.get("events", 0),
            entry.get("last_event") or "-",
            entry.get("replica") or "-",
        ]
        for bucket in ("inflight", "recent")
        for entry in summaries.get(bucket, [])
    ]
    render.table(
        ["request", "state", "outcome", "duration_s", "events", "last_event", "replica"],
        rows,
        title=f"Flight recorder @ {base} (in-flight first, then recent)",
    )
