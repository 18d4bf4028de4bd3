"""Multi-host connectivity smoke: prove jax.distributed actually works.

Run the SAME command on every worker of a slice (the SPMD contract —
reference launches its NCCL ranks the same way, e.g. its torchrun-shaped
entrypoints; here the fan-out is ``prime pods connect --all-workers``):

    python -m prime_tpu.parallel.multihost_smoke \
        --coordinator <worker0>:8476 --num-processes N --process-id $I

Each process initializes the distributed runtime via
``initialize_multihost`` (prime_tpu/parallel/distributed.py), then proves
the pooled device set is real with three checks that each REQUIRE
cross-process communication:

1. ``psum`` of ones over a global mesh — result must equal the GLOBAL
   device count, which no process can produce locally.
2. ``all_gather`` of process-stamped shards — every process must observe
   every other process's stamp.
3. A dp/tp-sharded matmul whose replicated scalar output must match a
   single-host numpy reference — the XLA partitioner inserts the
   cross-host collectives implicitly from shardings, the same path the
   real training step uses.

Each process prints one ``MULTIHOST_SMOKE_OK {json}`` line on success and
exits nonzero on any failure. In CI this runs as two CPU processes
(tests/test_multihost.py) — multi-host semantics without multi-host
hardware; on a real v5e-16+ slice the identical command validates DCN.
"""

from __future__ import annotations

import argparse
import json
import os
import sys


def run_smoke(
    coordinator_address: str | None = None,
    num_processes: int | None = None,
    process_id: int | None = None,
) -> dict:
    """Initialize the distributed runtime and run the three cross-process
    checks. Returns the result record (also asserted internally)."""
    from prime_tpu.parallel.distributed import initialize_multihost

    initialize_multihost(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from prime_tpu.parallel.mesh import make_mesh

    n_global = jax.device_count()
    n_local = jax.local_device_count()
    n_proc = jax.process_count()
    assert n_proc == (num_processes or n_proc), (
        f"process_count {n_proc} != requested {num_processes}"
    )
    assert n_global == n_local * n_proc, (n_global, n_local, n_proc)

    mesh = make_mesh({"dp": n_global})

    # 1. psum over every device: only correct if the collective spans hosts
    ones = jax.device_put(
        jnp.ones((n_global,)), NamedSharding(mesh, P("dp"))
    )
    total = jax.jit(
        jax.shard_map(
            lambda x: jax.lax.psum(jnp.sum(x), "dp"),
            mesh=mesh, in_specs=P("dp"), out_specs=P(),
        )
    )(ones)
    assert float(total) == float(n_global), (float(total), n_global)

    # 2. all_gather of process-stamped shards: device i carries value
    # 1000*process_of(device i) + i; the gathered vector must contain every
    # process's stamp on every process
    stamps = np.asarray(
        [1000 * d.process_index + i for i, d in enumerate(mesh.devices.ravel())],
        dtype=np.float32,
    )
    stamped = jax.device_put(jnp.asarray(stamps), NamedSharding(mesh, P("dp")))
    gathered = jax.jit(
        jax.shard_map(
            lambda x: jax.lax.all_gather(x, "dp", tiled=True),
            mesh=mesh, in_specs=P("dp"), out_specs=P(),
            # the gathered result IS replicated, but the varying-axes checker
            # can't statically infer that for all_gather output
            check_vma=False,
        )
    )(stamped)
    seen_procs = sorted({int(v) // 1000 for v in np.asarray(gathered)})
    assert seen_procs == list(range(n_proc)), (seen_procs, n_proc)

    # 3. sharded matmul: dp-sharded activations x tp-sharded weights with a
    # replicated scalar out — the partitioner must insert the cross-host
    # collectives itself, exactly as in the real train/serve steps
    tp = n_local
    mesh2 = make_mesh({"dp": n_global // tp, "tp": tp})
    key = jax.random.PRNGKey(0)
    x_host = jax.random.normal(key, (8 * (n_global // tp), 64))
    w_host = jax.random.normal(jax.random.PRNGKey(1), (64, 16 * tp))
    x = jax.device_put(x_host, NamedSharding(mesh2, P("dp", None)))
    w = jax.device_put(w_host, NamedSharding(mesh2, P(None, "tp")))
    out = jax.jit(
        lambda a, b: jnp.sum(a @ b),
        out_shardings=NamedSharding(mesh2, P()),
    )(x, w)
    ref = float(np.sum(np.asarray(x_host) @ np.asarray(w_host)))
    got = float(out)
    assert abs(got - ref) <= 1e-2 + 1e-4 * abs(ref), (got, ref)

    return {
        "process_id": jax.process_index(),
        "process_count": n_proc,
        "global_devices": n_global,
        "local_devices": n_local,
        "psum": float(total),
        "procs_seen_in_gather": seen_procs,
        "sharded_matmul_ok": True,
    }


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--coordinator", default=None,
                        help="host:port of process 0 (omit on Cloud TPU VMs)")
    parser.add_argument("--num-processes", type=int, default=None)
    parser.add_argument("--process-id", type=int, default=None)
    parser.add_argument(
        "--devices-per-process", type=int, default=None,
        help="virtual CPU devices per process (CI only; must be set before "
        "jax import, so main() sets XLA_FLAGS/JAX_PLATFORMS itself)",
    )
    args = parser.parse_args(argv)
    if args.devices_per_process:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.devices_per_process}"
        )
    record = run_smoke(
        coordinator_address=args.coordinator,
        num_processes=args.num_processes,
        process_id=args.process_id,
    )
    print("MULTIHOST_SMOKE_OK " + json.dumps(record), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
