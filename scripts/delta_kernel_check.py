"""The gated delta rule's state kernel on the chip against the plain step
(models/delta.py ``_step``), at the tiles the served configurations have:
``chiprun -- python3 scripts/delta_kernel_check.py``. Prints one JSON line a
tile (the worst gap of the stepped row's state and output, whether the rows
that belong to nobody and the other stack rows kept their bits, and the time of
a call) and exits 1 if a tile is off. Without a TPU it exits 2 and checks
nothing, so that a call that lands on another host cannot pass; ``--interpret``
asks for the interpreted kernel at a toy tile (``JAX_PLATFORMS=cpu``): a check of
the code path, with no time in its line."""

import json
import sys
import time

import numpy as np

import jax
import jax.numpy as jnp

sys.path.insert(0, __file__.rsplit("/", 2)[0])

from prime_tpu.models import delta  # noqa: E402
from prime_tpu.ops.pallas_delta import delta_decode_step  # noqa: E402

TILES = {"qwen3-next-ep2": (3, 128, 32, 128, 128), "olmo-hybrid-7b-pp2": (12, 16, 30, 96, 192)}


def check(name, layers, slots, heads, d_k, d_v, interpret):
    rng = np.random.default_rng(0)
    unit = lambda x: x / np.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    leaf = jnp.asarray(rng.normal(size=(layers, slots, heads, d_k, d_v)) * 0.3, jnp.float32)
    q = jnp.asarray(unit(rng.normal(size=(slots, heads, d_k))) * d_k**-0.5, jnp.float32)
    k = jnp.asarray(unit(rng.normal(size=(slots, heads, d_k))), jnp.float32)
    v = jnp.asarray(rng.normal(size=(slots, heads, d_v)), jnp.float32)
    beta = jnp.asarray(rng.uniform(0.1, 1.9, size=(slots, heads)), jnp.float32)
    g = jnp.asarray(-rng.uniform(1e-3, 0.2, size=(slots, heads)), jnp.float32)
    live = jnp.asarray(rng.uniform(size=(slots,)) < 0.75)
    row = layers - 1
    with jax.default_matmul_precision("highest"):
        want_state, want_o = jax.jit(delta._step)(leaf[row], q, k, v, beta, g)
    step = jax.jit(lambda leaf: delta_decode_step(leaf, row, q, k, v, beta, g, live, interpret=interpret))
    got_leaf, got_o = jax.block_until_ready(step(leaf))
    timed = {}
    if not interpret:
        t = time.monotonic()
        for _ in range(20):
            out = step(leaf)
        jax.block_until_ready(out)
        timed = {"call_s_with_copy_in": (time.monotonic() - t) / 20}
    alive = np.asarray(live)
    gap_state = float(np.abs(np.asarray(got_leaf[row] - want_state))[alive].max())
    gap_o = float(np.abs(np.asarray(got_o - want_o))[alive].max())
    kept = bool(np.array_equal(np.asarray(got_leaf[row])[~alive], np.asarray(leaf[row])[~alive])
                and np.array_equal(np.asarray(got_leaf[:row]), np.asarray(leaf[:row])))
    ok = gap_state < 1e-5 and gap_o < 1e-5 and kept
    print(json.dumps({"tile": name, "shape": [layers, slots, heads, d_k, d_v], "live_rows": int(alive.sum()),
                      "state_gap": gap_state, "output_gap": gap_o, "others_kept": kept, "ok": ok,
                      "interpreted": interpret, "platform": jax.devices()[0].platform, **timed}), flush=True)
    return ok


if __name__ == "__main__":
    interpret = "--interpret" in sys.argv[1:]
    if not interpret and jax.devices()[0].platform != "tpu":
        print(f"no TPU here (platform {jax.devices()[0].platform!r}): nothing checked; "
              "--interpret runs the interpreted kernel at a toy tile", file=sys.stderr)
        sys.exit(2)
    tiles = {n: (2, 3, *t[2:]) for n, t in TILES.items()} if interpret else TILES
    sys.exit(0 if all([check(name, *tile, interpret) for name, tile in tiles.items()]) else 1)
