"""Ring attention: sequences sharded across chips over ICI (long-context demo).

Runs on any device set: a v5e slice, or locally on a virtual CPU mesh:

    JAX_PLATFORMS=cpu \
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/long_context_ring_attention.py
"""

import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

import jax
import jax.numpy as jnp

from prime_tpu.ops.attention import xla_attention_causal
from prime_tpu.parallel.mesh import make_mesh
from prime_tpu.parallel.ring_attention import ring_self_attention


def main() -> None:
    n = jax.device_count()
    mesh = make_mesh({"sp": n})
    batch, heads, kv_heads, head_dim = 1, 8, 4, 64
    seq = 512 * n  # each device holds a 512-token shard; total grows with the ring
    print(f"ring attention over sp={n}: total sequence {seq}")

    q = jax.random.normal(jax.random.PRNGKey(0), (batch, heads, seq, head_dim), dtype=jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(1), (batch, kv_heads, seq, head_dim), dtype=jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(2), (batch, kv_heads, seq, head_dim), dtype=jnp.float32)

    out = ring_self_attention(q, k, v, mesh)
    ref = xla_attention_causal(q, k, v, head_dim**-0.5)
    err = float(jnp.max(jnp.abs(out - ref)))
    print(f"max |ring - dense| = {err:.2e}  ({'OK' if err < 2e-3 else 'MISMATCH'})")


if __name__ == "__main__":
    main()
