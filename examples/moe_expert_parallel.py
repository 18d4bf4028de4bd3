"""Mixtral-style MoE with expert parallelism over an ep mesh axis.

Runs anywhere: on a CPU host use
    JAX_PLATFORMS=cpu \
    XLA_FLAGS=--xla_force_host_platform_device_count=8 python examples/moe_expert_parallel.py
"""

import jax
import jax.numpy as jnp

from prime_tpu.models import get_config
from prime_tpu.models.llama import init_params
from prime_tpu.parallel.mesh import make_mesh
from prime_tpu.parallel.sharding import shard_batch
from prime_tpu.train import (
    default_optimizer,
    init_train_state,
    make_train_step,
    shard_train_state,
)


def main() -> None:
    config = get_config("tiny-moe")
    mesh = make_mesh({"dp": 1, "fsdp": 2, "ep": 2, "tp": 2})
    print(f"MoE: {config.n_experts} experts (top-{config.experts_per_token}), mesh {dict(mesh.shape)}")

    optimizer = default_optimizer(learning_rate=1e-3)
    params = init_params(jax.random.PRNGKey(0), config, jnp.float32)
    state = shard_train_state(init_train_state(params, optimizer), mesh, config)
    step = make_train_step(config, optimizer)  # includes the Switch aux loss

    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 64), 0, config.vocab_size)
    batch = tuple(
        shard_batch(x, mesh)
        for x in (tokens, jnp.roll(tokens, -1, 1), jnp.ones_like(tokens, jnp.float32))
    )
    for i in range(5):
        state, metrics = step(state, *batch)
        print(f"  step {i}: loss={float(metrics['loss']):.4f}")
    spec = state.params["layers"]["w_gate"].sharding.spec
    print(f"expert weights sharded as {spec}")


if __name__ == "__main__":
    main()
