"""Pipeline-parallel training over a pp mesh axis.

Runs anywhere: on a CPU host use
    JAX_PLATFORMS=cpu \
    XLA_FLAGS=--xla_force_host_platform_device_count=8 python examples/pipeline_parallel_training.py
On a real multi-chip slice the same code pipelines stages over ICI.
"""

import jax
import jax.numpy as jnp

from prime_tpu.models import get_config
from prime_tpu.models.llama import init_params
from prime_tpu.parallel.mesh import make_mesh
from prime_tpu.parallel.pipeline import make_pipeline_train_step, shard_pipeline_params
from prime_tpu.train import default_optimizer, init_train_state

STAGES = 4
MICROBATCHES = 4


def main() -> None:
    config = get_config("debug-128m").scaled(n_layers=STAGES * 3)  # 3 layers/stage
    mesh = make_mesh({"pp": STAGES}, devices=jax.devices()[:STAGES])
    print(f"pipeline: {STAGES} stages x {config.n_layers // STAGES} layers, "
          f"{MICROBATCHES} microbatches, bubble {(STAGES-1)/(MICROBATCHES+STAGES-1):.0%}")

    optimizer = default_optimizer(learning_rate=1e-3)
    params = shard_pipeline_params(
        init_params(jax.random.PRNGKey(0), config, jnp.float32), mesh, config
    )
    state = init_train_state(params, optimizer)
    step = make_pipeline_train_step(config, optimizer, mesh, n_microbatches=MICROBATCHES)

    tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 64), 0, config.vocab_size)
    targets = jnp.roll(tokens, -1, axis=1)
    mask = jnp.ones_like(tokens, jnp.float32)
    for i in range(5):
        state, metrics = step(state, tokens, targets, mask)
        print(f"  step {i}: loss={float(metrics['loss']):.4f}")


if __name__ == "__main__":
    main()
