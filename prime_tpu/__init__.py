"""prime-tpu: a TPU-native compute-platform CLI + SDK suite.

Capability surface modeled on PrimeIntellect's `prime` monorepo (see SURVEY.md),
re-designed TPU-first: TPU slices (v5e/v5p, ICI topologies) are first-class
compute, sandboxes are JAX/XLA-preloaded, and the evals runner drives inference
through a native JAX backend (`prime_tpu.models` / `prime_tpu.parallel`).

Layout (strictly downward dependencies, reference: SURVEY.md §1):
  core/       config + HTTP transport (L0/L1)
  api/        resource API clients (L2)
  sandboxes/  remote code-execution SDK (control plane + gateway data plane)
  evals/      Evals Hub SDK + native JAX eval runner
  tunnel/     managed reverse-tunnel SDK
  envhub/     environment packaging + hub client
  commands/   click CLI (L3)
  models/     JAX model zoo (Llama family) — the inference/eval compute path
  ops/        TPU kernels: attention, RMSNorm, RoPE (pallas + XLA reference)
  parallel/   mesh/sharding, ring attention, distributed init
  testing/    in-process fake control plane for hermetic tests
"""

import os as _os
import sys as _sys

__version__ = "0.1.0"

# JAX's persistent compilation cache, placed in ONE spot for every compute
# entry point (prime serve/train/eval, the benchmark, chip_smoke.py's children all
# import this package) without importing JAX for the others. Where
# JAX_COMPILATION_CACHE_DIR is set, nothing is done and no other directory is
# set in code. Where it is not, the cache lives at <checkout>/.jax_cache: a
# fixed path, because the path is part of the cache key — never a temporary
# name, a pid or a time.
if "JAX_COMPILATION_CACHE_DIR" not in _os.environ:
    _os.environ["JAX_COMPILATION_CACHE_DIR"] = _os.path.join(
        _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))), ".jax_cache"
    )
    if "jax" in _sys.modules:
        # jax read the variable when it was imported; tell it now
        _sys.modules["jax"].config.update(
            "jax_compilation_cache_dir", _os.environ["JAX_COMPILATION_CACHE_DIR"]
        )
