"""Context-parallel training: the sequence axis sharded over sp with ring
attention inside the model forward (SURVEY §5 long-context first-class;
the training-side complement of long_context.py's sp decode).

The invariants: cp logits == plain logits, cp train-step loss AND gradients
== the plain step's, window/softcap/sink configs ride the ring, and invalid
modes (cache, per-layer schedules, missing sp axis) reject loudly.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding

from prime_tpu.models import get_config
from prime_tpu.models.llama import forward, init_cache, init_params
from prime_tpu.parallel.mesh import make_mesh
from prime_tpu.parallel.sharding import cp_batch_spec
from prime_tpu.train import (
    default_optimizer,
    init_train_state,
    make_train_step,
)


CFG = get_config("tiny-test")


def _cp_put(x, mesh):
    from prime_tpu.parallel.sharding import prune_spec

    return jax.device_put(x, NamedSharding(mesh, prune_spec(cp_batch_spec(), mesh)))


def test_cp_forward_matches_plain():
    mesh = make_mesh({"dp": 1, "fsdp": 1, "sp": 8})
    params = init_params(jax.random.PRNGKey(0), CFG, dtype=jnp.float32)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 128), 0, CFG.vocab_size)
    ref, _ = forward(params, tokens, CFG, attn_impl="xla")
    out, _ = jax.jit(
        lambda p, t: forward(p, t, CFG, attn_impl="ring", mesh=mesh)
    )(params, _cp_put(tokens, mesh))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-3, atol=2e-3)


def test_cp_forward_uniform_window_and_sinks():
    """Mistral-style uniform window and GPT-OSS sinks both ride the ring."""
    mesh = make_mesh({"dp": 1, "fsdp": 1, "sp": 8})
    windowed = CFG.scaled(sliding_window=24, sliding_pattern="uniform")
    params = init_params(jax.random.PRNGKey(2), windowed, dtype=jnp.float32)
    tokens = jax.random.randint(jax.random.PRNGKey(3), (2, 128), 0, CFG.vocab_size)
    ref, _ = forward(params, tokens, windowed, attn_impl="xla")
    out, _ = jax.jit(
        lambda p, t: forward(p, t, windowed, attn_impl="ring", mesh=mesh)
    )(params, _cp_put(tokens, mesh))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-3, atol=2e-3)

    sinky = get_config("tiny-gptoss").scaled(
        sliding_window=0, capacity_factor=8.0
    )
    sp = init_params(jax.random.PRNGKey(4), sinky, dtype=jnp.float32)
    toks = jax.random.randint(jax.random.PRNGKey(5), (2, 128), 1, sinky.vocab_size)
    ref, _ = forward(sp, toks, sinky, attn_impl="xla")
    out, _ = jax.jit(
        lambda p, t: forward(p, t, sinky, attn_impl="ring", mesh=mesh)
    )(sp, _cp_put(toks, mesh))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-3, atol=2e-3)


def test_cp_forward_softcap():
    """Gemma2-style score softcapping rides the ring fold (the canonical
    _apply_softcap, cap-before-mask)."""
    mesh = make_mesh({"dp": 1, "fsdp": 1, "sp": 8})
    capped = CFG.scaled(attn_softcap=20.0)
    params = init_params(jax.random.PRNGKey(6), capped, dtype=jnp.float32)
    tokens = jax.random.randint(jax.random.PRNGKey(7), (2, 128), 0, capped.vocab_size)
    ref, _ = forward(params, tokens, capped, attn_impl="xla")
    out, _ = jax.jit(
        lambda p, t: forward(p, t, capped, attn_impl="ring", mesh=mesh)
    )(params, _cp_put(tokens, mesh))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-3, atol=2e-3)


def test_cp_composes_with_tp_and_fsdp():
    """Context parallelism on a (fsdp, tp, sp) mesh: heads shard over tp
    (megatron layout — no silent per-device replication of every head's
    attention), batch over fsdp, sequence over sp."""
    from prime_tpu.parallel.sharding import ring_qkv_axes, shard_params

    mesh = make_mesh({"fsdp": 2, "tp": 2, "sp": 2})
    assert ring_qkv_axes(mesh, CFG.n_kv_heads) == (("fsdp",), "tp")
    params = init_params(jax.random.PRNGKey(0), CFG, dtype=jnp.float32)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0, CFG.vocab_size)
    ref, _ = forward(params, tokens, CFG, attn_impl="xla")
    sharded = shard_params(params, mesh, CFG)
    out, _ = jax.jit(
        lambda p, t: forward(p, t, CFG, attn_impl="ring", mesh=mesh)
    )(sharded, _cp_put(tokens, mesh))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-3, atol=2e-3)
    # a tp degree the kv heads can't divide is an error, not replication
    with pytest.raises(ValueError, match="divide n_kv_heads"):
        ring_qkv_axes(make_mesh({"tp": 8}), CFG.n_kv_heads)


def test_cp_train_step_matches_plain():
    """One optimizer step under context parallelism == the plain step:
    same loss, same updated parameters (the ring is exactly differentiable
    — ppermute's transpose is the reverse rotation)."""
    mesh = make_mesh({"dp": 1, "fsdp": 1, "sp": 8})
    params = init_params(jax.random.PRNGKey(0), CFG, dtype=jnp.float32)
    optimizer = default_optimizer(learning_rate=1e-2)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 128), 0, CFG.vocab_size)
    targets = jnp.roll(tokens, -1, axis=1)
    mask = jnp.ones_like(tokens, dtype=jnp.float32)

    # the step donates its state: each run gets its own copy of the params
    plain_step = make_train_step(CFG, optimizer, attn_impl="xla")
    plain_state, plain_metrics = plain_step(
        init_train_state(jax.tree.map(jnp.copy, params), optimizer), tokens, targets, mask
    )

    cp_step = make_train_step(CFG, optimizer, attn_impl="ring", ring_mesh=mesh)
    cp_state, cp_metrics = cp_step(
        init_train_state(jax.tree.map(jnp.copy, params), optimizer),
        _cp_put(tokens, mesh), _cp_put(targets, mesh), _cp_put(mask, mesh),
    )
    assert float(cp_metrics["loss"]) == pytest.approx(float(plain_metrics["loss"]), rel=1e-5)
    # the ring folds KV blocks in a different order than dense softmax, so
    # near-zero gradient elements see fp reassociation that Adam's
    # normalization amplifies — atol covers that, not a math divergence
    for a, b in zip(jax.tree.leaves(plain_state.params), jax.tree.leaves(cp_state.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-3, atol=5e-4)


def test_cp_rejects_invalid_modes():
    mesh = make_mesh({"dp": 1, "fsdp": 1, "sp": 8})
    params = init_params(jax.random.PRNGKey(0), CFG, dtype=jnp.float32)
    tokens = jnp.zeros((2, 128), jnp.int32)
    with pytest.raises(ValueError, match="no-cache"):
        forward(
            params, tokens, CFG, attn_impl="ring", mesh=mesh,
            cache=init_cache(CFG, 2, 256, dtype=jnp.float32),
        )
    with pytest.raises(ValueError, match="'sp' axis"):
        forward(params, tokens, CFG, attn_impl="ring", mesh=make_mesh({"dp": 8}))
    with pytest.raises(ValueError, match="uniform"):
        forward(
            params, tokens, CFG.scaled(sliding_window=16, sliding_pattern="even"),
            attn_impl="ring", mesh=mesh,
        )
