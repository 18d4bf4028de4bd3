"""Pipeline parallelism: GPipe schedule correctness + pipelined training."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from prime_tpu.models import get_config
from prime_tpu.models.llama import forward, init_params
from prime_tpu.parallel.mesh import make_mesh
from prime_tpu.parallel.pipeline import (
    make_pipeline_train_step,
    pipeline_forward,
    shard_pipeline_params,
)


CFG = get_config("tiny-test").scaled(n_layers=4)  # 4 layers over 2 or 4 stages


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.PRNGKey(0), CFG, dtype=jnp.float32)


def test_pipeline_forward_matches_dense(params):
    """Pipelined logits == the plain scan forward, for 2 and 4 stages."""
    tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0, CFG.vocab_size)
    ref, _ = forward(params, tokens, CFG, attn_impl="xla")
    for stages, microbatches in ((2, 4), (4, 2), (2, 8)):
        mesh = make_mesh({"pp": stages}, devices=jax.devices()[:stages])
        staged = shard_pipeline_params(params, mesh, CFG)
        out = pipeline_forward(staged, tokens, CFG, mesh, n_microbatches=microbatches)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=2e-3, atol=2e-3,
            err_msg=f"pp={stages} M={microbatches}",
        )


def test_pipeline_single_stage_degenerates(params):
    tokens = jax.random.randint(jax.random.PRNGKey(2), (4, 8), 0, CFG.vocab_size)
    mesh = make_mesh({"pp": 1}, devices=jax.devices()[:1])
    staged = shard_pipeline_params(params, mesh, CFG)
    out = pipeline_forward(staged, tokens, CFG, mesh, n_microbatches=2)
    ref, _ = forward(params, tokens, CFG, attn_impl="xla")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-3, atol=2e-3)


def test_pipeline_train_step_reduces_loss(params):
    from prime_tpu.train import default_optimizer, init_train_state

    mesh = make_mesh({"pp": 4}, devices=jax.devices()[:4])
    optimizer = default_optimizer(learning_rate=1e-3)
    # fresh params: the jitted step donates its state, and device_put may
    # alias the module fixture's buffers when the placement already matches
    own_params = init_params(jax.random.PRNGKey(0), CFG, dtype=jnp.float32)
    state = init_train_state(shard_pipeline_params(own_params, mesh, CFG), optimizer)
    step = make_pipeline_train_step(CFG, optimizer, mesh, n_microbatches=2)
    tokens = jax.random.randint(jax.random.PRNGKey(3), (8, 16), 0, CFG.vocab_size)
    targets = jnp.roll(tokens, -1, axis=1)
    mask = jnp.ones_like(tokens, jnp.float32)
    losses = []
    for _ in range(4):
        state, metrics = step(state, tokens, targets, mask)
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0]
    assert np.isfinite(losses).all()


def test_pipeline_grads_match_dense(params):
    """Backprop through ppermute: pipelined grads == dense grads."""
    from prime_tpu.train.trainer import cross_entropy_loss

    tokens = jax.random.randint(jax.random.PRNGKey(4), (4, 8), 0, CFG.vocab_size)
    targets = jnp.roll(tokens, -1, axis=1)
    mask = jnp.ones_like(tokens, jnp.float32)

    def dense_loss(p):
        logits, _ = forward(p, tokens, CFG, attn_impl="xla")
        return cross_entropy_loss(logits, targets, mask)

    mesh = make_mesh({"pp": 2}, devices=jax.devices()[:2])

    def pp_loss(p):
        return cross_entropy_loss(
            pipeline_forward(p, tokens, CFG, mesh, n_microbatches=2), targets, mask
        )

    dense_grads = jax.grad(dense_loss)(params)
    staged = shard_pipeline_params(params, mesh, CFG)
    pp_grads = jax.grad(pp_loss)(staged)
    for a, b in zip(jax.tree.leaves(dense_grads), jax.tree.leaves(pp_grads)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=5e-3, atol=5e-4)


def test_pipeline_moe_matches_dense():
    """Sparse-MoE layers pipeline too: with capacity high enough that no
    token drops, the staged logits equal the plain scan's (per-microbatch
    routing groups see the same tokens), and the train step carries the
    bubble-masked load-balance aux."""
    cfg = get_config("tiny-moe").scaled(n_layers=4, capacity_factor=8.0)
    mparams = init_params(jax.random.PRNGKey(7), cfg, dtype=jnp.float32)
    tokens = jax.random.randint(jax.random.PRNGKey(8), (4, 16), 0, cfg.vocab_size)
    ref, _, ref_aux = forward(mparams, tokens, cfg, attn_impl="xla", return_aux=True)
    mesh = make_mesh({"pp": 2}, devices=jax.devices()[:2])
    staged = shard_pipeline_params(mparams, mesh, cfg)
    out, aux = pipeline_forward(staged, tokens, cfg, mesh, n_microbatches=2, return_aux=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-3, atol=2e-3)
    # aux is a mean over (different) routing groups — same scale, not equal
    assert 0.1 * float(ref_aux) < float(aux) < 10 * float(ref_aux)

    from prime_tpu.train import default_optimizer, init_train_state

    opt = default_optimizer(learning_rate=1e-3)
    state = init_train_state(staged, opt)
    step = make_pipeline_train_step(cfg, opt, mesh, n_microbatches=2)
    state, metrics = step(state, tokens, jnp.roll(tokens, -1, 1), jnp.ones_like(tokens, jnp.float32))
    assert np.isfinite(float(metrics["loss"]))


def test_pipeline_validates_divisibility(params):
    mesh = make_mesh({"pp": 4}, devices=jax.devices()[:4])
    tokens = jnp.zeros((6, 8), jnp.int32)
    with pytest.raises(ValueError, match="not divisible"):
        pipeline_forward(params, tokens, CFG, mesh, n_microbatches=4)
    bad_cfg = CFG.scaled(n_layers=3)
    with pytest.raises(ValueError, match="divide into"):
        pipeline_forward(params, jnp.zeros((4, 8), jnp.int32), bad_cfg, mesh, 2)


def test_pipeline_forward_matches_dense_gemma_style():
    """Gemma knobs (GeGLU, (1+w) norms, post-norms, scaled embed, softcaps,
    and the even sliding/global alternation whose per-layer flags must stay
    GLOBALLY indexed across stage boundaries) must produce identical logits
    through the pipeline schedule."""
    cfg = CFG.scaled(
        name="tiny-gemma-pp", act="gelu_tanh", norm_plus_one=True, post_norms=True,
        scale_embed=True, attn_softcap=50.0, final_softcap=30.0, query_scale=24,
        sliding_window=4,  # seq 16 > window 4: sliding layers genuinely differ
    )
    gparams = init_params(jax.random.PRNGKey(3), cfg, dtype=jnp.float32)
    tokens = jax.random.randint(jax.random.PRNGKey(4), (4, 16), 0, cfg.vocab_size)
    ref, _ = forward(gparams, tokens, cfg, attn_impl="xla")
    mesh = make_mesh({"pp": 2}, devices=jax.devices()[:2])
    staged = shard_pipeline_params(gparams, mesh, cfg)
    out = pipeline_forward(staged, tokens, cfg, mesh, n_microbatches=2)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-3, atol=2e-3)


def test_pipeline_forward_matches_dense_gemma3_style():
    """Gemma3's 5:1 schedule + dual-frequency rope (local theta selected by
    the traced flag) through the pipeline: with 4 layers and pattern '3:1',
    the global layer sits at index 3 — in the SECOND stage, so a local
    (stage-relative) flag indexing would compute it wrong."""
    cfg = CFG.scaled(
        name="tiny-g3-pp", act="gelu_tanh", norm_plus_one=True, post_norms=True,
        scale_embed=True, qk_norm=True, query_scale=24,
        sliding_window=4, sliding_pattern="3:1",
        rope_theta=1000000.0, rope_local_theta=10000.0, rope_scale=8.0,
    )
    gparams = init_params(jax.random.PRNGKey(5), cfg, dtype=jnp.float32)
    tokens = jax.random.randint(jax.random.PRNGKey(6), (4, 16), 0, cfg.vocab_size)
    ref, _ = forward(gparams, tokens, cfg, attn_impl="xla")
    mesh = make_mesh({"pp": 2}, devices=jax.devices()[:2])
    staged = shard_pipeline_params(gparams, mesh, cfg)
    out = pipeline_forward(staged, tokens, cfg, mesh, n_microbatches=2)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-3, atol=2e-3)


def test_pipeline_gptoss_matches_dense():
    """GPT-OSS config (sinks + biased clamped-GLU MoE + even-alternating
    sliding window + non-truncated yarn) under pipeline parallelism: the
    sinks/bias leaves shard over pp with the layer stack and the staged
    logits match the plain scan."""
    cfg = get_config("tiny-gptoss").scaled(n_layers=4, capacity_factor=8.0)
    mparams = init_params(jax.random.PRNGKey(11), cfg, dtype=jnp.float32)
    tokens = jax.random.randint(jax.random.PRNGKey(12), (4, 16), 1, cfg.vocab_size)
    ref, _ = forward(mparams, tokens, cfg, attn_impl="xla")
    mesh = make_mesh({"pp": 2}, devices=jax.devices()[:2])
    staged = shard_pipeline_params(mparams, mesh, cfg)
    out = pipeline_forward(staged, tokens, cfg, mesh, n_microbatches=2)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-3, atol=2e-3)
