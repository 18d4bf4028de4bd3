"""GRPO trainer: advantage math, masking, update direction, end-to-end reward
improvement, and a sharded update over the virtual mesh."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from prime_tpu.evals.tokenizer import ByteTokenizer
from prime_tpu.models import get_config
from prime_tpu.models.llama import init_params
from prime_tpu.train.grpo import (
    GrpoConfig,
    group_advantages,
    make_grpo_step,
    pack_rollouts,
    run_grpo,
    token_logprobs,
)
from prime_tpu.train.trainer import init_train_state


@pytest.fixture()
def tiny():
    # function-scoped: make_grpo_step donates its TrainState, so params fed to
    # one step are dead buffers afterwards — each test needs a fresh tree
    config = get_config("tiny-test")
    params = init_params(jax.random.PRNGKey(0), config, dtype=jnp.float32)
    return config, params


# -- pure math ---------------------------------------------------------------


def test_group_advantages_zero_mean_unit_std():
    rewards = np.array([[0.0, 1.0, 0.0, 1.0], [0.2, 0.4, 0.6, 0.8]], dtype=np.float32)
    adv = group_advantages(rewards, eps=0.0)
    np.testing.assert_allclose(adv.mean(axis=1), 0.0, atol=1e-6)
    np.testing.assert_allclose(adv.std(axis=1), 1.0, atol=1e-5)


def test_group_advantages_degenerate_group_is_zero():
    rewards = np.full((1, 4), 0.7, dtype=np.float32)
    adv = group_advantages(rewards)
    np.testing.assert_allclose(adv, 0.0)


def test_grpo_config_validation():
    with pytest.raises(ValueError, match="temperature"):
        GrpoConfig(temperature=0.0)
    with pytest.raises(ValueError, match="group_size"):
        GrpoConfig(group_size=1)


# -- packing -----------------------------------------------------------------


def test_pack_rollouts_contiguous_and_eos_masked():
    prompt_ids = [[5, 6, 7], [9]]
    gen = np.array([[11, 2, 0, 0], [12, 13, 14, 15]], dtype=np.int32)  # eos_id=2 row 0
    gen_lens = np.array([1, 4])
    tokens, mask = pack_rollouts(prompt_ids, gen, gen_lens, pad_id=0, total_len=8, eos_id=2)
    # row 0: prompt 5,6,7 then completion 11 + EOS 2 — both masked
    assert tokens[0].tolist() == [5, 6, 7, 11, 2, 0, 0, 0]
    assert mask[0].tolist() == [0, 0, 0, 1, 1, 0, 0, 0]
    # row 1: no EOS fired — all 4 generated tokens masked, no +1
    assert tokens[1].tolist() == [9, 12, 13, 14, 15, 0, 0, 0]
    assert mask[1].tolist() == [0, 1, 1, 1, 1, 0, 0, 0]


def test_token_logprobs_shape_and_position_zero(tiny):
    config, params = tiny
    tokens = jnp.array([[3, 4, 5, 6]], dtype=jnp.int32)
    lp = token_logprobs(params, tokens, config)
    assert lp.shape == (1, 4)
    assert float(lp[0, 0]) == 0.0
    assert bool(jnp.all(lp[:, 1:] <= 0.0))


# -- update direction --------------------------------------------------------


def test_update_raises_positive_advantage_logprob(tiny):
    """One step must raise the logprob of positively-advantaged completions
    and lower the negatively-advantaged ones — the core policy-gradient
    direction, deterministic (no sampling involved)."""
    config, params = tiny
    optimizer = optax.sgd(5e-2)
    state = init_train_state(params, optimizer)
    step = make_grpo_step(config, optimizer, clip_eps=0.2, kl_coef=0.0)

    tokens = jnp.array([[3, 4, 5, 6, 7, 8], [3, 4, 5, 9, 10, 11]], dtype=jnp.int32)
    mask = jnp.array([[0, 0, 0, 1, 1, 1], [0, 0, 0, 1, 1, 1]], dtype=jnp.float32)
    adv = jnp.array([1.0, -1.0])
    old_lp = token_logprobs(state.params, tokens, config)

    new_state, metrics = step(state, None, tokens, mask, adv, old_lp, old_lp)
    new_lp = token_logprobs(new_state.params, tokens, config)

    pos_delta = float(jnp.sum((new_lp - old_lp)[0] * mask[0]))
    neg_delta = float(jnp.sum((new_lp - old_lp)[1] * mask[1]))
    assert pos_delta > 0, f"positive-advantage completion logprob fell: {pos_delta}"
    assert neg_delta < 0, f"negative-advantage completion logprob rose: {neg_delta}"
    assert np.isfinite(float(metrics["loss"]))


def test_padding_tokens_do_not_contribute(tiny):
    """Perturbing tokens outside the mask must not change the loss."""
    config, params = tiny
    optimizer = optax.sgd(1e-2)
    state = init_train_state(params, optimizer)
    step = make_grpo_step(config, optimizer)

    tokens = jnp.array([[3, 4, 5, 6, 0, 0]], dtype=jnp.int32)
    mask = jnp.array([[0, 1, 1, 1, 0, 0]], dtype=jnp.float32)
    adv = jnp.array([1.0])
    old_lp = token_logprobs(state.params, tokens, config)
    fresh = jax.tree.map(jnp.copy, params)  # step donates its input state

    _, m1 = step(state, None, tokens, mask, adv, old_lp, old_lp)
    state2 = init_train_state(fresh, optimizer)
    tokens2 = tokens.at[0, 4].set(9)  # pad-region perturbation
    old_lp2 = jnp.where(mask > 0, old_lp, 0.0)
    _, m2 = step(state2, None, tokens2, mask, adv, old_lp2, old_lp2)
    assert float(m1["loss"]) == pytest.approx(float(m2["loss"]), abs=1e-5)


def test_ratio_clipping_engages(tiny):
    """With old_lp far below the current policy, ratios blow past 1+eps and
    the clip fraction must register."""
    config, params = tiny
    optimizer = optax.sgd(1e-3)
    state = init_train_state(params, optimizer)
    step = make_grpo_step(config, optimizer, clip_eps=0.2)

    tokens = jnp.array([[3, 4, 5, 6]], dtype=jnp.int32)
    mask = jnp.array([[0, 1, 1, 1]], dtype=jnp.float32)
    adv = jnp.array([1.0])
    old_lp = token_logprobs(state.params, tokens, config) - 2.0  # ratio ~ e^2
    _, metrics = step(state, None, tokens, mask, adv, old_lp, old_lp)
    assert float(metrics["clip_frac"]) == pytest.approx(1.0)
    assert float(metrics["ratio_mean"]) > 1.2


def test_kl_zero_against_self_and_positive_after_drift(tiny):
    config, params = tiny
    optimizer = optax.sgd(5e-2)
    state = init_train_state(params, optimizer)
    step = make_grpo_step(config, optimizer, kl_coef=0.1)

    tokens = jnp.array([[3, 4, 5, 6]], dtype=jnp.int32)
    mask = jnp.array([[0, 1, 1, 1]], dtype=jnp.float32)
    adv = jnp.array([1.0])
    lp0 = token_logprobs(params, tokens, config)
    new_state, metrics = step(state, None, tokens, mask, adv, lp0, lp0)
    assert float(metrics["kl"]) == pytest.approx(0.0, abs=1e-6)
    # after the update the policy has moved off the (frozen) reference
    lp1 = token_logprobs(new_state.params, tokens, config)
    state2 = init_train_state(new_state.params, optimizer)
    _, metrics2 = step(state2, None, tokens, mask, adv, lp1, lp0)
    assert float(metrics2["kl"]) > 0.0


# -- end-to-end --------------------------------------------------------------


def test_run_grpo_improves_reward():
    """20 GRPO steps on an env whose reward is the fraction of digit bytes in
    the completion (a dense, trivially learnable signal for a random-init
    model): the mean reward must rise above its start."""
    from prime_tpu.models.config import ModelConfig

    # byte-range vocab so every sampled id decodes to a real character —
    # digits carry ~16% of the random policy's mass, a dense group signal
    config = ModelConfig(
        name="grpo-test", vocab_size=64, d_model=64, n_layers=2, n_heads=4,
        n_kv_heads=2, d_ff=128, max_seq_len=64,
    )
    params = init_params(jax.random.PRNGKey(1), config, dtype=jnp.float32)
    tok = ByteTokenizer()

    def scorer(completion: str, answer: str) -> float:
        if not completion:
            return 0.0
        return sum(1 for c in completion if c.isdigit()) / len(completion)

    cfg = GrpoConfig(
        group_size=4,
        prompts_per_step=2,
        max_prompt_len=8,
        max_new_tokens=8,
        temperature=1.0,
        steps=20,
        learning_rate=0.0,  # optimizer passed explicitly below
    )
    state, report = run_grpo(
        config,
        params,
        tok,
        # prompt bytes must stay under the 64-id vocab: digits/punctuation only
        examples=[{"prompt": "12+34", "answer": "1"}, {"prompt": "5*6", "answer": "2"}],
        scorer=scorer,
        cfg=cfg,
        optimizer=optax.chain(optax.clip_by_global_norm(1.0), optax.adam(3e-3)),
        rng=jax.random.PRNGKey(7),
    )
    assert report.steps == 20
    early = float(np.mean(report.mean_rewards[:3]))
    late = float(np.mean(report.mean_rewards[-3:]))
    assert late > early, f"reward did not improve: early={early:.4f} late={late:.4f}"
    assert np.isfinite(report.final_loss)


def test_run_grpo_sharded_mesh():
    """One sharded GRPO step over the virtual 8-device mesh: rollout batch
    divisibility is enforced and the update executes SPMD."""
    from prime_tpu.parallel.mesh import make_mesh

    config = get_config("tiny-test")
    params = init_params(jax.random.PRNGKey(2), config, dtype=jnp.float32)
    tok = ByteTokenizer()
    mesh = make_mesh({"dp": 2, "fsdp": 2, "tp": 2}, devices=jax.devices()[:8])

    cfg = GrpoConfig(
        group_size=4, prompts_per_step=2, max_prompt_len=8, max_new_tokens=4,
        temperature=1.0, steps=2, kl_coef=0.05,
    )
    state, report = run_grpo(
        config, params, tok,
        examples=[{"prompt": "ab", "answer": "ab"}],
        scorer=lambda c, a: float(len(c) > 0),
        cfg=cfg,
        mesh=mesh,
        rng=jax.random.PRNGKey(3),
    )
    assert report.steps == 2
    assert np.isfinite(report.final_loss)


def test_run_grpo_batch_divisibility_error():
    from prime_tpu.parallel.mesh import make_mesh

    config = get_config("tiny-test")
    params = init_params(jax.random.PRNGKey(2), config, dtype=jnp.float32)
    mesh = make_mesh({"dp": 4, "fsdp": 2, "tp": 1}, devices=jax.devices()[:8])
    cfg = GrpoConfig(group_size=3, prompts_per_step=1, temperature=1.0)
    with pytest.raises(ValueError, match="divisible"):
        run_grpo(
            config, params, ByteTokenizer(),
            examples=[{"prompt": "a", "answer": "a"}],
            scorer=None, cfg=cfg, mesh=mesh,
        )


# -- LoRA GRPO ---------------------------------------------------------------


def test_run_grpo_lora_trains_adapters_only():
    """GRPO with lora: the returned state holds adapter factors (base stays
    frozen), rollouts/updates go through the merged policy, and the KL
    reference is the base itself (zero KL at the zero-effect init)."""
    from prime_tpu.train.lora import LoraConfig

    config = get_config("tiny-test")
    params = init_params(jax.random.PRNGKey(1), config, dtype=jnp.float32)
    before = jax.tree.map(jnp.copy, params)
    cfg = GrpoConfig(
        group_size=4, prompts_per_step=2, max_prompt_len=8, max_new_tokens=4,
        temperature=1.0, steps=2, kl_coef=0.05, learning_rate=1e-2,
    )
    state, report = run_grpo(
        config, params, ByteTokenizer(),
        examples=[{"prompt": "ab", "answer": "ab"}],
        scorer=lambda c, a: float(len(c) > 0),
        cfg=cfg,
        rng=jax.random.PRNGKey(5),
        lora=LoraConfig(r=4, alpha=8),
    )
    assert report.steps == 2 and np.isfinite(report.final_loss)
    # state carries {layers: {wq: {a, b}, ...}} adapter factors
    assert set(state.params["layers"]["wq"]) == {"a", "b"}
    # base weights untouched
    for x, y in zip(jax.tree.leaves(before), jax.tree.leaves(params)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_run_grpo_lora_sharded():
    from prime_tpu.parallel.mesh import make_mesh
    from prime_tpu.train.lora import LoraConfig

    config = get_config("tiny-test")
    params = init_params(jax.random.PRNGKey(2), config, dtype=jnp.float32)
    mesh = make_mesh({"dp": 2, "fsdp": 2, "tp": 2}, devices=jax.devices()[:8])
    cfg = GrpoConfig(
        group_size=4, prompts_per_step=2, max_prompt_len=8, max_new_tokens=4,
        temperature=1.0, steps=1,
    )
    state, report = run_grpo(
        config, params, ByteTokenizer(),
        examples=[{"prompt": "xy", "answer": "xy"}],
        scorer=None, cfg=cfg, mesh=mesh, rng=jax.random.PRNGKey(6),
        lora=LoraConfig(r=4),
    )
    assert report.steps == 1 and np.isfinite(report.final_loss)


def test_run_grpo_does_not_consume_caller_params():
    """ADVICE r2: run_grpo donates its TrainState internally — the CALLER's
    params tree must stay alive and usable after the run (saving, comparing,
    a second run), not alias deleted donated buffers."""
    config = get_config("tiny-test")
    params = init_params(jax.random.PRNGKey(3), config, dtype=jnp.float32)
    tok = ByteTokenizer()
    cfg = GrpoConfig(
        group_size=2, prompts_per_step=1, max_prompt_len=8, max_new_tokens=4,
        temperature=1.0, steps=1,
    )
    run_grpo(
        config, params, tok,
        examples=[{"prompt": "1+1", "answer": "2"}],
        scorer=None,
        cfg=cfg,
        rng=jax.random.PRNGKey(0),
    )
    # any host-side use of the original tree must still work
    total = float(jnp.sum(params["embed"]))
    assert np.isfinite(total)


def test_run_grpo_lora_with_remat_matches_no_remat():
    """The GRPO-LoRA fused path under activation checkpointing: remat must
    change memory, not math — adapters after a rematerialized run equal the
    plain run's bit-for-bit aside from fp reassociation."""
    from prime_tpu.train.lora import LoraConfig

    config = get_config("tiny-test")
    params = init_params(jax.random.PRNGKey(1), config, dtype=jnp.float32)

    def run(remat):
        cfg = GrpoConfig(
            group_size=4, prompts_per_step=2, max_prompt_len=8, max_new_tokens=4,
            temperature=1.0, steps=2, kl_coef=0.05, learning_rate=1e-2,
            remat=remat,
        )
        state, report = run_grpo(
            config, params, ByteTokenizer(),
            examples=[{"prompt": "ab", "answer": "ab"}],
            scorer=lambda c, a: float(len(c) > 0),
            cfg=cfg,
            rng=jax.random.PRNGKey(5),
            lora=LoraConfig(r=4, alpha=8),
        )
        assert np.isfinite(report.final_loss)
        return state

    plain = run("none")
    dots = run("dots")
    full = run("full")
    # recompute reassociates fp ops; tolerance covers that, not a math change
    for a, b in zip(jax.tree.leaves(plain.params), jax.tree.leaves(dots.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5)
    for a, b in zip(jax.tree.leaves(plain.params), jax.tree.leaves(full.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5)
