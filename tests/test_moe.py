"""Mixture-of-experts: routing math, model integration, Mixtral parity, ep sharding."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from prime_tpu.models import get_config
from prime_tpu.models.llama import forward, init_params
from prime_tpu.ops.moe import expert_capacity, moe_mlp, top_k_routing


CFG = get_config("tiny-moe")


# -- routing ------------------------------------------------------------------


def test_topk_routing_shapes_and_mass():
    rng = jax.random.PRNGKey(0)
    logits = jax.random.normal(rng, (32, 4), dtype=jnp.float32)
    capacity = expert_capacity(32, 4, k=2, capacity_factor=2.0)
    dispatch, combine, aux = top_k_routing(logits, k=2, capacity=capacity)
    assert dispatch.shape == (32, 4, capacity) == combine.shape
    # with generous capacity every token is dispatched to exactly k experts
    np.testing.assert_allclose(np.asarray(jnp.sum(dispatch, axis=(1, 2))), 2.0)
    # combine weights sum to 1 per token (renormalized top-k gates)
    np.testing.assert_allclose(np.asarray(jnp.sum(combine, axis=(1, 2))), 1.0, rtol=1e-5)
    # each expert slot holds at most one token
    assert float(jnp.max(jnp.sum(dispatch, axis=0))) <= 1.0 + 1e-6
    assert float(aux) > 0


def test_capacity_drops_tokens():
    # all tokens prefer expert 0; capacity forces drops, residual path holds
    logits = jnp.zeros((32, 4)).at[:, 0].set(10.0)
    dispatch, combine, _ = top_k_routing(logits, k=1, capacity=8)
    per_token = np.asarray(jnp.sum(dispatch, axis=(1, 2)))
    assert per_token.sum() == 8  # only capacity-many served
    assert set(per_token.tolist()) == {0.0, 1.0}


def test_moe_mlp_matches_dense_expert_when_one_expert():
    """n_experts=1, k=1: MoE must reduce to the plain FFN (no routing freedom)."""
    rng = jax.random.PRNGKey(1)
    d, f = 32, 64
    x = jax.random.normal(rng, (2, 8, d), dtype=jnp.float32)
    w_gate = jax.random.normal(jax.random.PRNGKey(2), (1, d, f), jnp.float32) * 0.1
    w_up = jax.random.normal(jax.random.PRNGKey(3), (1, d, f), jnp.float32) * 0.1
    w_down = jax.random.normal(jax.random.PRNGKey(4), (1, f, d), jnp.float32) * 0.1
    router = jnp.zeros((d, 1), jnp.float32)
    y, _ = moe_mlp(x, router, w_gate, w_up, w_down, k=1, capacity_factor=4.0)
    dense = (jax.nn.silu(x @ w_gate[0]) * (x @ w_up[0])) @ w_down[0]
    np.testing.assert_allclose(np.asarray(y), np.asarray(dense), rtol=1e-4, atol=1e-5)


# -- model integration --------------------------------------------------------


def test_moe_forward_and_decode_consistency():
    """Prefill+decode through the MoE stack == full forward (same tokens)."""
    from prime_tpu.models.llama import init_cache

    params = init_params(jax.random.PRNGKey(0), CFG, dtype=jnp.float32)
    seq = 8
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, seq), 0, CFG.vocab_size)
    full_logits, _ = forward(params, tokens, CFG)

    prefix = 5
    cache = init_cache(CFG, 2, seq + 2, dtype=jnp.float32)
    logits, cache = forward(params, tokens[:, :prefix], CFG, cache=cache)
    np.testing.assert_allclose(
        np.asarray(full_logits[:, :prefix]), np.asarray(logits), rtol=2e-3, atol=2e-3
    )
    for i in range(prefix, seq):
        step_logits, cache = forward(
            params, tokens[:, i : i + 1], CFG,
            positions=cache.lengths[:, None], cache=cache, decode=True,
        )
        np.testing.assert_allclose(
            np.asarray(full_logits[:, i]), np.asarray(step_logits[:, 0]), rtol=2e-3, atol=2e-3
        )


def test_moe_train_step_includes_aux_and_learns():
    from prime_tpu.train import default_optimizer, init_train_state, make_train_step

    optimizer = default_optimizer(learning_rate=1e-3)
    params = init_params(jax.random.PRNGKey(0), CFG, dtype=jnp.float32)
    state = init_train_state(params, optimizer)
    step = make_train_step(CFG, optimizer)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, CFG.vocab_size)
    targets = jnp.roll(tokens, -1, axis=1)
    mask = jnp.ones_like(tokens, jnp.float32)
    losses = []
    for _ in range(5):
        state, metrics = step(state, tokens, targets, mask)
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0]
    assert np.isfinite(losses).all()


def test_moe_generate_end_to_end():
    from prime_tpu.models.sampler import generate

    params = init_params(jax.random.PRNGKey(0), CFG, dtype=jnp.float32)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 6), 1, CFG.vocab_size)
    lengths = jnp.asarray([6, 4], jnp.int32)
    result = generate(params, tokens, lengths, CFG, jax.random.PRNGKey(2), max_new_tokens=4)
    assert result.tokens.shape == (2, 4)


# -- Mixtral checkpoint parity ------------------------------------------------


@pytest.fixture(scope="module")
def mixtral_model():
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")
    cfg = transformers.MixtralConfig(
        vocab_size=256,
        hidden_size=64,
        intermediate_size=128,
        num_hidden_layers=2,
        num_attention_heads=4,
        num_key_value_heads=2,
        num_local_experts=4,
        num_experts_per_tok=2,
        max_position_embeddings=128,
        rope_theta=10000.0,
        rms_norm_eps=1e-5,
        tie_word_embeddings=False,
        attn_implementation="eager",
    )
    torch.manual_seed(0)
    model = transformers.MixtralForCausalLM(cfg)
    model.eval()
    return model


def test_mixtral_logits_match_transformers(mixtral_model):
    torch = pytest.importorskip("torch")

    from prime_tpu.models.hf_loader import config_from_hf, params_from_state_dict

    state = {k: v.float().numpy() for k, v in mixtral_model.state_dict().items()}
    config = config_from_hf(mixtral_model.config, name="tiny-mixtral")
    assert config.is_moe and config.n_experts == 4
    # generous capacity: parity requires no token drops
    config = config.scaled(capacity_factor=8.0)
    params = params_from_state_dict(state, config, dtype=jnp.float32)

    tokens = np.array([[1, 7, 42, 5, 99, 3], [2, 11, 250, 77, 8, 4]], dtype=np.int32)
    with torch.no_grad():
        ref = mixtral_model(torch.tensor(tokens, dtype=torch.long)).logits.numpy()
    ours, _ = forward(params, jnp.asarray(tokens), config)
    np.testing.assert_allclose(np.asarray(ours), ref, rtol=2e-3, atol=2e-3)


# -- expert parallelism -------------------------------------------------------


def test_moe_sharded_train_step_with_ep_axis():
    from prime_tpu.parallel.mesh import make_mesh
    from prime_tpu.parallel.sharding import shard_batch, shard_params
    from prime_tpu.train import (
        default_optimizer,
        init_train_state,
        make_train_step,
        shard_train_state,
    )

    mesh = make_mesh({"dp": 1, "fsdp": 2, "ep": 2, "tp": 2})
    optimizer = default_optimizer(learning_rate=1e-3)
    params = init_params(jax.random.PRNGKey(0), CFG, dtype=jnp.float32)
    state = shard_train_state(init_train_state(params, optimizer), mesh, CFG)
    step = make_train_step(CFG, optimizer)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, CFG.vocab_size)
    tokens, targets, mask = (
        shard_batch(x, mesh)
        for x in (tokens, jnp.roll(tokens, -1, 1), jnp.ones_like(tokens, jnp.float32))
    )
    state, metrics = step(state, tokens, targets, mask)
    assert np.isfinite(float(metrics["loss"]))
    # expert weights really are sharded over ep
    sharding = state.params["layers"]["w_gate"].sharding
    assert "ep" in str(sharding.spec)


def test_moe_sharded_generate_via_slice():
    """JaxGenerator serves an MoE model over a slice mesh, auto-carving ep."""
    from prime_tpu.evals.runner import JaxGenerator

    gen = JaxGenerator("tiny-moe", slice_name="v5e-8", tensor_parallel=2)
    assert gen.mesh.shape.get("ep", 1) == 4  # 8 devices / tp2 -> all 4 experts sharded
    outs = gen.generate(["a", "bb"], max_new_tokens=4, temperature=0.0)
    assert len(outs) == 2


def test_prune_spec_drops_missing_axes():
    from jax.sharding import PartitionSpec as P

    from prime_tpu.parallel.mesh import make_mesh
    from prime_tpu.parallel.sharding import prune_spec

    mesh = make_mesh({"dp": 1, "fsdp": 4, "tp": 2})
    assert prune_spec(P(None, "ep", "fsdp", "tp"), mesh) == P(None, None, "fsdp", "tp")
    assert prune_spec(P(("dp", "fsdp"), None), mesh) == P(("dp", "fsdp"), None)
    assert prune_spec(P(("dp", "ep"), None), mesh) == P("dp", None)


def test_moe_serving_raises_capacity_to_no_drop():
    """JaxGenerator must never drop tokens at inference (capacity >= E/k)."""
    from prime_tpu.evals.runner import JaxGenerator

    gen = JaxGenerator("tiny-moe")  # preset capacity_factor is 2.0, E/k = 2.0
    assert gen.config.capacity_factor >= gen.config.n_experts / gen.config.experts_per_token

    tight = get_config("tiny-moe").scaled(capacity_factor=0.5)
    import prime_tpu.models as models_pkg

    # simulate a preset with a tight training capacity
    from prime_tpu.models.config import MODEL_PRESETS

    MODEL_PRESETS["tiny-moe-tight"] = tight.scaled(name="tiny-moe-tight")
    try:
        gen = JaxGenerator("tiny-moe-tight")
        assert gen.config.capacity_factor == 2.0  # raised to E/k
    finally:
        MODEL_PRESETS.pop("tiny-moe-tight")


def test_mesh_for_slice_rejects_impossible_fsdp_ep():
    from prime_tpu.parallel.mesh import mesh_for_slice

    devices = jax.devices()[:8]
    with pytest.raises(ValueError, match="must divide"):
        mesh_for_slice("v5e-8", tensor_parallel=2, fsdp=2, expert_parallel=4, devices=devices)
    with pytest.raises(ValueError, match="must divide"):
        mesh_for_slice("v5e-8", tensor_parallel=2, fsdp=3, devices=devices)


def test_grouped_routing_matches_single_group():
    """Grouped dispatch must not change results when capacity is generous."""
    params = init_params(jax.random.PRNGKey(0), CFG, dtype=jnp.float32)
    lp = jax.tree.map(lambda p: p[0], params["layers"])  # layer 0 weights
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 8, CFG.d_model), jnp.float32)
    one_group, _ = moe_mlp(
        x, lp["router"], lp["w_gate"], lp["w_up"], lp["w_down"],
        k=2, capacity_factor=4.0, group_size=4096,
    )
    grouped, _ = moe_mlp(
        x, lp["router"], lp["w_gate"], lp["w_up"], lp["w_down"],
        k=2, capacity_factor=4.0, group_size=4,  # 4 groups of 4 tokens
    )
    np.testing.assert_allclose(np.asarray(grouped), np.asarray(one_group), rtol=1e-4, atol=1e-5)


def test_grouped_routing_pads_ragged_token_count():
    """Token count not divisible by the group: padding is masked from routing."""
    params = init_params(jax.random.PRNGKey(0), CFG, dtype=jnp.float32)
    lp = jax.tree.map(lambda p: p[0], params["layers"])
    x = jax.random.normal(jax.random.PRNGKey(6), (1, 6, CFG.d_model), jnp.float32)
    y, aux = moe_mlp(
        x, lp["router"], lp["w_gate"], lp["w_up"], lp["w_down"],
        k=2, capacity_factor=4.0, group_size=4,  # 6 tokens -> groups of 4 + pad 2
    )
    ref, _ = moe_mlp(
        x, lp["router"], lp["w_gate"], lp["w_up"], lp["w_down"],
        k=2, capacity_factor=4.0, group_size=4096,
    )
    # group boundaries change per-group capacity contention; generous capacity
    # makes them equivalent
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref), rtol=1e-4, atol=1e-5)
    assert np.isfinite(float(aux))


def test_moe_dispatch_memory_is_linear_in_tokens():
    """The routing tensors must scale O(T·g), not O(T^2)."""
    from prime_tpu.ops.moe import MOE_GROUP_SIZE, expert_capacity

    seq, e, k, cf = 32768, 8, 2, 1.25
    capacity = expert_capacity(min(MOE_GROUP_SIZE, seq), e, k, cf)
    n_groups = -(-seq // MOE_GROUP_SIZE)
    dispatch_elems = n_groups * MOE_GROUP_SIZE * e * capacity
    # 32k-token Mixtral batch: routing tensors stay under ~100M elements
    assert dispatch_elems < 1.1e8, dispatch_elems


# -- DeepSeekMoE: sigmoid scores, selection bias, shared experts --------------


def test_sigmoid_routing_bias_shifts_selection_not_gates():
    """DeepSeek-V3 routing: sigmoid scores each expert independently; the
    aux-free balance bias changes WHICH experts win but the gate values come
    from the unbiased scores; routed_scaling multiplies the combine."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from prime_tpu.ops.moe import top_k_routing

    logits = jnp.asarray(
        [[2.0, 1.0, 0.0, -1.0], [0.0, 0.5, 1.5, -0.5]], jnp.float32
    )
    dispatch, combine, _aux = top_k_routing(
        logits, k=2, capacity=2, score_func="sigmoid", norm_topk=True
    )
    probs = np.asarray(jax.nn.sigmoid(logits))
    # token 0 picks experts 0,1; gates = normalized sigmoid scores
    g0 = probs[0, [0, 1]] / probs[0, [0, 1]].sum()
    np.testing.assert_allclose(np.asarray(combine[0]).sum(-1)[[0, 1]], g0, rtol=1e-5)

    # a huge bias on expert 3 forces it into every selection...
    bias = jnp.asarray([0.0, 0.0, 0.0, 100.0], jnp.float32)
    d_b, c_b, _ = top_k_routing(
        logits, k=2, capacity=2, score_func="sigmoid", select_bias=bias, norm_topk=True
    )
    assert np.asarray(d_b).sum(-1)[:, 3].all()  # expert 3 selected for all tokens
    # ...but its gate is still the UNBIASED sigmoid score (normalized)
    tok0 = probs[0, [0, 3]] / probs[0, [0, 3]].sum()
    np.testing.assert_allclose(np.asarray(c_b[0]).sum(-1)[[0, 3]], tok0, rtol=1e-5)

    # routed scaling multiplies the combine weights
    _d, c_s, _ = top_k_routing(
        logits, k=2, capacity=2, score_func="sigmoid", norm_topk=True, routed_scale=2.5
    )
    np.testing.assert_allclose(np.asarray(c_s), np.asarray(combine) * 2.5, rtol=1e-5)


def test_tiny_deepseek_forward_shared_expert_and_generate():
    """The V3-shaped preset (MLA + sigmoid MoE + shared experts) runs end to
    end; the shared expert really contributes; the balance bias reroutes."""
    import jax
    import jax.numpy as jnp

    from prime_tpu.models import get_config
    from prime_tpu.models.llama import forward, init_params
    from prime_tpu.models.sampler import generate

    cfg = get_config("tiny-deepseek")
    params = init_params(jax.random.PRNGKey(0), cfg, jnp.float32)
    assert sum(x.size for x in jax.tree_util.tree_leaves(params)) == cfg.param_count
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 10), 1, cfg.vocab_size)
    logits, _ = forward(params, tokens, cfg)
    assert bool(jnp.all(jnp.isfinite(logits)))

    zeroed = dict(params)
    layers = dict(zeroed["layers"])
    layers["w_shared_down"] = jnp.zeros_like(layers["w_shared_down"])
    zeroed["layers"] = layers
    logits0, _ = forward(zeroed, tokens, cfg)
    assert float(jnp.max(jnp.abs(logits - logits0))) > 1e-3

    biased = dict(params)
    layers = dict(biased["layers"])
    layers["score_bias"] = layers["score_bias"].at[:, 0].add(100.0)
    biased["layers"] = layers
    logits_b, _ = forward(biased, tokens, cfg)
    assert float(jnp.max(jnp.abs(logits_b - logits))) > 1e-4

    out = generate(
        params, tokens, jnp.full((2,), 10, jnp.int32), cfg,
        jax.random.PRNGKey(2), max_new_tokens=4, temperature=0.0,
    )
    assert out.tokens.shape == (2, 4)


def test_tiny_deepseek_ep_sharded_train_step():
    """MLA + DeepSeekMoE over a dp/fsdp/ep/tp mesh: one train step, finite
    loss and grads (experts on ep, shared expert megatron-dense)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from jax.sharding import NamedSharding, PartitionSpec

    from prime_tpu.models import get_config
    from prime_tpu.models.llama import init_params
    from prime_tpu.parallel.mesh import make_mesh
    from prime_tpu.train import (
        default_optimizer,
        init_train_state,
        make_train_step,
        shard_train_state,
    )

    cfg = get_config("tiny-deepseek")
    mesh = make_mesh(
        {"dp": 1, "fsdp": 2, "ep": 2, "tp": 2}, devices=jax.devices()[:8]
    )
    opt = default_optimizer()
    state = shard_train_state(
        init_train_state(init_params(jax.random.PRNGKey(0), cfg, jnp.float32), opt),
        mesh, cfg,
    )
    step = make_train_step(cfg, opt)
    t = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, cfg.vocab_size)
    sharding = NamedSharding(mesh, PartitionSpec(("dp", "fsdp"), None))
    batch = tuple(
        jax.device_put(x, sharding)
        for x in (t, jnp.roll(t, -1, 1), jnp.ones_like(t, jnp.float32))
    )
    _state, metrics = step(state, *batch)
    assert np.isfinite(float(metrics["loss"]))


def test_negative_selection_bias_never_double_picks():
    """Regression: a balance bias driving every non-chosen score negative
    must not let the zeroed winner be argmax'd twice (exclusion is -inf)."""
    import jax.numpy as jnp
    import numpy as np

    from prime_tpu.ops.moe import top_k_routing

    logits = jnp.asarray([[1.0, -2.0, -2.5, -3.0]], jnp.float32)
    bias = jnp.asarray([0.0, -0.5, -0.6, -0.7], jnp.float32)
    dispatch, _c, _a = top_k_routing(
        logits, k=2, capacity=2, score_func="sigmoid", select_bias=bias
    )
    per_expert = np.asarray(dispatch).sum(-1)[0]  # how often each expert chosen
    assert per_expert.max() <= 1.0, per_expert    # no expert picked twice
    assert per_expert.sum() == 2.0                # two DISTINCT experts


def test_score_bias_survives_training_steps():
    """The selection-only bias has zero gradient; unmasked AdamW decay would
    erase it. The optimizer's decay mask must leave it untouched."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from prime_tpu.models import get_config
    from prime_tpu.models.llama import init_params
    from prime_tpu.train import default_optimizer, init_train_state, make_train_step

    cfg = get_config("tiny-deepseek")
    params = init_params(jax.random.PRNGKey(0), cfg, jnp.float32)
    params["layers"]["score_bias"] = params["layers"]["score_bias"] + 0.25
    opt = default_optimizer()
    state = init_train_state(params, opt)
    step = make_train_step(cfg, opt)
    t = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, cfg.vocab_size)
    for _ in range(3):
        state, _metrics = step(state, t, jnp.roll(t, -1, 1), jnp.ones_like(t, jnp.float32))
    np.testing.assert_allclose(
        np.asarray(state.params["layers"]["score_bias"]), 0.25, rtol=1e-6
    )


def test_tiny_deepseek_pipeline_train_step():
    """MLA + DeepSeekMoE staged over pp: specs cover the new keys and the
    stage forward routes through the MLA block."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from prime_tpu.models import get_config
    from prime_tpu.models.llama import init_params
    from prime_tpu.parallel.mesh import make_mesh
    from prime_tpu.parallel.pipeline import (
        make_pipeline_train_step,
        shard_pipeline_params,
    )
    from prime_tpu.train import default_optimizer, init_train_state

    cfg = get_config("tiny-deepseek")  # 2 layers -> 2 stages
    mesh = make_mesh({"pp": 2}, devices=jax.devices()[:2])
    opt = default_optimizer()
    params = shard_pipeline_params(init_params(jax.random.PRNGKey(0), cfg, jnp.float32), mesh, cfg)
    state = init_train_state(params, opt)
    step = make_pipeline_train_step(cfg, opt, mesh, n_microbatches=2)
    t = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, cfg.vocab_size)
    _state, metrics = step(state, t, jnp.roll(t, -1, 1), jnp.ones_like(t, jnp.float32))
    assert np.isfinite(float(metrics["loss"]))


# -- the inference layer's token group (moe_infer) ------------------------------


def _infer_inputs(tokens, d=32, ff=16, experts=8, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    normal = lambda i, *shape: jax.random.normal(keys[i], shape, jnp.float32)  # noqa: E731
    return (
        normal(0, 1, tokens, d), normal(1, d, experts) * 0.5, normal(2, experts, d, ff) * d**-0.5,
        normal(3, experts, d, ff) * d**-0.5, normal(4, experts, ff, d) * ff**-0.5,
    )


@pytest.mark.parametrize("tokens", [64, 256])
def test_a_chunk_as_one_token_group_is_the_same_tokens_in_four(tokens):
    """By default ``moe_infer`` takes a chunk through as ONE group (one read of
    every touched expert) wherever its row buffers fit; the same tokens in
    groups of a quarter give the same output and the same summed counts, but
    four times the calls, and so up to four times the experts touched."""
    from prime_tpu.ops.moe import MOE_STATS, moe_infer

    x, router, gate, up, down = _infer_inputs(tokens)
    mask = jnp.arange(tokens)[None, :] < tokens - 5  # a padded tail routes nowhere
    one, one_stats = moe_infer(x, router, gate[2:6], up[2:6], down[2:6], k=3, first_expert=2,
                               token_mask=mask)
    four, four_stats = moe_infer(x, router, gate[2:6], up[2:6], down[2:6], k=3, first_expert=2,
                                 token_mask=mask, group_size=tokens // 4)
    assert np.abs(np.asarray(one - four)).max() < 1e-5
    one_counts = dict(zip(MOE_STATS, np.asarray(one_stats).tolist()))
    four_counts = dict(zip(MOE_STATS, np.asarray(four_stats).tolist()))
    for name in ("local", "zero", "elsewhere"):
        assert one_counts[name] == four_counts[name]
    assert one_counts["local"] + one_counts["elsewhere"] == (tokens - 5) * 3
    assert (one_counts["calls"], four_counts["calls"]) == (1, 4)
    assert one_counts["experts_touched"] == 4 and four_counts["experts_touched"] == 16
    # a group's rows are padded to whole tiles once, not once a quarter
    assert one_counts["rows_computed"] <= four_counts["rows_computed"]


def test_the_token_group_comes_from_the_row_buffers_bytes():
    """``moe_infer``'s default group is the largest power of two of tokens whose
    sorted rows fit ``MOE_ROW_BUFFER_BYTES``: qwen3-next's 2048-token chunk (and
    a 4096-token one) is one group; LongCat's 2048-token wave of 4 x 512 stays
    two groups of 1024, as before; the training einsum keeps its own bound."""
    import inspect

    from prime_tpu.ops.moe import MOE_GROUP_SIZE, MOE_ROW_BUFFER_BYTES, infer_group_size, moe_infer

    qwen3_next, longcat = get_config("qwen3-next-ep2"), get_config("longcat-flash-ep32")
    assert infer_group_size(qwen3_next.experts_per_token, qwen3_next.d_model, 2) == 4096
    assert infer_group_size(longcat.experts_per_token, longcat.d_model, 2) == 1024
    assert 2048 * 10 * 2048 * 2 < MOE_ROW_BUFFER_BYTES < 2048 * 12 * 6144 * 2
    assert infer_group_size(512, 8192, 4) == 32  # never under a row tile of tokens
    assert inspect.signature(moe_infer).parameters["group_size"].default is None
    assert inspect.signature(moe_mlp).parameters["group_size"].default == MOE_GROUP_SIZE == 1024


# -- the grouped products' tile and moe_infer's row layout ----------------------

@pytest.mark.parametrize("product", ["gate_up", "down"])
@pytest.mark.parametrize("preset", ["laguna-s-ep2", "qwen3-next-ep2", "longcat-flash-ep32"])
def test_the_grouped_products_tile_holds_the_whole_contraction(preset, product):
    """``tk = k`` at every served shape: a group's consecutive row-tile visits
    keep their weight block, so an expert crosses HBM once. ``tn`` is a multiple
    of 128 that divides the width, as wide as two buffers of the tile allow."""
    from prime_tpu.ops.moe import GMM_WEIGHT_VMEM_BYTES, MOE_ROW_TILE, grouped_matmul_tiling

    config = get_config(preset)
    k, n = (config.d_model, config.d_ff)[:: 1 if product == "gate_up" else -1]
    tm, tk, tn = grouped_matmul_tiling(k, n, 2)
    assert (tm, tk) == (MOE_ROW_TILE, k)
    assert tn % 128 == 0 and n % tn == 0
    assert 2 * tk * tn * 2 <= GMM_WEIGHT_VMEM_BYTES
    wider = [w for w in range(tn + 128, n + 1, 128) if n % w == 0]
    assert all(2 * k * w * 2 > GMM_WEIGHT_VMEM_BYTES for w in wider)


@pytest.mark.parametrize(
    "k, n, itemsize, want",
    [(64, 48, 4, (32, 64, 48)),            # a width under a lane tile is taken whole
     (32768, 256, 2, (32, 24576, 128)),    # too long for the narrowest tile: walked
     (3072, 1024, 4, (32, 3072, 512))],    # float32 weights: half the width
    ids=["narrow", "contraction_walked", "float32"],
)
def test_the_tile_follows_the_shapes(k, n, itemsize, want):
    from prime_tpu.ops.moe import grouped_matmul_tiling

    assert grouped_matmul_tiling(k, n, itemsize) == want


@pytest.mark.parametrize(
    "preset, chunk, slots",
    [("laguna-s-ep2", 512, 32), ("qwen3-next-ep2", 2048, 128), ("longcat-flash-ep32", 1024, 64)],
)
def test_a_prompts_chunk_is_tile_aligned_and_a_decode_step_is_not(preset, chunk, slots):
    """From the shapes alone: a group of tokens that gives an expert half a tile
    of rows or more under even routing starts every group on a row tile; a
    decode step (every slot live) stays dense, LongCat's 768 rows over 16 held
    experts among them."""
    from prime_tpu.ops.moe import row_budget, tile_aligned_groups

    config = get_config(preset)
    k, held, width = config.experts_per_token, config.experts_held, config.router_width
    assert tile_aligned_groups(chunk, k, width) and not tile_aligned_groups(slots, k, width)
    # dropless either way: the pairs' worst case, and aligned a tile less a row an expert
    assert row_budget(slots, k, held, width) == -(-slots * min(k, held) // 32) * 32
    assert row_budget(chunk, k, held, width) == chunk * min(k, held) + held * 31 // 32 * 32


_GROUPS = {
    "an_empty_group": [5, 0, 30, 9],
    "one_row": [1, 0, 0, 1],
    "straddles_a_tile": [20, 20, 20, 4],
    "spans_three_tiles": [3, 90, 0, 3],
    "every_row_owned": [32, 32, 32, 32],
}


@pytest.mark.parametrize("stacked", [False, True], ids=["one_layer", "layer_stack"])
@pytest.mark.parametrize("groups", sorted(_GROUPS))
def test_grouped_matmul_at_the_new_tile_agrees_with_ragged_dot(groups, stacked, monkeypatch):
    """The kernel (interpret mode) with the contraction in one tile and the
    width in two, against ``jax.lax.ragged_dot``, over groups that are empty, of
    one row, that straddle a row tile and that span three."""
    from prime_tpu.ops import moe

    monkeypatch.setattr(moe, "_kernel_path", lambda: (True, True))
    monkeypatch.setattr(moe, "GMM_WEIGHT_VMEM_BYTES", 2 * 256 * 128 * 4)
    assert moe.grouped_matmul_tiling(256, 256, 4) == (32, 256, 128)
    rng = np.random.default_rng(7)
    sizes = jnp.asarray(_GROUPS[groups], jnp.int32)
    rows = jnp.asarray(rng.normal(size=(128, 256)), jnp.float32)
    weights = jnp.asarray(rng.normal(size=(3, 4, 256, 256)), jnp.float32) * 256**-0.5
    if stacked:
        out = moe.grouped_matmul(rows, weights, sizes, jnp.asarray(2, jnp.int32))
    else:
        out = moe.grouped_matmul(rows, weights[2], sizes)
    want = jax.lax.ragged_dot(rows, weights[2], sizes)
    used = int(sizes.sum())
    assert np.abs(np.asarray(out - want))[:used].max() < 1e-4


def _layout_case(shape):
    """(inputs of ``_infer_inputs``, k, held range) at a prompt-chunk shape (an
    expert gets a tile of rows under even routing) and at a decode shape (two)."""
    tokens, experts, k = {"prompt_chunk": (96, 12, 4), "decode_step": (8, 12, 3)}[shape]
    return _infer_inputs(tokens, d=64, ff=32, experts=experts, seed=3), k, (3, 9)


@pytest.mark.parametrize("kernel", [False, True], ids=["ragged_dot", "megablox_interpret"])
@pytest.mark.parametrize("layout", ["dense", "tile_aligned"])
@pytest.mark.parametrize("shape", ["prompt_chunk", "decode_step"])
def test_moe_infer_in_either_layout_matches_the_float32_reference(shape, layout, kernel, monkeypatch):
    """The same picks and the same output whether a group starts where the one
    before ended or on a row tile, on both product paths, against the plain
    float32 reference (every held expert over every token, gated)."""
    from benchmark.reference import longcat_flash as ref
    from prime_tpu.ops import moe

    monkeypatch.setattr(moe, "_kernel_path", lambda: (kernel, kernel))
    monkeypatch.setattr(moe, "tile_aligned_groups", lambda *_: layout == "tile_aligned")
    (x, router, gate, up, down), k, (first, last) = _layout_case(shape)
    held = (gate[first:last], up[first:last], down[first:last])
    out, stats = moe.moe_infer(x, router, *held, k=k, first_expert=first, norm_topk=False)
    want = ref.moe(x[0], router, jnp.zeros((router.shape[1],)), held, first, router.shape[1],
                   k, 1.0, lambda m: m)
    assert np.abs(np.asarray(out[0] - want)).max() < 1e-5
    assert np.abs(np.asarray(want)).max() > 0.1
    # the picks, by hand
    picks = np.asarray(jax.lax.top_k(jax.nn.softmax(x[0] @ router, axis=-1), k)[1])
    counts = dict(zip(moe.MOE_STATS, np.asarray(stats).tolist()))
    local = int(((picks >= first) & (picks < last)).sum())
    assert (counts["local"], counts["zero"], counts["elsewhere"]) == (local, 0, picks.size - local)


@pytest.mark.parametrize("tile_mib, one_tile_products", [(6.0, 3), (1 / 64, 1)],
                         ids=["contraction_in_one_tile", "gate_and_up_walked"])
@pytest.mark.parametrize("layout", ["dense", "tile_aligned"])
def test_weight_reads_against_a_count_by_hand(layout, tile_mib, one_tile_products, monkeypatch):
    """``weight_reads``: a product whose contraction is one tile fetches each
    touched expert once, one that walks it fetches the expert at every row-tile
    visit; ``rows_computed`` is the visits x 32 in the layout in use."""
    from prime_tpu.ops import moe

    tile = moe.MOE_ROW_TILE
    monkeypatch.setattr(moe, "tile_aligned_groups", lambda *_: layout == "tile_aligned")
    monkeypatch.setattr(moe, "GMM_WEIGHT_VMEM_BYTES", int(2 * tile_mib * 2**20))
    x, router, gate, up, down = _infer_inputs(96, d=256, ff=128, experts=12, seed=5)
    # float32 (256, 128): 16 KiB a buffer holds gate / up's contraction in two
    # tiles and down's (128, 128) in one
    walked = [moe.grouped_matmul_tiling(a, b, 4)[1] < a for a, b in ((256, 128), (128, 256))]
    assert walked == ([False, False] if one_tile_products == 3 else [True, False])
    _, stats = moe.moe_infer(x, router, gate[3:9], up[3:9], down[3:9], k=4, first_expert=3)
    counts = dict(zip(moe.MOE_STATS, np.asarray(stats).tolist()))
    picks = np.asarray(jax.lax.top_k(jax.nn.softmax(x[0] @ router, axis=-1), 4)[1])
    sizes = np.bincount(picks[(picks >= 3) & (picks < 9)] - 3, minlength=6)
    if layout == "tile_aligned":
        visits = int(np.sum(-(-sizes // tile)))
    else:
        ends = np.cumsum(sizes)
        starts = ends - sizes
        visits = int(np.sum(np.where(sizes > 0, -(-ends // tile) - starts // tile, 0)))
    touched = int((sizes > 0).sum())
    assert counts["experts_touched"] == touched and counts["rows_computed"] == visits * tile
    assert visits > touched  # some group takes a second tile in either layout
    assert counts["weight_reads"] == one_tile_products * touched + (3 - one_tile_products) * visits


@pytest.mark.parametrize(
    "reads, touched, want",
    [(3 * 4864, 4864, 1.0),                       # every product in one tile
     (2 * 7540 + 4864, 4864, (2 * 7540 + 4864) / (3 * 4864)),  # gate and up fetched at 1.55 visits
     (None, 4864, None),                          # the parent: no such counter
     (0, 0, None)],                               # a window with no prefill
    ids=["once", "gate_and_up_walked", "no_counter", "no_prefill"],
)
def test_the_weight_reads_reader_divides_the_windows_deltas(reads, touched, want):
    """``moe_prefill_weight_reads_per_touched_expert`` from two registry
    snapshots, prefill phase only; nothing where the program has no counter."""
    from benchmark.harness.manifest import load_manifest
    from benchmark.layer_metrics import moe_prefill_weight_reads_per_touched_expert as reader
    from benchmark.systems.serve_http import counter_delta

    def family(prefill, decode):
        return {"type": "counter", "series": [
            {"labels": {"phase": "prefill"}, "value": prefill},
            {"labels": {"phase": "decode"}, "value": decode}]}

    def snapshot(scale):
        counters = {"serve_moe_experts_touched_total": family(scale * touched, 1000 * scale)}
        if reads is not None:
            counters["serve_moe_weight_reads_total"] = family(scale * reads, 4000 * scale)
        return counters

    value = reader.read({"counters": (snapshot(1), snapshot(3)), "counter_delta": counter_delta})
    assert value == want if want is None else abs(value - want) < 1e-12
    entry = {m["name"]: m for m in load_manifest()["per_layer"]}[reader.__name__.rsplit(".", 1)[1]]
    assert (entry["moves"], entry["better"], entry["source"]) == ("latency_mean_ms", "lower", "program_counter")
