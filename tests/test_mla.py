"""Multi-head latent attention (DeepSeek-style MLA, models/mla.py).

The load-bearing contracts:
- the ABSORBED formulation (what serves) equals the textbook per-head
  reconstruction (the oracle) to fp32 noise;
- prefill+decode through the latent cache equals the dense no-cache forward
  at the same positions;
- the engine decodes exactly the one-shot sampler's tokens (slot splicing,
  chunked prefill, and continuous decode all ride the latent cache);
- the cache really is latent-compressed, and kv_quant is rejected loudly.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from prime_tpu.models import get_config
from prime_tpu.models.llama import forward, init_cache, init_params
from prime_tpu.models.sampler import generate


CFG = get_config("tiny-mla")
PARAMS = init_params(jax.random.PRNGKey(0), CFG, dtype=jnp.float32)


@pytest.fixture(scope="module")
def tokens():
    return jax.random.randint(jax.random.PRNGKey(1), (2, 12), 1, CFG.vocab_size)


@pytest.mark.parametrize("preset", ["tiny-mla", "tiny-mla-qlora"])
def test_absorbed_equals_naive_oracle(preset):
    """q_nope @ W_kc . c_kv == q_nope . (W_kc @ c_kv): the absorption is a
    reassociation, so the two formulations agree to fp32 noise."""
    from prime_tpu.models.mla import mla_attention_block, naive_mla_attention
    from prime_tpu.ops.rope import rope_frequencies

    config = get_config(preset)
    params = init_params(jax.random.PRNGKey(0), config, dtype=jnp.float32)
    lp = {k: v[0] for k, v in params["layers"].items()}
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 12, config.d_model)) * 0.1
    positions = jnp.broadcast_to(jnp.arange(12)[None], (2, 12))
    tables = rope_frequencies(config.qk_rope_head_dim, 64, config.rope_theta)
    absorbed, *_ = mla_attention_block(
        x, lp, positions, tables, config, (), None, None, False, "xla"
    )
    naive = naive_mla_attention(x, lp, positions, tables, config)
    assert float(jnp.max(jnp.abs(absorbed - naive))) < 1e-5


def test_prefill_decode_matches_dense(tokens):
    dense_logits, _ = forward(PARAMS, tokens, CFG)
    cache = init_cache(CFG, 2, 16, dtype=jnp.float32)
    _, cache = forward(PARAMS, tokens[:, :11], CFG, cache=cache)
    step_logits, cache = forward(
        PARAMS, tokens[:, 11:12], CFG, cache=cache, decode=True,
        positions=jnp.full((2, 1), 11, jnp.int32),
    )
    assert float(jnp.max(jnp.abs(step_logits[:, 0] - dense_logits[:, 11]))) < 1e-4
    assert cache.lengths.tolist() == [12, 12]


def test_chunked_prefill_matches_one_shot(tokens):
    """Chunked prefill writes latent columns at the offset and attends over
    the cache — logits for the final chunk must match one-shot prefill."""
    cache_ref = init_cache(CFG, 2, 16, dtype=jnp.float32)
    ref_logits, cache_ref = forward(PARAMS, tokens, CFG, cache=cache_ref)

    cache = init_cache(CFG, 2, 16, dtype=jnp.float32)
    _, cache = forward(PARAMS, tokens[:, :8], CFG, cache=cache)
    chunk_logits, cache = forward(
        PARAMS, tokens[:, 8:], CFG, cache=cache,
        prefill_offset=jnp.asarray(8, jnp.int32),
    )
    assert float(jnp.max(jnp.abs(chunk_logits - ref_logits[:, 8:]))) < 1e-4
    assert float(jnp.max(jnp.abs(cache.k - cache_ref.k))) < 1e-5


def test_generate_greedy_deterministic(tokens):
    lengths = jnp.full((2,), 12, jnp.int32)
    a = generate(PARAMS, tokens, lengths, CFG, jax.random.PRNGKey(3), max_new_tokens=6, temperature=0.0)
    b = generate(PARAMS, tokens, lengths, CFG, jax.random.PRNGKey(9), max_new_tokens=6, temperature=0.0)
    assert a.tokens.tolist() == b.tokens.tolist()  # greedy ignores the rng


def test_engine_matches_one_shot_sampler():
    from prime_tpu.serve.engine import ContinuousBatchingEngine

    prompt = [9, 8, 7, 6, 5]
    ref = generate(
        PARAMS, jnp.asarray([prompt], jnp.int32), jnp.asarray([5], jnp.int32),
        CFG, jax.random.PRNGKey(7), max_new_tokens=6, temperature=0.0,
    ).tokens[0].tolist()
    engine = ContinuousBatchingEngine(PARAMS, CFG, max_slots=2, capacity=64, chunk=4)
    reqs = [engine.submit(prompt, max_new_tokens=6), engine.submit([3, 2], max_new_tokens=6)]
    while not all(r.done for r in reqs):
        engine.tick()
    assert reqs[0].all_tokens(timeout=1) == ref


def test_sharded_generate_tp_fsdp(tokens):
    """MLA under the serving mesh: query heads on tp, latent cache head axis
    replicated (cache_spec_for); decoded tokens match the single-device run."""
    from jax.sharding import NamedSharding

    from prime_tpu.parallel.mesh import make_mesh
    from prime_tpu.parallel.sharding import (
        batch_spec,
        cache_spec_for,
        lengths_spec,
        prune_spec,
        shard_params,
    )

    lengths = jnp.full((2,), 12, jnp.int32)
    ref = generate(
        PARAMS, tokens, lengths, CFG, jax.random.PRNGKey(5), max_new_tokens=4,
        temperature=0.0,
    ).tokens.tolist()
    mesh = make_mesh({"dp": 1, "fsdp": 2, "tp": 2}, devices=jax.devices()[:4])
    sharded = shard_params(PARAMS, mesh, CFG)
    with jax.set_mesh(mesh):
        out = generate(
            sharded,
            jax.device_put(tokens, NamedSharding(mesh, batch_spec())),
            jax.device_put(lengths, NamedSharding(mesh, lengths_spec())),
            CFG, jax.random.PRNGKey(5), max_new_tokens=4, temperature=0.0,
            attn_impl="xla", cache_spec=prune_spec(cache_spec_for(CFG), mesh),
        )
    assert out.tokens.tolist() == ref


def test_train_step_finite_grads():
    from prime_tpu.parallel.mesh import make_mesh
    from prime_tpu.parallel.sharding import shard_batch
    from prime_tpu.train import (
        default_optimizer,
        init_train_state,
        make_train_step,
        shard_train_state,
    )

    mesh = make_mesh({"dp": 1, "fsdp": 2, "tp": 2}, devices=jax.devices()[:4])
    opt = default_optimizer()
    state = shard_train_state(
        init_train_state(init_params(jax.random.PRNGKey(3), CFG, jnp.float32), opt),
        mesh, CFG,
    )
    step = make_train_step(CFG, opt)
    t = jax.random.randint(jax.random.PRNGKey(4), (4, 16), 0, CFG.vocab_size)
    batch = tuple(
        shard_batch(x, mesh) for x in (t, jnp.roll(t, -1, 1), jnp.ones_like(t, jnp.float32))
    )
    _state, metrics = step(state, *batch)
    assert np.isfinite(float(metrics["loss"]))
    assert np.isfinite(float(metrics["grad_norm"]))


def test_cache_is_latent_compressed_and_kv_quant_rejected():
    cache = init_cache(CFG, 2, 64, dtype=jnp.float32)
    # joint latent column: rank + rope wide, ONE head; dummy v is 1-wide
    assert cache.k.shape == (CFG.n_layers, 2, 1, CFG.mla_cache_dim, 64)
    assert cache.v.shape == (CFG.n_layers, 2, 1, 1, 64)
    mha_bytes = CFG.n_layers * 2 * 2 * CFG.n_heads * (
        CFG.qk_nope_head_dim + CFG.qk_rope_head_dim
    ) * 64 * 4
    assert cache.k.nbytes + cache.v.nbytes < 0.2 * mha_bytes
    with pytest.raises(ValueError, match="kv_quant"):
        init_cache(CFG, 2, 64, quantized=True)
    with pytest.raises(ValueError, match="kv_quant"):
        generate(
            PARAMS, jnp.asarray([[1, 2]], jnp.int32), jnp.asarray([2], jnp.int32),
            CFG, jax.random.PRNGKey(0), max_new_tokens=2, kv_quant=True,
        )


def test_ring_rejected_for_mla(tokens):
    from prime_tpu.parallel.mesh import make_mesh

    mesh = make_mesh({"sp": 2}, devices=jax.devices()[:2])
    with pytest.raises(ValueError, match="ring"):
        forward(PARAMS, tokens, CFG, attn_impl="ring", mesh=mesh)


def test_param_count_matches_tree():
    leaves = jax.tree_util.tree_leaves(PARAMS)
    assert sum(x.size for x in leaves) == CFG.param_count
    qcfg = get_config("tiny-mla-qlora")
    qparams = init_params(jax.random.PRNGKey(0), qcfg, dtype=jnp.float32)
    assert sum(x.size for x in jax.tree_util.tree_leaves(qparams)) == qcfg.param_count


@pytest.mark.parametrize("preset", ["tiny-mla", "tiny-mla-qlora"])
def test_int8_weights_mla(preset):
    """int8 quantization covers every MLA projection (wkv_b's scales fold
    into the absorb/value einsums exactly) and generate still runs."""
    from prime_tpu.models.quantize import is_quantized, quantize_params_int8

    config = get_config(preset)
    params = init_params(jax.random.PRNGKey(0), config, dtype=jnp.float32)
    qparams = quantize_params_int8(params)
    assert is_quantized(qparams)
    assert isinstance(qparams["layers"]["wkv_b"], tuple)
    assert isinstance(qparams["layers"]["wkv_a"], tuple)

    tokens = jax.random.randint(jax.random.PRNGKey(7), (2, 10), 0, config.vocab_size)
    fp_logits, _ = forward(params, tokens, config)
    q_logits, _ = forward(qparams, tokens, config)
    fp_probs = np.asarray(jax.nn.softmax(fp_logits, axis=-1))
    q_probs = np.asarray(jax.nn.softmax(q_logits, axis=-1))
    assert np.abs(fp_probs - q_probs).max() < 0.06

    # scale folding is EXACT vs explicitly dequantized weights
    dequant = dict(params)
    layers = dict(qparams["layers"])
    for key, value in layers.items():
        if isinstance(value, tuple):
            layers[key] = (value[0].astype(jnp.float32) * value[1]).astype(jnp.float32)
    dequant["layers"] = layers
    d_logits, _ = forward(dequant, tokens, config)
    assert np.abs(np.asarray(q_logits) - np.asarray(d_logits)).max() < 1e-3

    out = generate(
        qparams, tokens, jnp.full((2,), 10, jnp.int32), config,
        jax.random.PRNGKey(8), max_new_tokens=4, temperature=0.0,
    )
    assert out.tokens.shape == (2, 4)


def test_int4_weights_mla_skips_wkv_b():
    """int4's reduction-axis group scales can't fold through the absorb
    einsum; wkv_b stays for the int8 pass, everything else goes int4."""
    from prime_tpu.models.quantize import quantize_params_int4, quantize_params_int8

    params = init_params(jax.random.PRNGKey(0), CFG, dtype=jnp.float32)
    q4 = quantize_params_int8(quantize_params_int4(params))
    assert str(q4["layers"]["wq"][0].dtype) == "uint8"
    assert str(q4["layers"]["wkv_b"][0].dtype) == "int8"
    tokens = jax.random.randint(jax.random.PRNGKey(7), (2, 8), 0, CFG.vocab_size)
    logits, _ = forward(q4, tokens, CFG)
    assert bool(jnp.all(jnp.isfinite(logits)))


def test_unsupported_attention_features_rejected():
    """Per-head attention features have no latent-form equivalent: loud
    error, not silently different numerics."""
    bad = CFG.scaled(sliding_window=64, sliding_pattern="uniform")
    params = init_params(jax.random.PRNGKey(0), bad, dtype=jnp.float32)
    tokens = jnp.asarray([[1, 2, 3]], jnp.int32)
    with pytest.raises(ValueError, match="sliding_window"):
        forward(params, tokens, bad)
    with pytest.raises(ValueError, match="attn_softcap"):
        forward(params, tokens, CFG.scaled(attn_softcap=50.0))


# -- HF DeepSeek-V3 parity ----------------------------------------------------


@pytest.fixture(scope="module")
def deepseek_model():
    import torch
    import transformers

    cfg = transformers.DeepseekV3Config(
        vocab_size=256,
        hidden_size=64,
        intermediate_size=128,
        moe_intermediate_size=48,
        num_hidden_layers=2,
        num_attention_heads=4,
        num_key_value_heads=4,
        kv_lora_rank=32,
        q_lora_rank=48,
        qk_rope_head_dim=16,
        qk_nope_head_dim=32,
        v_head_dim=32,
        n_routed_experts=8,
        num_experts_per_tok=2,
        n_shared_experts=1,
        n_group=1,
        topk_group=1,
        first_k_dense_replace=0,
        routed_scaling_factor=2.5,
        norm_topk_prob=True,
        max_position_embeddings=128,
        rope_theta=10000.0,
        rope_scaling=None,
        rms_norm_eps=1e-6,
        tie_word_embeddings=False,
        attn_implementation="eager",
    )
    torch.manual_seed(31)
    model = transformers.DeepseekV3ForCausalLM(cfg)
    model.eval()
    return model


def test_deepseek_v3_logits_match_transformers(deepseek_model):
    """The full V3 stack at once — MLA (low-rank q, interleaved rope
    de-interleaved at load), sigmoid routing with the e_score bias, routed
    scaling, shared expert — pinned against transformers' reference."""
    import torch

    from prime_tpu.models.hf_loader import config_from_hf, params_from_state_dict

    state = {k: v.float().numpy() for k, v in deepseek_model.state_dict().items()}
    config = config_from_hf(deepseek_model.config, name="tiny-ds-hf")
    assert config.mla and config.moe_score_func == "sigmoid"
    assert config.n_shared_experts == 1 and config.routed_scaling_factor == 2.5
    params = params_from_state_dict(
        state, config, dtype=jnp.float32,
        rope_interleave=bool(getattr(deepseek_model.config, "rope_interleave", False)),
    )
    tokens = np.array([[3, 17, 200, 45, 9, 88, 121, 7]], dtype=np.int32)
    with torch.no_grad():
        hf_logits = deepseek_model(torch.tensor(tokens, dtype=torch.long)).logits.numpy()
    ours, _ = forward(params, jnp.asarray(tokens), config)
    np.testing.assert_allclose(np.asarray(ours), hf_logits, rtol=5e-4, atol=5e-4)


def test_deepseek_v3_greedy_matches_transformers(deepseek_model):
    import torch

    from prime_tpu.models.hf_loader import config_from_hf, params_from_state_dict

    state = {k: v.float().numpy() for k, v in deepseek_model.state_dict().items()}
    config = config_from_hf(deepseek_model.config, name="tiny-ds-hf")
    params = params_from_state_dict(
        state, config, dtype=jnp.float32,
        rope_interleave=bool(getattr(deepseek_model.config, "rope_interleave", False)),
    )
    prompt = np.array([[5, 42, 100, 7]], dtype=np.int32)
    with torch.no_grad():
        hf_out = deepseek_model.generate(
            torch.tensor(prompt, dtype=torch.long),
            max_new_tokens=8, do_sample=False, eos_token_id=None, pad_token_id=0,
        ).numpy()[0, 4:]
    ours = generate(
        params, jnp.asarray(prompt), jnp.asarray([4], jnp.int32), config,
        jax.random.PRNGKey(0), max_new_tokens=8, temperature=0.0,
    ).tokens[0]
    assert np.asarray(ours).tolist() == hf_out.tolist()


def test_deepseek_v3_unmodeled_features_rejected():
    from prime_tpu.models.hf_loader import config_from_hf

    class Cfg:
        model_type = "deepseek_v3"
        vocab_size = 256
        hidden_size = 64
        intermediate_size = 128
        num_hidden_layers = 2
        num_attention_heads = 4
        kv_lora_rank = 32
        q_lora_rank = None
        qk_rope_head_dim = 16
        qk_nope_head_dim = 32
        v_head_dim = 32
        n_routed_experts = 8
        first_k_dense_replace = 0
        n_group = 1
        rope_scaling = None

    ok = config_from_hf(Cfg())
    assert ok.mla and ok.q_lora_rank is None

    prefixed = Cfg()
    prefixed.first_k_dense_replace = 2
    assert config_from_hf(prefixed).first_k_dense == 2  # modeled since round 5

    grouped = Cfg()
    grouped.n_group = 4
    grouped.topk_group = 2
    cfg = config_from_hf(grouped)
    assert cfg.moe_n_groups == 4 and cfg.moe_topk_groups == 2  # modeled since r5

    scaled = Cfg()
    scaled.rope_scaling = {"type": "linear", "factor": 4}
    with pytest.raises(ValueError, match="rope_scaling"):
        config_from_hf(scaled)  # only yarn is the published DeepSeek scheme


@pytest.fixture(scope="module")
def deepseek_prefix_model():
    """first_k_dense_replace=1: layer 0 is a dense MLP, layers 1-2 are MoE
    (the real V2-Lite/V3 structure the two-scan forward exists for)."""
    import torch
    import transformers

    cfg = transformers.DeepseekV3Config(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        moe_intermediate_size=48, num_hidden_layers=3, num_attention_heads=4,
        num_key_value_heads=4, kv_lora_rank=32, q_lora_rank=48,
        qk_rope_head_dim=16, qk_nope_head_dim=32, v_head_dim=32,
        n_routed_experts=8, num_experts_per_tok=2, n_shared_experts=1,
        n_group=1, topk_group=1, first_k_dense_replace=1,
        routed_scaling_factor=2.5, norm_topk_prob=True,
        max_position_embeddings=128, rope_theta=10000.0, rope_scaling=None,
        rms_norm_eps=1e-6, tie_word_embeddings=False,
        attn_implementation="eager",
    )
    torch.manual_seed(33)
    model = transformers.DeepseekV3ForCausalLM(cfg)
    model.eval()
    return model


def _load_prefix(model, dtype=jnp.float32):
    from prime_tpu.models.hf_loader import config_from_hf, params_from_state_dict

    state = {k: v.float().numpy() for k, v in model.state_dict().items()}
    config = config_from_hf(model.config, name="ds-prefix")
    params = params_from_state_dict(state, config, dtype=dtype, rope_interleave=True)
    return params, config


def test_deepseek_dense_prefix_logits_match_transformers(deepseek_prefix_model):
    import torch

    params, config = _load_prefix(deepseek_prefix_model)
    assert config.first_k_dense == 1 and config.dense_ff == 128
    assert "dense_layers" in params and "router" not in params["dense_layers"]
    assert params["layers"]["router"].shape[0] == 2  # MoE tail only
    tokens = np.array([[3, 17, 200, 45, 9, 88, 121, 7]], dtype=np.int32)
    with torch.no_grad():
        hf = deepseek_prefix_model(torch.tensor(tokens, dtype=torch.long)).logits.numpy()
    ours, _ = forward(params, jnp.asarray(tokens), config)
    np.testing.assert_allclose(np.asarray(ours), hf, rtol=5e-4, atol=5e-4)


def test_deepseek_dense_prefix_greedy_and_engine(deepseek_prefix_model):
    """Greedy decode matches transformers through the two-scan cache, and
    the continuous engine serves the model (cache split/join per tick)."""
    import torch

    from prime_tpu.serve.engine import ContinuousBatchingEngine

    params, config = _load_prefix(deepseek_prefix_model)
    prompt = np.array([[5, 42, 100, 7]], dtype=np.int32)
    with torch.no_grad():
        hf_out = deepseek_prefix_model.generate(
            torch.tensor(prompt, dtype=torch.long), max_new_tokens=8,
            do_sample=False, eos_token_id=None, pad_token_id=0,
        ).numpy()[0, 4:]
    ours = generate(
        params, jnp.asarray(prompt), jnp.asarray([4], jnp.int32), config,
        jax.random.PRNGKey(0), max_new_tokens=8, temperature=0.0,
    ).tokens[0]
    assert np.asarray(ours).tolist() == hf_out.tolist()

    engine = ContinuousBatchingEngine(params, config, max_slots=2, capacity=64, chunk=4)
    request = engine.submit([5, 42, 100, 7], max_new_tokens=8)
    while not request.done:
        engine.tick()
    assert request.all_tokens(timeout=1) == hf_out.tolist()


def test_deepseek_dense_prefix_trains_and_quantizes(deepseek_prefix_model):
    from prime_tpu.models.quantize import quantize_params_int8
    from prime_tpu.train import default_optimizer, init_train_state, make_train_step

    params, config = _load_prefix(deepseek_prefix_model)
    # quantized forward FIRST: the jitted train step donates its buffers,
    # deleting every array the q8 tree shares by reference (embed, norms)
    q8 = quantize_params_int8(params)
    assert isinstance(q8["dense_layers"]["w_gate"], tuple)  # prefix quantized too
    tokens = jnp.asarray([[3, 17, 200, 45]], jnp.int32)
    logits, _ = forward(q8, tokens, config)
    assert bool(jnp.all(jnp.isfinite(logits)))

    opt = default_optimizer()
    state = init_train_state(params, opt)
    step = make_train_step(config, opt)
    t = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, config.vocab_size)
    _state, metrics = step(state, t, jnp.roll(t, -1, 1), jnp.ones_like(t, jnp.float32))
    assert np.isfinite(float(metrics["loss"]))


def test_deepseek_v3_group_routing_matches_transformers():
    """n_group=2/topk_group=1: group-limited selection (HF's 0.0-mask quirk
    included) pinned against transformers logits + greedy."""
    import torch
    import transformers

    from prime_tpu.models.hf_loader import config_from_hf, params_from_state_dict

    cfg = transformers.DeepseekV3Config(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        moe_intermediate_size=48, num_hidden_layers=2, num_attention_heads=4,
        num_key_value_heads=4, kv_lora_rank=32, q_lora_rank=None,
        qk_rope_head_dim=16, qk_nope_head_dim=32, v_head_dim=32,
        n_routed_experts=8, num_experts_per_tok=2, n_shared_experts=1,
        n_group=2, topk_group=1, first_k_dense_replace=0,
        routed_scaling_factor=2.5, norm_topk_prob=True,
        max_position_embeddings=128, rope_theta=10000.0, rope_scaling=None,
        rms_norm_eps=1e-6, tie_word_embeddings=False,
        attn_implementation="eager",
    )
    torch.manual_seed(35)
    model = transformers.DeepseekV3ForCausalLM(cfg)
    model.eval()
    state = {k: v.float().numpy() for k, v in model.state_dict().items()}
    config = config_from_hf(model.config, name="ds-groups")
    assert config.moe_n_groups == 2 and config.moe_topk_groups == 1
    params = params_from_state_dict(state, config, dtype=jnp.float32, rope_interleave=True)
    tokens = np.array([[3, 17, 200, 45, 9, 88, 121, 7]], dtype=np.int32)
    with torch.no_grad():
        hf = model(torch.tensor(tokens, dtype=torch.long)).logits.numpy()
    ours, _ = forward(params, jnp.asarray(tokens), config)
    np.testing.assert_allclose(np.asarray(ours), hf, rtol=5e-4, atol=5e-4)
    prompt = np.array([[5, 42, 100, 7]], dtype=np.int32)
    with torch.no_grad():
        hf_out = model.generate(
            torch.tensor(prompt, dtype=torch.long), max_new_tokens=6,
            do_sample=False, eos_token_id=None, pad_token_id=0,
        ).numpy()[0, 4:]
    ours_gen = generate(
        params, jnp.asarray(prompt), jnp.asarray([4], jnp.int32), config,
        jax.random.PRNGKey(0), max_new_tokens=6, temperature=0.0,
    ).tokens[0]
    assert np.asarray(ours_gen).tolist() == hf_out.tolist()


def test_deepseek_v3_yarn_matches_transformers():
    """DeepSeek-yarn long-context: NTK-by-parts tables over the rope
    sub-head plus mscale_all_dim^2 on the softmax scale, pinned against
    transformers logits + greedy (the real V2/V3 checkpoints' scheme)."""
    import torch
    import transformers

    from prime_tpu.models.hf_loader import config_from_hf, params_from_state_dict

    cfg = transformers.DeepseekV3Config(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        moe_intermediate_size=48, num_hidden_layers=2, num_attention_heads=4,
        num_key_value_heads=4, kv_lora_rank=32, q_lora_rank=48,
        qk_rope_head_dim=16, qk_nope_head_dim=32, v_head_dim=32,
        n_routed_experts=8, num_experts_per_tok=2, n_shared_experts=1,
        n_group=1, topk_group=1, first_k_dense_replace=0,
        routed_scaling_factor=2.5, norm_topk_prob=True,
        max_position_embeddings=128, rope_theta=10000.0,
        rope_scaling={
            "rope_type": "yarn", "factor": 4.0, "beta_fast": 32,
            "beta_slow": 1, "mscale": 1.0, "mscale_all_dim": 1.0,
            "original_max_position_embeddings": 32,
        },
        rms_norm_eps=1e-6, tie_word_embeddings=False,
        attn_implementation="eager",
    )
    torch.manual_seed(37)
    model = transformers.DeepseekV3ForCausalLM(cfg)
    model.eval()
    state = {k: v.float().numpy() for k, v in model.state_dict().items()}
    config = config_from_hf(model.config, name="ds-yarn")
    assert config.rope_yarn is not None
    assert config.attn_scale_mult > 1.0  # mscale_all_dim=1, factor=4 -> >1
    # no-drop capacity (E/k), as the serving path sets it: at 48 tokens the
    # default 2.0 headroom drops tokens that HF's dropless routing serves,
    # which would mask whether the YARN math matches
    config = config.scaled(capacity_factor=config.n_experts / config.experts_per_token)
    params = params_from_state_dict(state, config, dtype=jnp.float32, rope_interleave=True)
    tokens = np.array([[3, 17, 200, 45, 9, 88, 121, 7] * 6], dtype=np.int32)  # past orig range
    with torch.no_grad():
        hf = model(torch.tensor(tokens, dtype=torch.long)).logits.numpy()
    ours, _ = forward(params, jnp.asarray(tokens), config)
    np.testing.assert_allclose(np.asarray(ours), hf, rtol=5e-4, atol=5e-4)
    prompt = np.array([[5, 42, 100, 7]], dtype=np.int32)
    with torch.no_grad():
        hf_out = model.generate(
            torch.tensor(prompt, dtype=torch.long), max_new_tokens=6,
            do_sample=False, eos_token_id=None, pad_token_id=0,
        ).numpy()[0, 4:]
    ours_gen = generate(
        params, jnp.asarray(prompt), jnp.asarray([4], jnp.int32), config,
        jax.random.PRNGKey(0), max_new_tokens=6, temperature=0.0,
    ).tokens[0]
    assert np.asarray(ours_gen).tolist() == hf_out.tolist()


def test_deepseek_v2_lite_preset_shapes_without_materializing():
    """The published V2-Lite architecture (15.7B, 64 experts + 2 shared, one
    dense-prefix layer) structurally checks out via eval_shape — no 15.7B
    materialization, just the traced param tree and the cache footprint."""
    config = get_config("deepseek-v2-lite")
    assert config.mla and config.first_k_dense == 1 and config.n_experts == 64
    assert config.param_count == pytest.approx(15.7e9, rel=0.02)

    shapes = jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), config, dtype=jnp.bfloat16)
    )
    total = sum(
        int(np.prod(leaf.shape)) for leaf in jax.tree_util.tree_leaves(shapes)
    )
    assert total == config.param_count
    assert shapes["dense_layers"]["w_gate"].shape == (1, 2048, 10944)
    assert shapes["layers"]["w_gate"].shape == (26, 64, 2048, 1408)
    assert shapes["layers"]["w_shared_gate"].shape == (26, 2048, 2 * 1408)
    assert shapes["layers"]["wkv_b"].shape == (26, 512, 16 * (128 + 128))

    # latent cache: 576 * 2 bytes/token/layer -> a 32k-token sequence fits
    # in ~1 GiB of cache vs ~10.7 GiB for per-head K (nope+rope) + V (v_dim)
    cache_shapes = jax.eval_shape(
        lambda: init_cache(config, 1, 32768, dtype=jnp.bfloat16)
    )
    latent_bytes = int(np.prod(cache_shapes.k.shape)) * 2
    full_kv_bytes = 27 * 16 * ((128 + 64) + 128) * 32768 * 2
    assert latent_bytes < 0.12 * full_kv_bytes
