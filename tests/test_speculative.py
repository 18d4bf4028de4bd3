"""Prompt-lookup speculative decoding: exact parity with plain greedy decode.

The whole point of greedy speculation is that it changes WHEN tokens are
computed, never WHICH tokens — so every test pins spec_generate's buffer,
lengths, and padding against sampler.generate token-for-token.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from prime_tpu.models import get_config
from prime_tpu.models.llama import init_params
from prime_tpu.models.sampler import generate
from prime_tpu.models.speculative import propose_ngram_drafts, spec_generate


CFG = get_config("tiny-test")


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.PRNGKey(0), CFG, dtype=jnp.float32)


def ref_and_spec(params, tokens, lengths, max_new, eos_id=-1, draft_len=4):
    ref = generate(
        params, tokens, lengths, CFG, jax.random.PRNGKey(1),
        max_new_tokens=max_new, temperature=0.0, eos_id=eos_id, pad_id=0,
        attn_impl="xla",
    )
    out = spec_generate(
        params, tokens, lengths, CFG,
        max_new_tokens=max_new, draft_len=draft_len, eos_id=eos_id, pad_id=0,
        attn_impl="xla",
    )
    return ref, out


def test_spec_matches_greedy_random_prompts(params):
    """Arbitrary prompts (drafts mostly rejected): emitted tokens identical."""
    tokens = jax.random.randint(jax.random.PRNGKey(2), (4, 12), 1, CFG.vocab_size)
    lengths = jnp.asarray([12, 7, 9, 12], dtype=jnp.int32)
    ref, out = ref_and_spec(params, tokens, lengths, max_new=16)
    np.testing.assert_array_equal(np.asarray(out.tokens), np.asarray(ref.tokens))
    np.testing.assert_array_equal(np.asarray(out.lengths), np.asarray(ref.lengths))


def test_spec_matches_greedy_repetitive_prompts(params):
    """Highly periodic prompts (drafts mostly ACCEPTED): still identical."""
    period = jnp.asarray([5, 9, 13, 17], dtype=jnp.int32)
    tokens = jnp.tile(period, (2, 6))  # (2, 24) period-4 repetition
    lengths = jnp.asarray([24, 21], dtype=jnp.int32)
    ref, out = ref_and_spec(params, tokens, lengths, max_new=24, draft_len=6)
    np.testing.assert_array_equal(np.asarray(out.tokens), np.asarray(ref.tokens))
    np.testing.assert_array_equal(np.asarray(out.lengths), np.asarray(ref.lengths))


def test_spec_matches_greedy_with_eos(params):
    """EOS placement, post-EOS padding, and lengths all match generate.
    Every vocab id is tried as EOS until one actually fires mid-stream."""
    tokens = jax.random.randint(jax.random.PRNGKey(3), (4, 10), 1, CFG.vocab_size)
    lengths = jnp.asarray([10, 10, 6, 8], dtype=jnp.int32)
    ref_free = generate(
        params, tokens, lengths, CFG, jax.random.PRNGKey(1),
        max_new_tokens=12, temperature=0.0, eos_id=-1, pad_id=0, attn_impl="xla",
    )
    # pick an EOS id that genuinely appears in the free-running output
    flat = np.asarray(ref_free.tokens).ravel()
    eos_id = int(flat[len(flat) // 2])
    ref, out = ref_and_spec(params, tokens, lengths, max_new=12, eos_id=eos_id)
    np.testing.assert_array_equal(np.asarray(out.tokens), np.asarray(ref.tokens))
    np.testing.assert_array_equal(np.asarray(out.lengths), np.asarray(ref.lengths))


def test_spec_draft_len_invariance(params):
    """The draft budget is a performance knob, never a correctness knob."""
    tokens = jax.random.randint(jax.random.PRNGKey(4), (2, 8), 1, CFG.vocab_size)
    lengths = jnp.asarray([8, 5], dtype=jnp.int32)
    outs = [
        np.asarray(
            spec_generate(
                params, tokens, lengths, CFG, max_new_tokens=10,
                draft_len=d, eos_id=-1, pad_id=0, attn_impl="xla",
            ).tokens
        )
        for d in (1, 3, 8)
    ]
    np.testing.assert_array_equal(outs[0], outs[1])
    np.testing.assert_array_equal(outs[1], outs[2])


def test_propose_ngram_drafts_copies_after_bigram():
    history = jnp.asarray([[7, 8, 9, 3, 4, 7, 8, 0, 0, 0]], dtype=jnp.int32)
    lengths = jnp.asarray([7], dtype=jnp.int32)  # tail bigram (7, 8)
    drafts = propose_ngram_drafts(history, lengths, draft_len=3)
    # bigram (7,8) last occurred at 0..1 -> draft copies 9, 3, 4
    assert drafts.tolist() == [[9, 3, 4]]


def test_propose_ngram_drafts_fallback_repeats_last():
    history = jnp.asarray([[1, 2, 3, 4, 0, 0]], dtype=jnp.int32)
    lengths = jnp.asarray([4], dtype=jnp.int32)  # bigram (3,4) never seen before
    drafts = propose_ngram_drafts(history, lengths, draft_len=2)
    assert drafts.tolist() == [[4, 4]]


def test_spec_generate_sharded_matches_single_device(params):
    """spec_generate under a (fsdp, tp) mesh: per-row verify windows and
    cache scatters must partition like the plain decode path."""
    from jax.sharding import NamedSharding

    from prime_tpu.parallel.mesh import make_mesh
    from prime_tpu.parallel.sharding import batch_spec, cache_spec, lengths_spec, shard_params

    tokens = jnp.tile(jnp.asarray([5, 9, 13, 17], dtype=jnp.int32), (4, 4))  # periodic
    lengths = jnp.asarray([16, 13, 16, 11], dtype=jnp.int32)
    ref = spec_generate(
        params, tokens, lengths, CFG, max_new_tokens=12, draft_len=4,
        eos_id=-1, pad_id=0, attn_impl="xla",
    )
    mesh = make_mesh({"dp": 1, "fsdp": 4, "tp": 2})
    sharded = shard_params(params, mesh, CFG)
    tokens_s = jax.device_put(tokens, NamedSharding(mesh, batch_spec()))
    lengths_s = jax.device_put(lengths, NamedSharding(mesh, lengths_spec()))
    with jax.set_mesh(mesh):
        out = spec_generate(
            sharded, tokens_s, lengths_s, CFG, max_new_tokens=12, draft_len=4,
            eos_id=-1, pad_id=0, attn_impl="xla", cache_spec=cache_spec(),
        )
    np.testing.assert_array_equal(np.asarray(out.tokens), np.asarray(ref.tokens))


# -- sampled speculation: exact in DISTRIBUTION -------------------------------


SAMP_CFG = CFG.scaled(name="tiny-samp", vocab_size=16, d_model=32, n_layers=1, n_heads=2, n_kv_heads=1, d_ff=64)


@pytest.fixture(scope="module")
def samp_params():
    return init_params(jax.random.PRNGKey(3), SAMP_CFG, dtype=jnp.float32)


def test_spec_sampled_matches_plain_distribution(samp_params):
    """Rejection sampling against the n-gram proposal must reproduce the
    autoregressive sampling distribution exactly — compare per-position
    marginals over many seeds (TV distance below statistical noise)."""
    n_runs = 2048
    max_new = 3
    prompt = jnp.array([[3, 7, 3, 7, 3]], dtype=jnp.int32)
    lengths = jnp.array([5], dtype=jnp.int32)
    keys = jax.random.split(jax.random.PRNGKey(42), n_runs)

    def run_spec(key):
        return spec_generate(
            samp_params, prompt, lengths, SAMP_CFG, max_new_tokens=max_new,
            draft_len=3, pad_id=0, attn_impl="xla", temperature=0.7, rng=key,
        ).tokens[0]

    def run_plain(key):
        return generate(
            samp_params, prompt, lengths, SAMP_CFG, key, max_new_tokens=max_new,
            temperature=0.7, pad_id=0, attn_impl="xla",
        ).tokens[0]

    spec_tokens = np.asarray(jax.vmap(run_spec)(keys))      # (n, max_new)
    plain_tokens = np.asarray(jax.vmap(run_plain)(keys))
    for position in range(max_new):
        spec_hist = np.bincount(spec_tokens[:, position], minlength=16) / n_runs
        plain_hist = np.bincount(plain_tokens[:, position], minlength=16) / n_runs
        tv = 0.5 * np.abs(spec_hist - plain_hist).sum()
        assert tv < 0.09, f"position {position}: TV {tv:.3f}"


def test_spec_sampled_top_p_collapses_to_greedy(samp_params):
    """nucleus with a vanishing top_p keeps only the argmax token — sampled
    speculation must then emit exactly the greedy sequence."""
    prompt = jnp.array([[3, 7, 3, 7, 3, 9, 2, 11]], dtype=jnp.int32)
    lengths = jnp.array([8], dtype=jnp.int32)
    greedy = spec_generate(
        samp_params, prompt, lengths, SAMP_CFG, max_new_tokens=8,
        draft_len=3, pad_id=0, attn_impl="xla",
    )
    nucleus = spec_generate(
        samp_params, prompt, lengths, SAMP_CFG, max_new_tokens=8,
        draft_len=3, pad_id=0, attn_impl="xla",
        temperature=1.0, top_p=1e-6, nucleus=True, rng=jax.random.PRNGKey(9),
    )
    np.testing.assert_array_equal(np.asarray(greedy.tokens), np.asarray(nucleus.tokens))
    np.testing.assert_array_equal(np.asarray(greedy.lengths), np.asarray(nucleus.lengths))


def test_spec_sampled_requires_rng(samp_params):
    prompt = jnp.array([[1, 2, 3]], dtype=jnp.int32)
    with pytest.raises(ValueError, match="rng"):
        spec_generate(
            samp_params, prompt, jnp.array([3], dtype=jnp.int32), SAMP_CFG,
            max_new_tokens=2, temperature=0.5,
        )


def _ulps_under_the_best(generator, prompt, ids):
    """How far under the model's best logit each token of ``ids`` lay where it
    was chosen, in bfloat16 ulps of that best logit: one causal forward over the
    prompt and ``ids`` themselves, in the generator's own dtype."""
    from prime_tpu.models.llama import forward

    start = generator.tokenizer.encode(prompt, add_special_tokens=True)
    logits, _ = forward(generator.params, jnp.asarray([start + ids], jnp.int32), generator.config)
    rows = np.asarray(logits[0, len(start) - 1 : len(start) - 1 + len(ids)], np.float32)
    best = rows.max(axis=-1)
    ulp = 2.0 ** (np.floor(np.log2(np.abs(best))) - 7)
    return (best - rows[np.arange(len(ids)), ids]) / ulp


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
def test_jax_generator_speculative_matches_plain(dtype):
    """float32: speculation emits plain decode's tokens, to the token. bfloat16:
    the verify window (``cache_prefill_attention`` at per-row offsets, which
    rounds P before it divides by the sum) and the decode step (which divides
    first) are two programs, and where the model's best two logits lie within
    one bfloat16 ulp the argmax may fall either way. So the two agree up to the
    first such place, and every token either emits lies within one ulp of the
    model's best logit over that sequence's own prefix: a wrong mask or offset
    in the window's walk picks tokens far under the best and fails this."""
    from prime_tpu.evals.runner import JaxGenerator

    plain = JaxGenerator("tiny-test", dtype=dtype)
    spec = JaxGenerator("tiny-test", speculative=True, draft_len=4, dtype=dtype)
    prompts = ["12+34=46 12+34=", "hello hello hello "]
    a = plain.generate_ids(prompts, max_new_tokens=12, temperature=0.0)
    b = spec.generate_ids(prompts, max_new_tokens=12, temperature=0.0)
    if dtype == jnp.float32:
        assert a == b
        assert plain.generate(prompts, 12, 0.0) == spec.generate(prompts, 12, 0.0)
        return
    vocab = plain.config.vocab_size
    for prompt, x, y in zip(prompts, a, b):
        assert len(x) == len(y) == 12
        for ids in (x, y):
            assert _ulps_under_the_best(plain, prompt, ids).max() <= 1.0, (prompt, x, y)
        # the yardstick has teeth: tokens that are not the model's lie far under
        assert _ulps_under_the_best(plain, prompt, [(t + 1) % vocab for t in y]).max() > 8.0


def test_spec_kv_quant_matches_plain_kv_quant(params):
    """int8-cache speculation: the verify window quantizes its slots with the
    same per-slot scheme plain decode uses, so the stored cache is identical
    — greedy spec+kvq must emit exactly what plain kvq decode does (fp32
    weights: no matmul-rounding ties)."""
    tokens = jnp.tile(jnp.arange(1, 9, dtype=jnp.int32), (2, 2))  # periodic (2, 16)
    lengths = jnp.array([16, 12], dtype=jnp.int32)
    ref = generate(
        params, tokens, lengths, CFG, jax.random.PRNGKey(1),
        max_new_tokens=12, temperature=0.0, pad_id=0, attn_impl="xla",
        kv_quant=True,
    )
    out = spec_generate(
        params, tokens, lengths, CFG,
        max_new_tokens=12, draft_len=4, pad_id=0, attn_impl="xla",
        kv_quant=True,
    )
    np.testing.assert_array_equal(np.asarray(ref.tokens), np.asarray(out.tokens))
    np.testing.assert_array_equal(np.asarray(ref.lengths), np.asarray(out.lengths))


def test_jax_generator_speculative_with_kv_quant():
    """The former hard incompatibility is now a working combination."""
    from prime_tpu.evals.runner import JaxGenerator

    gen = JaxGenerator("tiny-test", speculative=True, kv_quant=True)
    out = gen.generate(["12+34=46 12+34="], max_new_tokens=8, temperature=0.0)
    assert len(out) == 1 and isinstance(out[0], str)
