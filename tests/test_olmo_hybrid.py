"""The gated_delta block kind in its dense post-norm form (Olmo-Hybrid): the
gated delta rule with ``beta = 2 sigmoid(b)`` and one value head a key head, a
full attention with a whole-width q/k norm and no rotary embedding every fourth
layer, a dense feed-forward, OLMo-2's norm-on-the-output residual path: the
program against the plain reference ``benchmark/reference/olmo_hybrid.py`` at the
tiny preset (two periods of four layers, 3 heads of (12, 24): no dimension is a
multiple of a tile, and keys and values differ in width, so a transposed or
padded state block fails), seeded random weights, on the CPU.

Everything here runs in float32 (the CPU's float32 matmul is exact float32), so
program and reference differ only by the order of their sums, of which the
chunked delta rule changes many: the tolerance is 1e-4 OF THE LARGEST LOGIT
(``TOL`` times 4.1-4.4 here), three times the 3.4e-5 of it they were seen to
differ by at most (nine prompts of 100-140 positions, chunked three ways, then
decoded: 4e-5 to 1.5e-4 absolute). It is relative because this block norms
every sub-layer's OUTPUT: a mixer's output is divided by its own rms before it
joins the residual stream, so the rounding of a small output is carried at the
stream's scale, which a norm on the input (the block's other model, 4.4e-5 at
2e-4) does not do. Each named fault moves the reference's own logits by a
hundred times that or more (``test_the_tolerance_tells_each_fault``), so a
program that had it could not pass.
"""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.reference import olmo_hybrid as ref
from prime_tpu.models import delta, get_config
from prime_tpu.models.llama import forward, init_cache, init_params
from prime_tpu.ops.pallas_delta import delta_decode_step, delta_step_heads
from prime_tpu.serve.engine import ContinuousBatchingEngine, cache_leaves

TOL = 1e-4
CONFIG = get_config("tiny-olmohybrid")
LAYER_TYPES = (["linear_attention"] * 3 + ["full_attention"]) * 8
# the configuration file's keys for the tiny preset (the cell's rehearsal model)
MODEL = dict(
    hidden_size=96, intermediate_size=192, num_hidden_layers=8, num_attention_heads=3,
    num_key_value_heads=3, head_dim=None, rms_norm_eps=1e-6, layer_types=LAYER_TYPES,
    linear_num_key_heads=3, linear_num_value_heads=3, linear_key_head_dim=12,
    linear_value_head_dim=24, linear_conv_kernel_dim=4, linear_allow_neg_eigval=True,
    vocab_size=512,
)


def _flat(ours):
    """The program's leaves under the reference's names."""
    stack = ours["layers"]
    return {
        **{name: leaf for name, leaf in stack.items() if not isinstance(leaf, dict)},
        **{f"{group}.{name}": leaf for group in ("delta", "attn") for name, leaf in stack[group].items()},
        "embed": ours["embed"], "lm_head": ours["lm_head"],
    }


@pytest.fixture(scope="module")
def weights():
    """The reference's own weights (bf16-rounded values) and the program's,
    leaf for leaf the same, the program's widened to float32."""
    ours = init_params(jax.random.PRNGKey(0), CONFIG, dtype=jnp.bfloat16)
    theirs = ref.make_weights(MODEL, 0, jax.devices())
    mine = _flat(ours)
    for name, value in theirs.items():
        assert mine[name].dtype == value.dtype and bool(jnp.all(mine[name] == value)), name
    # what the reference states and does not store: every norm weight one
    norms = {"attn_post_norm", "mlp_post_norm", "delta.gate_norm", "attn.q_norm_full", "attn.k_norm_full"}
    assert set(mine) - set(theirs) == norms
    assert all(bool(jnp.all(mine[n] == 1)) for n in norms) and bool(jnp.all(ours["final_norm"] == 1))
    return jax.tree.map(lambda x: x.astype(jnp.float32), ours), theirs


def _tokens(seed, count):
    return np.random.default_rng(seed).integers(3, 500, size=count).tolist()


def _reference(theirs, tokens, model=MODEL):
    """Float32 logits at every position of one sequence's full pass."""
    return np.asarray(ref.logits_at(model, theirs, tokens, np.arange(len(tokens))))


def _one(tokens):
    return jnp.asarray([tokens], jnp.int32)


@jax.jit
def _plain(params, tokens):
    return forward(params, tokens, CONFIG)[0]


@functools.partial(jax.jit, static_argnames=("attn_impl",))
def _decode(params, column, cache, attn_impl="auto"):
    return forward(params, column, CONFIG, cache=cache, decode=True, positions=cache.lengths[:, None],
                   attn_impl=attn_impl)


@jax.jit
def _chunk(params, tokens, cache, offset):
    return forward(params, tokens, CONFIG, cache=cache, prefill_offset=offset)


# the decode step's two paths: plain XLA (what the CPU backend takes by itself),
# and the kernels a chip takes (the column write, flash-decode and the state
# kernel of ops/pallas_delta.py), interpreted
STEP_PATHS = {"xla-step": "auto", "kernel-step": "pallas"}


@pytest.fixture(params=sorted(STEP_PATHS))
def step_impl(request, monkeypatch):
    if request.param == "kernel-step":
        monkeypatch.setenv("PRIME_TPU_PALLAS_INTERPRET", "1")
    return STEP_PATHS[request.param]


def test_the_presets_state_the_cut_and_the_stack_has_what_the_flags_say():
    served = get_config("olmo-hybrid-7b-pp2")
    # 8.2 GB of bfloat16: four periods at the published widths, the whole vocabulary twice (untied)
    assert served.param_count == 4_100_788_944
    assert (served.cache_rows, served.linear_layers, served.linear_conv_channels) == (4, 12, 11520)
    assert (served.head_dim, served.is_moe, served.rotary, served.linear_neg_eigval) == (128, False, False, True)
    cache = init_cache(served, 1, 16)
    # a slot: 61.4 KB of K/V a token, 26.5 MB of float32 state, 0.8 MB of conv tails
    assert 2 * cache.k.nbytes // 16 == 61_440 and cache.delta_state.nbytes == 26_542_080
    assert cache.conv_tail.nbytes == 829_440
    cache = init_cache(CONFIG, 3, 128)
    assert cache.k.shape == cache.v.shape == (2, 3, 3, 32, 128)
    assert cache.delta_state.shape == (6, 3, 3, 12, 24) and cache.delta_state.dtype == jnp.float32
    assert cache.conv_tail.shape == (6, 3, 3, 3 * (12 + 12 + 24))
    params = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), CONFIG))
    stack = params["layers"]
    # dense feed-forward a layer, norms on the outputs only, no gate half in wq, one norm over all heads
    assert stack["w_gate"].shape == (8, 96, 192) and "router" not in stack
    assert "attn_norm" not in stack and stack["attn_post_norm"].shape == (8, 96)
    assert stack["attn"]["wq"].shape == (2, 96, 96) and stack["attn"]["q_norm_full"].shape == (2, 96)
    assert "q_norm" not in stack["attn"]
    assert sum(leaf.size for leaf in jax.tree.leaves(params)) == CONFIG.param_count


def test_forward_without_a_cache_matches_the_reference(weights):
    ours, theirs = weights
    tokens = _tokens(1, 150)  # three blocks of the chunked form, the last one short
    want = _reference(theirs, tokens)
    got = _plain(ours, _one(tokens))
    assert np.abs(np.asarray(got[0]) - want).max() < TOL * np.abs(want).max()
    last, _ = forward(ours, _one(tokens), CONFIG, last_positions=jnp.asarray([129]))
    assert np.abs(np.asarray(last[0, 0]) - want[129]).max() < TOL * np.abs(want).max()


def test_bf16_weights_fail_the_tolerance(weights):
    """The comparison is tight enough that serving in bfloat16 fails it."""
    _, theirs = weights
    tokens = _tokens(1, 40)
    low = init_params(jax.random.PRNGKey(0), CONFIG, dtype=jnp.bfloat16)
    got = _plain(low, _one(tokens))
    want = _reference(theirs, tokens)
    assert np.abs(np.asarray(got[0]) - want).max() > 100 * TOL * np.abs(want).max()


def _chunks_then_decode(ours, want, tokens, sizes, spoil=None, attn_impl="auto"):
    """The worst gap of a chunked prefill of ``sizes`` and a decode of the rest,
    as a share of the largest logit; ``spoil(cache)`` stands for a fault between
    the prefill and the decode."""
    cache = init_cache(CONFIG, 1, 256, dtype=jnp.float32)
    offset, worst = 0, 0.0
    for size in sizes:
        logits, cache = _chunk(ours, _one(tokens[offset : offset + size]), cache, jnp.asarray(offset, jnp.int32))
        worst = max(worst, np.abs(np.asarray(logits[0]) - want[offset : offset + size]).max())
        offset += size
    cache = spoil(cache) if spoil else cache
    for at in range(offset, len(tokens)):
        logits, cache = _decode(ours, _one(tokens[at : at + 1]), cache, attn_impl=attn_impl)
        worst = max(worst, np.abs(np.asarray(logits[0, 0]) - want[at]).max())
    assert int(cache.lengths[0]) == len(tokens)
    return worst / np.abs(want).max()


@pytest.mark.parametrize("sizes", [(64, 64), (80, 16, 32), (16, 3, 1, 70)],
                         ids=["two-chunks", "three-chunks", "odd-chunks"])
def test_chunked_prefill_hands_state_and_tail_on_to_decode(weights, sizes, step_impl):
    ours, theirs = weights
    tokens = _tokens(2, sum(sizes) + 12)
    worst = _chunks_then_decode(ours, _reference(theirs, tokens), tokens, sizes, attn_impl=step_impl)
    assert worst < TOL


@pytest.mark.parametrize("leaf", ["delta_state", "conv_tail"])
def test_a_state_or_a_tail_dropped_between_prefill_and_decode_fails_the_tolerance(weights, leaf):
    ours, theirs = weights
    tokens = _tokens(2, 140)
    spoil = lambda cache: cache._replace(**{leaf: jnp.zeros_like(getattr(cache, leaf))})  # noqa: E731
    worst = _chunks_then_decode(ours, _reference(theirs, tokens), tokens, (64, 64), spoil)
    assert worst > 100 * TOL


# ---- the state kernel at this model's tiles ----


def _step_inputs(seed, rows, batch, heads, d_k, d_v):
    rng = np.random.default_rng(seed)
    unit = lambda x: x / np.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    leaf = rng.normal(size=(rows, batch, heads, d_k, d_v)) * 0.3
    q = unit(rng.normal(size=(batch, heads, d_k))) * d_k**-0.5
    k = unit(rng.normal(size=(batch, heads, d_k)))
    v = rng.normal(size=(batch, heads, d_v))
    beta = rng.uniform(0.1, 1.9, size=(batch, heads))  # past 1: the negative-eigenvalue range
    g = -rng.uniform(1e-3, 0.2, size=(batch, heads))
    return tuple(jnp.asarray(t, jnp.float32) for t in (leaf, q, k, v, beta, g))


@pytest.mark.parametrize("heads, d_k, d_v", [(3, 12, 24), (30, 96, 192)], ids=["tiny", "published"])
def test_the_state_kernel_is_the_step_at_this_models_tiles(heads, d_k, d_v):
    """Interpreted, at the test preset's (3, 12, 24) and at the published
    (30, 96, 192): keys narrower than values, neither a multiple of 128. The
    stepped stack row and the live rows only, to float32 rounding; every other
    row of the leaf bit for bit."""
    leaf, q, k, v, beta, g = _step_inputs(heads, 2, 3, heads, d_k, d_v)
    live = jnp.asarray([True, False, True])
    want_state, want_o = delta._step(leaf[1], q, k, v, beta, g)
    got_leaf, got_o = delta_decode_step(leaf, 1, q, k, v, beta, g, live, interpret=True)
    assert got_leaf.shape == leaf.shape and got_o.shape == (3, heads, d_v)
    for row in (0, 2):
        assert np.abs(np.asarray(got_leaf[1, row] - want_state[row])).max() < 2e-6
        assert np.abs(np.asarray(got_o[row] - want_o[row])).max() < 2e-6
    assert np.array_equal(np.asarray(got_leaf[1, 1]), np.asarray(leaf[1, 1]))
    assert np.array_equal(np.asarray(got_leaf[0]), np.asarray(leaf[0]))
    assert not np.any(np.asarray(got_o[1]))


def test_a_grid_step_holds_the_heads_whose_padded_tiles_fit():
    # 30 heads of (96, 192) float32 are 2.2 MB in the slots and, with 192 lanes in two
    # tiles, 2.9 MB in VMEM: four of those pass the budget, so 15 heads a step
    assert delta_step_heads(30, 96, 192) == 15
    assert delta_step_heads(32, 128, 128) == 32 and delta_step_heads(3, 12, 24) == 3


# ---- the engine: the same slots, admission, chunk prefill, finalize and decode ----


def _engine(ours, slots=2, **kwargs):
    kwargs.setdefault("prefix_cache_mb", 0)
    return ContinuousBatchingEngine(
        ours, CONFIG, max_slots=slots, capacity=256, chunk=4, prefill_chunk=64, **kwargs,
    )


def _run(engine, requests, ticks=600):
    for _ in range(ticks):
        engine.tick()
        if all(r.done for r in requests):
            break
    assert all(r.done and r.error is None for r in requests)
    return [r.all_tokens(timeout=1) for r in requests]


def _gap(theirs, prompt, answer):
    """By how much the served tokens' reference logits lie below the
    reference's best, at worst and as a share of the largest logit: 0 where
    every served token is the argmax."""
    logits = np.asarray(ref.score(MODEL, theirs, prompt, answer, 256, 64))
    return float((logits.max(axis=-1) - logits[np.arange(len(answer)), answer]).max() / np.abs(logits).max())


def test_two_requests_of_one_wave_match_each_alone_and_the_reference(weights):
    """Lengths 150 and 21 in one admission wave, through the engine's slots:
    chunk prefill, finalize (each row's state and tail copied whole into its
    slot) and the decode chunks, greedy, every served token the reference's
    best within the tolerance."""
    ours, theirs = weights
    first, second = _tokens(30, 150), _tokens(31, 21)
    engine = _engine(ours)
    a = engine.submit(first, max_new_tokens=20, temperature=0.0)
    b = engine.submit(second, max_new_tokens=30, temperature=0.0)
    together = _run(engine, [a, b])
    assert [len(t) for t in together] == [20, 30]
    for prompt, answer in zip((first, second), together):
        assert _gap(theirs, prompt, answer) < TOL
    for prompt, answer in zip((first, second), together):
        request = engine.submit(prompt, max_new_tokens=len(answer), temperature=0.0)
        assert _run(engine, [request])[0] == answer
    held = cache_leaves(engine._cache)
    assert {name: spec.kind for name, (_, spec) in held.items()} == {
        "k": "full", "v": "full", "conv_tail": "state", "delta_state": "state",
    }
    values = engine.registry.values()
    assert values["serve_state_resets_total"] == values["serve_requests_admitted_total"] == 4


def test_the_kernel_step_serves_the_same_tokens_through_the_engine(weights, monkeypatch):
    ours, theirs = weights
    monkeypatch.setenv("PRIME_TPU_PALLAS_INTERPRET", "1")
    prompt = _tokens(40, 70)
    engine = _engine(ours, attn_impl="pallas")
    answer = _run(engine, [engine.submit(prompt, max_new_tokens=12, temperature=0.0)])[0]
    assert _gap(theirs, prompt, answer) < TOL
    # the state kernel's steps were counted: a live row of the two, 6 linear layers, a decode step
    steps = {s["labels"]["kind"]: s["value"]
             for s in engine.registry.snapshot()["serve_delta_state_steps_total"]["series"]}
    assert steps["walked"] > 0 and steps["walked"] == steps["spared"]


# ---- the tolerance tells each fault ----


def _per_head_norm(t, eps):
    heads = t.reshape(t.shape[0], 3, -1)
    return ref._norm(heads, eps).reshape(t.shape)


def _rotary(q, k, positions):
    def turn(x):
        half = x.shape[-1] // 2
        inv = 10000.0 ** (-jnp.arange(half, dtype=jnp.float32) / half)
        angle = positions[:, None].astype(jnp.float32) * inv[None, :]
        cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
        x1, x2 = x[..., :half], x[..., half:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)

    return turn(q), turn(k)


def _pre_norm(x, sub_layer, eps):
    return x + sub_layer(ref._norm(x, eps))


FAULTS = {
    "sigmoid-without-the-2": {"model": {"linear_allow_neg_eigval": False}},
    "pre-norm-for-post-norm": {"patch": ("_residual", _pre_norm)},
    "per-head-qk-norm": {"patch": ("_qk_norm", _per_head_norm)},
    "rotary-applied": {"patch": ("_positions", _rotary)},
    # key head j serving value heads 2j and 2j + 1, as the block's other model has it
    "key-heads-repeated": {"patch": ("_key_heads", lambda t: t[:, jnp.arange(t.shape[1]) // 2])},
    "state-forgotten": {"zero": "delta.A_log"},  # A = 1: a state is gone within a few positions
    "conv-dropped": {"zero": "delta.conv_w"},
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_the_tolerance_tells_each_fault(weights, monkeypatch, fault):
    _, theirs = weights
    tokens = _tokens(60, 100)
    jax.clear_caches()  # the reference's jitted pass is traced anew, with or without a fault
    clean = _reference(theirs, tokens)
    spec = FAULTS[fault]
    faulty_weights = theirs
    if "zero" in spec:
        faulty_weights = {**theirs, spec["zero"]: jnp.zeros_like(theirs[spec["zero"]])}
    if "patch" in spec:
        monkeypatch.setattr(ref, *spec["patch"])
    jax.clear_caches()
    try:
        faulty = _reference(faulty_weights, tokens, {**MODEL, **spec.get("model", {})})
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    assert np.abs(faulty - clean).max() > 100 * TOL * np.abs(clean).max(), fault
