"""The gated_delta block kind (Qwen3-Next): linear attention by the gated delta
rule with its matrix state in the engine's slots, a gated full attention every
fourth layer, a share of the experts beside a gated shared expert: the program
against the plain reference ``benchmark/reference/qwen3_next.py`` at the tiny
preset of the same block kind (two periods of four layers, 8 experts of which
experts 2-5 are held, top-3), seeded random weights, on the CPU.

Everything here runs in float32 (the CPU's float32 matmul is exact float32), so
program and reference differ only by the order of their sums, of which the
chunked delta rule changes many: the tolerance ``TOL`` is 2e-4 on logits of
magnitude 1-5, four times the 4.4e-5 they were seen to differ by at most (150
positions, three blocks of the chunked form). Each named fault moves the
reference's own logits by a hundred times ``TOL`` or more
(``test_the_tolerance_tells_each_fault``), so a program that had it could not
pass.
"""

import dataclasses
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.reference import qwen3_next as ref
from prime_tpu.models import delta, get_config
from prime_tpu.models.llama import CACHE_LEAVES, _mlp_block, forward, init_cache, init_params
from prime_tpu.serve.engine import ContinuousBatchingEngine, cache_leaves, row_capacities

TOL = 2e-4
CONFIG = get_config("tiny-qwen3next")
# the configuration file's keys for the tiny preset (the cell's rehearsal model)
MODEL = dict(
    hidden_size=64, num_hidden_layers=8, full_attention_interval=4, num_attention_heads=4,
    num_key_value_heads=2, head_dim=32, partial_rotary_factor=0.25, rope_theta=10000000,
    rms_norm_eps=1e-6, linear_num_key_heads=2, linear_num_value_heads=4, linear_key_head_dim=16,
    linear_value_head_dim=16, linear_conv_kernel_dim=4, moe_intermediate_size=32,
    shared_expert_intermediate_size=32, num_experts=4, num_experts_published=8, first_expert=2,
    num_experts_per_tok=3, norm_topk_prob=True, vocab_size=512,
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
    # what the reference states and does not store: (1 + w) norm weights zero, the gated norm's one
    assert set(mine) - set(theirs) == {"attn_norm", "mlp_norm", "delta.gate_norm", "attn.q_norm", "attn.k_norm"}
    assert not any(bool(jnp.any(mine[n] != 0)) for n in ("attn_norm", "mlp_norm", "attn.q_norm", "attn.k_norm"))
    assert bool(jnp.all(mine["delta.gate_norm"] == 1)) and bool(jnp.all(ours["final_norm"] == 0))
    return jax.tree.map(lambda x: x.astype(jnp.float32), ours), theirs


def _tokens(seed, count):
    return np.random.default_rng(seed).integers(3, 500, size=count).tolist()


def _reference(theirs, tokens, model=MODEL):
    """Float32 logits at every position of one sequence's full pass."""
    return np.asarray(ref.logits_at(model, theirs, tokens, np.arange(len(tokens))))


def _one(tokens):
    return jnp.asarray([tokens], jnp.int32)


# one program a shape (an eager ``forward`` traces its layer scan anew at every call,
# and an eager mixer runs its hundred small operations one program each)
_mixer = jax.jit(delta.delta_mixer, static_argnames=("key_heads",))
_experts = jax.jit(_mlp_block, static_argnames=("config", "dropless"))


@jax.jit
def _plain(params, tokens):
    return forward(params, tokens, CONFIG)[0]


@functools.partial(jax.jit, static_argnames=("attn_impl",))
def _decode(params, column, cache, attn_impl="auto"):
    return forward(params, column, CONFIG, cache=cache, decode=True, positions=cache.lengths[:, None],
                   attn_impl=attn_impl)


# the decode step's two paths: plain XLA (what the CPU backend takes by itself),
# and the kernels a chip takes (the column write, flash-decode and the state
# kernel of ops/pallas_delta.py), interpreted
STEP_PATHS = {"xla-step": "auto", "kernel-step": "pallas"}


@pytest.fixture(params=sorted(STEP_PATHS))
def step_impl(request, monkeypatch):
    if request.param == "kernel-step":
        monkeypatch.setenv("PRIME_TPU_PALLAS_INTERPRET", "1")
    return STEP_PATHS[request.param]


@jax.jit
def _chunk(params, tokens, cache, offset):
    return forward(params, tokens, CONFIG, cache=cache, prefill_offset=offset)


@jax.jit
def _chunk_at_last(params, tokens, cache, offset, last, live):
    return forward(params, tokens, CONFIG, cache=cache, prefill_offset=offset,
                   last_positions=last, token_mask=live)


def test_the_presets_state_the_share_and_the_cache_holds_rows_for_full_layers_only():
    served = get_config("qwen3-next-ep2")
    # 7.36 GB of bfloat16: one period at the published widths, 256 of 512 experts, half the vocabulary
    assert served.param_count == 3_677_613_120
    assert (served.cache_rows, served.linear_layers, served.linear_conv_channels) == (1, 3, 8192)
    assert (served.experts_held, served.first_expert, served.shared_ff) == (256, 0, 512)
    assert (CONFIG.cache_rows, CONFIG.linear_layers, CONFIG.first_expert) == (2, 6, 2)
    cache = init_cache(CONFIG, 3, 128)
    # a K/V row a PERIOD; a float32 matrix a value head and a conv tail a LINEAR layer
    assert cache.k.shape == cache.v.shape == (2, 3, 2, 32, 128)
    assert cache.delta_state.shape == (6, 3, 4, 16, 16) and cache.delta_state.dtype == jnp.float32
    assert cache.conv_tail.shape == (6, 3, 3, 2 * 2 * 16 + 4 * 16)
    held = cache_leaves(cache)
    assert {name: spec.kind for name, (_, spec) in held.items()} == {
        "k": "full", "v": "full", "conv_tail": "state", "delta_state": "state",
    }
    assert all(spec.slot_axis == 1 for _, spec in held.values()) and set(CACHE_LEAVES) >= set(held)
    params = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), CONFIG))
    stack = params["layers"]
    # the experts HELD, never the router's width; the linear and the full layers' own stacks
    assert stack["w_gate"].shape == (8, 4, 64, 32) and stack["router"].shape == (8, 64, 8)
    assert stack["delta"]["w_qkvz"].shape[0] == 6 and stack["attn"]["wq"].shape == (2, 64, 2 * 4 * 32)
    assert sum(leaf.size for leaf in jax.tree.leaves(params)) == CONFIG.param_count


def test_forward_without_a_cache_matches_the_reference(weights):
    ours, theirs = weights
    tokens = _tokens(1, 150)  # three blocks of the chunked form, the last one short
    want = _reference(theirs, tokens)
    got = _plain(ours, _one(tokens))
    assert np.abs(np.asarray(got[0]) - want).max() < TOL
    last, _ = forward(ours, _one(tokens), CONFIG, last_positions=jnp.asarray([129]))
    assert np.abs(np.asarray(last[0, 0]) - want[129]).max() < TOL


def test_bf16_weights_fail_the_tolerance(weights):
    """The comparison is tight enough that serving in bfloat16 fails it."""
    _, theirs = weights
    tokens = _tokens(1, 40)
    low = init_params(jax.random.PRNGKey(0), CONFIG, dtype=jnp.bfloat16)
    got = _plain(low, _one(tokens))
    assert np.abs(np.asarray(got[0]) - _reference(theirs, tokens)).max() > 100 * TOL


def _chunks_then_decode(ours, want, tokens, sizes, spoil=None, attn_impl="auto"):
    """The worst gap of a chunked prefill of ``sizes`` and a decode of the rest;
    ``spoil(cache)`` stands for a fault between the prefill and the decode."""
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
    return worst


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


def _delta_inputs(seed, batch, seq, heads=3, d_k=16, d_v=8, same_keys=False):
    rng = np.random.default_rng(seed)
    unit = lambda x: x / np.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    q = unit(rng.normal(size=(batch, seq, heads, d_k))) * d_k**-0.5
    k = unit(rng.normal(size=(batch, 1 if same_keys else seq, heads, d_k)))
    k = np.broadcast_to(k, (batch, seq, heads, d_k))
    v = rng.normal(size=(batch, seq, heads, d_v))
    beta = rng.uniform(0.1, 0.9, size=(batch, seq, heads))
    g = -rng.uniform(1e-3, 0.2, size=(batch, seq, heads))
    state = rng.normal(size=(batch, heads, d_k, d_v)) * 0.3
    return tuple(jnp.asarray(t, jnp.float32) for t in (q, k, v, beta, g, state))


@pytest.mark.parametrize("seq", [1, 2, 7, 63, 64, 65, 150, 200])
def test_the_chunked_delta_rule_is_the_recurrence(seq):
    """Any length, multiples of the block or not, from a state that is not zero:
    outputs and the state handed on, to float32 rounding (values of order 1)."""
    inputs = _delta_inputs(seq, 2, seq)
    want_o, want_s = delta.delta_rule_recurrent(*inputs)
    got_o, got_s = delta.delta_rule_chunked(*inputs)
    assert got_o.shape == want_o.shape
    assert np.abs(np.asarray(got_o - want_o)).max() < 2e-5
    assert np.abs(np.asarray(got_s - want_s)).max() < 2e-5


def test_the_chunked_form_stands_a_run_of_one_key():
    """Identical keys (a run of one token): ``(I + A)^-1`` has entries of order
    1 while a Neumann series' terms reach 1e8 before they cancel. Built a
    quadrant at a time it stays at float32 rounding."""
    q, k, v, beta, g, state = _delta_inputs(5, 1, 128, same_keys=True)
    beta, g = jnp.full_like(beta, 0.5), jnp.zeros_like(g)
    want_o, want_s = delta.delta_rule_recurrent(q, k, v, beta, g, state)
    got_o, got_s = delta.delta_rule_chunked(q, k, v, beta, g, state)
    assert np.abs(np.asarray(got_o - want_o)).max() < 2e-5
    assert np.abs(np.asarray(got_s - want_s)).max() < 2e-5


def _mixer_params(seed=0):
    ours = init_params(jax.random.PRNGKey(seed), CONFIG, dtype=jnp.float32)
    return {name: leaf[0] for name, leaf in ours["layers"]["delta"].items()}


def test_padded_rows_leave_state_and_tail_as_their_last_real_token_left_them():
    """``live`` False positions past a row's last token (the engine pads a
    wave's last chunk) change neither the state nor the tail, whatever they
    hold, and a row with no live position at all keeps both."""
    lp, heads = _mixer_params(), CONFIG.linear_key_heads
    rng = np.random.default_rng(7)
    h = jnp.asarray(rng.normal(size=(3, 80, 64)), jnp.float32)
    counts = (80, 37, 0)
    live = jnp.arange(80)[None, :] < jnp.asarray(counts)[:, None]
    state = jnp.asarray(rng.normal(size=(3, 4, 16, 16)), jnp.float32)
    tail = jnp.asarray(rng.normal(size=(3, 3, 128)), jnp.float32)
    out, new_state, new_tail = _mixer(h, lp, state, tail, live, key_heads=heads)
    for row, count in enumerate(counts):
        if count == 0:
            assert np.array_equal(np.asarray(new_state[row]), np.asarray(state[row]))
            assert np.array_equal(np.asarray(new_tail[row]), np.asarray(tail[row]))
            continue
        alone = _mixer(h[row : row + 1, :count], lp, state[row : row + 1],
                                  tail[row : row + 1], None, key_heads=heads)
        assert np.abs(np.asarray(out[row, :count] - alone[0][0])).max() < 2e-5
        assert np.abs(np.asarray(new_state[row] - alone[1][0])).max() < 2e-5
        assert np.array_equal(np.asarray(new_tail[row]), np.asarray(alone[2][0]))


def test_the_carried_state_matters_with_this_recipes_decay_and_not_with_the_published_one():
    """What a chunk hands on reaches positions a hundred tokens later under the
    recipe's ``A_log`` / ``dt_bias``; under the published initialisation (A
    uniform in (0, 16), dt_bias 1) a state is gone within a position, and a
    test of the carried state would test nothing."""
    lp, heads = _mixer_params(), CONFIG.linear_key_heads
    h = jnp.asarray(np.random.default_rng(8).normal(size=(1, 228, 64)), jnp.float32)

    def effect(lp):
        _, state, tail = _mixer(h[:, :100], lp, None, None, key_heads=heads)
        carried, _, _ = _mixer(h[:, 100:], lp, state, tail, key_heads=heads)
        dropped, _, _ = _mixer(h[:, 100:], lp, None, tail, key_heads=heads)
        return float(jnp.abs(carried[:, 100:] - dropped[:, 100:]).max())  # positions 200-227

    assert effect(lp) > 100 * TOL
    published = {**lp, "A_log": jnp.log(jnp.linspace(0.5, 16.0, 4)), "dt_bias": jnp.ones((4,))}
    assert effect(published) < 1e-6


def test_a_row_that_belongs_to_nobody_leaves_the_others_bit_identical(weights, step_impl):
    ours, _ = weights
    rows = jnp.asarray([_tokens(20, 12), _tokens(21, 12)], jnp.int32)
    cache = init_cache(CONFIG, 2, 64, dtype=jnp.float32)
    _, cache = forward(ours, rows, CONFIG, cache=cache)
    column = jnp.asarray([[7], [9]], jnp.int32)
    at = dict(cache=cache, decode=True, positions=cache.lengths[:, None], attn_impl=step_impl)
    plain, _ = forward(ours, column, CONFIG, **at)
    masked, after, state_steps = forward(
        ours, column, CONFIG, token_mask=jnp.asarray([[True], [False]]), return_state_steps=True, **at
    )
    # the state kernel visits the live row of the two, in each of the 6 linear layers
    assert np.asarray(state_steps).tolist() == ([6, 12] if step_impl == "pallas" else [0, 0])
    assert np.array_equal(np.asarray(plain[0]), np.asarray(masked[0]))
    assert not np.array_equal(np.asarray(plain[1]), np.asarray(masked[1]))
    assert bool(jnp.all(jnp.isfinite(masked)))
    # the dead row's state and tail stay as they were; the engine holds its length
    for leaf in ("delta_state", "conv_tail"):
        assert np.array_equal(np.asarray(getattr(after, leaf)[:, 1]), np.asarray(getattr(cache, leaf)[:, 1]))
        assert not np.array_equal(np.asarray(getattr(after, leaf)[:, 0]), np.asarray(getattr(cache, leaf)[:, 0]))


def test_what_the_block_does_not_serve_is_refused_by_name(weights):
    from prime_tpu.models.quantize import quantize_params_int4, quantize_params_int8

    ours, _ = weights
    cache = init_cache(CONFIG, 2, 64, dtype=jnp.float32)
    tokens = jnp.zeros((2, 4), jnp.int32)
    with pytest.raises(ValueError, match="per-row chunk offset.*R7"):
        forward(ours, tokens, CONFIG, cache=cache, prefill_offset=jnp.asarray([0, 3], jnp.int32))
    with pytest.raises(ValueError, match="kv_quant is unsupported.*R7"):
        init_cache(CONFIG, 2, 64, quantized=True)
    with pytest.raises(ValueError, match="a mesh.*R7"):
        forward(ours, tokens, CONFIG, mesh=jax.make_mesh((2,), ("tp",)))
    with pytest.raises(ValueError, match="a LoRA bank.*R7"):
        forward(ours, tokens, CONFIG, adapters={"layers": {}})
    for quantize in (quantize_params_int8, quantize_params_int4):
        with pytest.raises(ValueError, match="int8 or int4 weights.*R7"):
            quantize(ours)
    # a prefix cache is served since PR 39 (snapshots of the state: tests/test_prefix_state.py),
    # at a stride from shapes; speculation over state stays refused
    served = ContinuousBatchingEngine(ours, CONFIG, max_slots=2, capacity=128, prefix_cache_mb=8)
    assert served.prefix_cache.state_stride == served.state_stride == served.prefill_chunk == 64
    with pytest.raises(ValueError, match="cannot rewind"):
        ContinuousBatchingEngine(ours, CONFIG, max_slots=2, capacity=128, prefix_cache_mb=0,
                                 speculative=True)
    assert ContinuousBatchingEngine(ours, CONFIG, max_slots=2, capacity=128).prefix_cache is None


# ---- the shares add up ----


def test_the_two_shares_and_the_shared_expert_once_are_the_uncut_layer(weights):
    """The expert layer's routed output for the held ranges [0, E/2) and
    [E/2, E), plus the gated shared expert counted once, is the uncut
    reference's layer output: the cut ties to the whole layer."""
    whole = dict(MODEL, num_experts=8, first_expert=0)
    theirs = ref.make_weights(whole, 0, jax.devices())
    layer = {name: theirs[name][3].astype(jnp.float32) for name in
             ("router", "w_gate", "w_up", "w_down", "w_shared_gate", "w_shared_up", "w_shared_down",
              "shared_expert_gate")}
    # rows of unit rms: ``_mlp_block`` norms its input, and such a row is its own norm
    x = jnp.asarray(np.random.default_rng(9).normal(size=(50, 64)), jnp.float32)
    x = x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True))
    same = lambda w: w  # noqa: E731
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref.moe(x, layer, whole, same))
        routed = np.asarray(ref.moe(x, layer, whole, same, shared=False))

    def share(first, with_shared):
        config = dataclasses.replace(CONFIG, n_experts_held=4, first_expert=first)
        lp = {name: (leaf[first : first + 4] if name in ("w_gate", "w_up", "w_down") else leaf)
              for name, leaf in layer.items()
              if with_shared or not name.startswith(("w_shared", "shared_expert"))}
        lp["mlp_norm"] = jnp.zeros((64,), jnp.float32)
        out, _, stats = _experts(x[None], lp, config=config, dropless=True)
        return np.asarray(out[0] - x), np.asarray(stats)

    low, low_stats = share(0, True)
    high, high_stats = share(4, False)
    assert np.abs(low + high - want).max() < 1e-5
    assert np.abs(routed - want).max() > 100 * 1e-5  # the shared expert is not nothing
    # every pick is local to exactly one of the two shares
    assert low_stats[0] + high_stats[0] == 50 * 3 and low_stats[2] == high_stats[0]


# ---- the engine: the same slots, admission, chunk prefill, finalize and decode ----


def _engine(ours, slots=2, **kwargs):
    return ContinuousBatchingEngine(
        ours, CONFIG, max_slots=slots, capacity=256, chunk=4, prefill_chunk=64,
        prefix_cache_mb=0, **kwargs,
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
    reference's best, at worst: 0 where every served token is the argmax."""
    logits = np.asarray(ref.score(MODEL, theirs, prompt, answer, 256, 64))
    return float((logits.max(axis=-1) - logits[np.arange(len(answer)), answer]).max())


def test_two_requests_of_one_wave_match_each_alone_and_the_reference(weights):
    """Lengths 150 and 21 in one admission wave: three chunks of 64 for the one
    (the last padded), one padded chunk of 32 for the other, then finalize
    copies each row's state and tail whole into its slot."""
    ours, theirs = weights
    first, second = _tokens(30, 150), _tokens(31, 21)
    engine = _engine(ours)
    a = engine.submit(first, max_new_tokens=20, temperature=0.0)
    b = engine.submit(second, max_new_tokens=30, temperature=0.0)
    together = _run(engine, [a, b])
    assert [len(t) for t in together] == [20, 30]
    for prompt, answer in zip((first, second), together):
        assert _gap(theirs, prompt, answer) < TOL
    # each alone, in a slot the other wave left its state in: the same tokens
    for prompt, answer in zip((first, second), together):
        request = engine.submit(prompt, max_new_tokens=len(answer), temperature=0.0)
        assert _run(engine, [request])[0] == answer
    values = engine.registry.values()
    assert values["serve_state_resets_total"] == values["serve_requests_admitted_total"] == 4


def _slot_leaves(engine, slot, length):
    """What slot ``slot`` holds of a request of ``length`` positions: its K/V
    columns, and its state and tail whole."""
    out = {}
    for name, (leaf, spec) in cache_leaves(engine._cache).items():
        part = np.take(np.asarray(leaf), slot, axis=spec.slot_axis)
        if spec.capacity_axis is not None:
            axis = spec.capacity_axis - (spec.capacity_axis > spec.slot_axis)
            part = np.take(part, np.arange(length), axis=axis)
        out[name] = part
    return out


def _chunk_counts(engine):
    tokens = {s["labels"]["kind"]: s["value"]
              for s in engine.registry.snapshot()["serve_prefill_chunk_tokens_total"]["series"]}
    return engine.registry.values()["serve_prefill_chunks_total"], tokens["prompt"], tokens["pad"]


def test_one_chunk_a_prompt_is_the_split_prompt_and_the_counters_add_up(weights):
    """A 150-token prompt as ONE chunk of 256 (the cap of a whole slot) against
    three chunks of 64: the same first tokens and answer, the same K/V columns,
    matrix state and conv tail in the slot within the chunk-invariance
    tolerance, and ``serve_prefill_chunk_tokens_total`` adds up to the prompt
    and the plan's fill."""
    ours, theirs = weights
    prompt = _tokens(60, 150)
    served = {}
    for cap in (256, 64):
        engine = ContinuousBatchingEngine(
            ours, CONFIG, max_slots=2, capacity=256, chunk=4, prefill_chunk=cap, prefix_cache_mb=0,
        )
        request = engine.submit(prompt, max_new_tokens=9, temperature=0.0)
        answer = _run(engine, [request])[0]
        # 150 prompt positions and the 8 answer tokens whose columns were written
        served[cap] = (answer, _slot_leaves(engine, request.slot, 150 + 8), _chunk_counts(engine))
    (whole, whole_leaves, whole_counts), (split, split_leaves, split_counts) = served[256], served[64]
    assert whole == split and _gap(theirs, prompt, whole) < TOL
    assert sorted(whole_leaves) == ["conv_tail", "delta_state", "k", "v"]
    for name, leaf in whole_leaves.items():
        assert np.abs(leaf).max() > 0.01, name
        assert np.abs(leaf - split_leaves[name]).max() < TOL * max(1.0, np.abs(leaf).max()), name
    assert whole_counts == (1, 150, 256 - 150)
    assert split_counts == (3, 150, 3 * 64 - 150)


@pytest.mark.parametrize("max_wave, chunk", [(1, 1024), (None, 512)])
def test_an_engine_that_gives_width_up_gets_length(weights, max_wave, chunk):
    """The rule in a live engine: 64 published experts at top-3 give a held
    expert 24 rows of a 512-token chunk, under one row tile, so a prompt a wave
    (``max_wave`` 1) is cut at 1024 (48 rows); at the width the shapes allow
    (128 rows a wave here) the score budget is spent, and the chunk stays 512."""
    ours, _ = weights
    config = dataclasses.replace(CONFIG, n_experts=64)
    params = {**ours, "layers": {**ours["layers"], "router": jnp.zeros((8, 64, 64), jnp.float32)}}
    engine = ContinuousBatchingEngine(
        params, config, max_slots=2, capacity=1024, chunk=4, prefix_cache_mb=0, max_wave=max_wave,
    )
    assert (engine.prefill_chunk, engine.max_wave) == (chunk, 128 if max_wave is None else 1)
    assert row_capacities(engine.prefill_chunk, 1024) == [16, 32, 64, 128, 256, 512, 1024]


def test_a_prompt_too_long_for_its_slot_is_served_and_scored_on_its_tail(weights):
    """A prompt two ids too long for the slot beside its answer (the corner of
    the document-chat mix's clips once the template's role words are on): the
    server keeps the last ``capacity - max_tokens`` ids, and the reference,
    given the whole prompt as the benchmark's record holds it, scores the same
    tail. Fault: scored on its head, the served tokens lie logits below."""
    from benchmark.harness.tokenizer import NumericTokenizer
    from prime_tpu.serve.engine import EngineBackend

    ours, theirs = weights
    backend = EngineBackend(_engine(ours, slots=1), NumericTokenizer(eos_id=-1))
    prompt = _tokens(50, 238)  # 238 + 20 = the slot's 256 and two more
    request = backend.submit_text(" ".join(map(str, prompt)), 20, temperature=0.0)
    answer = _run(backend.engine, [request])[0]
    assert len(answer) == 20 and _gap(theirs, prompt, answer) < TOL
    logits = np.asarray(ref.logits_at(
        MODEL, theirs, np.asarray(prompt + answer, np.int32)[:256],
        np.arange(237, 256, dtype=np.int32)))
    at_head = logits.max(axis=-1) - logits[np.arange(19), answer[:19]]
    assert float(at_head.max()) > 100 * TOL


def test_a_retired_slots_state_never_reaches_the_next_tenant(weights):
    """One slot: a long tenant, decode chunks for nobody, then a short tenant,
    served as the reference serves it alone: its matrix state starts from zero."""
    ours, theirs = weights
    engine = _engine(ours, slots=1)
    _run(engine, [engine.submit(_tokens(40, 90), max_new_tokens=24, temperature=0.0)])
    state_then = np.asarray(engine._cache.delta_state)
    for _ in range(3):  # retired rows change nothing: no live row, no state moves
        engine._dispatch_decode()
        engine._sync_decode()
    assert np.array_equal(np.asarray(engine._cache.delta_state), state_then) and state_then.any()
    prompt = _tokens(41, 5)
    new = engine.submit(prompt, max_new_tokens=12, temperature=0.0)
    answer = _run(engine, [new])[0]
    assert _gap(theirs, prompt, answer) < TOL
    values = engine.registry.values()
    assert values["serve_state_resets_total"] / values["serve_requests_admitted_total"] == 1.0


def test_the_engine_counts_the_state_it_holds_and_the_experts_it_ran(weights, step_impl):
    ours, _ = weights
    engine = _engine(ours, attn_impl=step_impl)
    snapshot = engine.registry.snapshot()
    by_kind = {s["labels"]["kind"]: s["value"] for s in snapshot["serve_cache_bytes"]["series"]}
    held = cache_leaves(engine._cache)
    for kind in ("full", "state"):
        assert by_kind[kind] == sum(leaf.nbytes for leaf, spec in held.values() if spec.kind == kind)
    # 6 linear layers x 2 slots x (4 heads x 16 x 16 float32 + 3 x 128 float32 tail columns)
    assert by_kind["state"] == 6 * 2 * (4 * 16 * 16 * 4 + 3 * 128 * 4) and by_kind["ring"] == 0
    _run(engine, [engine.submit(_tokens(50, 21), max_new_tokens=9, temperature=0.0)])
    pairs = {(s["labels"]["kind"], s["labels"]["phase"]): s["value"]
             for s in engine.registry.snapshot()["serve_moe_pairs_total"]["series"]}
    # 21 prompt tokens x 8 layers x top-3, each pick local or elsewhere; no zero experts
    assert pairs[("local", "prefill")] + pairs[("elsewhere", "prefill")] == 21 * 8 * 3
    assert pairs[("local", "decode")] > 0 and pairs[("zero", "decode")] == 0
    # the state kernel's steps, of slots x head blocks (1) x 6 linear layers x decode
    # steps: the live rows' share of them on the kernel's path, none as plain XLA
    steps, rows = (engine.registry.get(f"serve_{name}_total")
                   for name in ("delta_state_steps", "decode_rows"))
    walked, spared = steps.value(kind="walked"), steps.value(kind="spared")
    if step_impl == "pallas":
        # (rows are counted as a chunk is dispatched, steps as it is synced: shares)
        live, retired = rows.value(state="live"), rows.value(state="retired")
        assert walked > 0 and walked % (6 * engine.chunk) == 0
        assert walked / (walked + spared) == live / (live + retired) == 0.5
    else:
        assert (walked, spared) == (0, 0)


# ---- the tolerance tells each named fault ----


FAULTS = {
    "shared-gate-dropped": dict(patch=("_shared_gate", lambda score: 1.0)),
    "output-gate-dropped": dict(patch=("_output_gate", lambda gate: 1.0)),
    "one-plus-w-as-w": dict(patch=("_norm", lambda x, eps, w=0.0: x * jax.lax.rsqrt(
        jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w)),
    "rotary-on-the-whole-head": dict(model={"partial_rotary_factor": 1.0}),
    "qk-norm-dropped": dict(patch=("_head_norm", lambda t, eps: t)),
    "delta-keys-not-unit": dict(patch=("_unit", lambda t: t)),
    "g-without-dt-bias": dict(zero="delta.dt_bias"),
    "topk-not-renormalised": dict(model={"norm_topk_prob": False}),
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
    assert np.abs(faulty - clean).max() > 100 * TOL, fault
