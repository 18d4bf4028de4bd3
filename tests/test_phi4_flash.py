"""The sambay block kind (Phi-4-mini-flash): state-space layers, window
attention over a ring, one full layer's K/V shared by the cross layers, gated
memory units, differential attention: the program against the plain reference
``benchmark/reference/phi4_flash.py`` at the tiny preset of the same block
kind (8 layers: each of the six layer kinds is there; window 8), seeded random
weights, on the CPU.

Everything here runs in float32 (the CPU's float32 matmul is exact float32),
so program and reference differ only by the order of their sums: the tolerance
``TOL`` is 1e-4 on logits of magnitude 1-4, six times the 1.7e-5 they were
seen to differ by at most (40 positions, ring wrapped four times). Each named
fault moves the reference's own logits by a hundred times ``TOL`` or more
(``test_the_tolerance_tells_each_fault``), so a program that had it could not
pass.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.reference import phi4_flash as ref
from prime_tpu.models import get_config
from prime_tpu.models.llama import CACHE_LEAVES, forward, init_cache, init_params
from prime_tpu.serve.engine import ContinuousBatchingEngine, cache_leaves

TOL = 1e-4
CONFIG = get_config("tiny-phi4flash")
WINDOW = CONFIG.sliding_window
# the configuration file's keys for the tiny preset (the cell's rehearsal model)
MODEL = dict(
    hidden_size=64, intermediate_size=128, num_hidden_layers=8, num_attention_heads=4,
    num_key_value_heads=2, sliding_window=8, vocab_size=512, layer_norm_eps=1e-5,
    ssm_expand=2, ssm_d_state=4, ssm_d_conv=4, ssm_dt_rank=4,
)


@pytest.fixture(scope="module")
def weights():
    """The reference's own weights (bf16-rounded values) and the program's,
    leaf for leaf the same, the program's widened to float32."""
    ours = init_params(jax.random.PRNGKey(0), CONFIG, dtype=jnp.bfloat16)
    theirs = ref.make_weights(MODEL, 0, jax.devices())
    pairs = {"window": "layers", "middle": "mid_layers", "cross": "cross_layers"}
    for group, stack in pairs.items():
        for mixer, layer in zip(("ssm", "attn") if group != "cross" else ("gmu", "attn"),
                                theirs[group]):
            assert set(layer) == set(ours[stack][mixer]), (group, mixer)
            for name, value in layer.items():
                mine = ours[stack][mixer][name]
                assert mine.dtype == value.dtype and bool(jnp.all(mine == value)), (group, name)
    for name in ("embed", "final_norm", "final_norm_bias"):
        assert bool(jnp.all(ours[name] == theirs[name]))
    return jax.tree.map(lambda x: x.astype(jnp.float32), ours), theirs


def _tokens(seed, count):
    return np.random.default_rng(seed).integers(3, 500, size=count).tolist()


def _reference(theirs, tokens, model=MODEL):
    """Float32 logits at every position of one sequence's full pass."""
    return np.asarray(ref.logits_at(model, theirs, np.asarray(tokens), np.arange(len(tokens))))


def _one(tokens):
    return jnp.asarray([tokens], jnp.int32)


# one program a shape (an eager ``forward`` traces its layer scans anew at every call)
@jax.jit
def _decode(params, column, cache):
    return forward(params, column, CONFIG, cache=cache, decode=True)


@jax.jit
def _chunk(params, tokens, cache, offset):
    return forward(params, tokens, CONFIG, cache=cache, prefill_offset=offset)


@jax.jit
def _chunk_at_last(params, tokens, cache, offset, last, live):
    return forward(params, tokens, CONFIG, cache=cache, prefill_offset=offset,
                   last_positions=last, token_mask=live)


def test_the_preset_has_the_published_count_and_the_cache_its_four_kinds_of_leaf():
    served = get_config("phi4-mini-flash")
    assert served.param_count == 3_852_562_944  # the published "3.8B"
    assert (served.window_pairs, served.cross_pairs, served.cross_half_layers) == (8, 7, 14)
    assert (served.d_inner, served.dt_rank, served.head_dim) == (5120, 160, 64)
    cache = init_cache(CONFIG, 3, 128)
    # one full row; a ring of WINDOW columns a window layer, whatever the capacity;
    # float32 state and a conv tail a state-space layer
    assert cache.k.shape == cache.v.shape == (1, 3, 1, 32, 128)
    assert cache.ring_k.shape == cache.ring_v.shape == (2, 3, 1, 32, WINDOW)
    assert cache.ssm_state.shape == (3, 3, 4, 128) and cache.ssm_state.dtype == jnp.float32
    assert cache.conv_tail.shape == (3, 3, 3, 128)
    held = cache_leaves(cache)
    assert {name: spec.kind for name, (_, spec) in held.items()} == {
        "k": "full", "v": "full", "ring_k": "ring", "ring_v": "ring",
        "ssm_state": "state", "conv_tail": "state",
    }
    assert all(spec.slot_axis == 1 for _, spec in held.values())
    # the other block kinds' caches hold K/V rows and nothing else
    plain = cache_leaves(init_cache(get_config("tiny-test"), 2, 64))
    assert set(plain) == {"k", "v"} and set(CACHE_LEAVES) >= set(plain)


def test_forward_without_a_cache_matches_the_reference(weights):
    ours, theirs = weights
    tokens = _tokens(1, 40)
    want = _reference(theirs, tokens)
    got, cache = forward(ours, _one(tokens), CONFIG)
    assert cache is None
    assert np.abs(np.asarray(got[0]) - want).max() < TOL
    # one position a row: the cross half runs there alone, with the same result
    last, _ = forward(ours, _one(tokens), CONFIG, last_positions=jnp.asarray([29]))
    assert np.abs(np.asarray(last[0, 0]) - want[29]).max() < TOL


def test_bf16_weights_fail_the_tolerance(weights):
    """The comparison is tight enough that serving in bfloat16 fails it."""
    _, theirs = weights
    tokens = _tokens(1, 40)
    low = init_params(jax.random.PRNGKey(0), CONFIG, dtype=jnp.bfloat16)
    got, _ = forward(low, _one(tokens), CONFIG)
    assert np.abs(np.asarray(got[0]) - _reference(theirs, tokens)).max() > 100 * TOL


def test_prefill_then_decode_wraps_the_ring_more_than_twice(weights):
    """A prompt, then 3 x window decode steps through the cache, against the
    reference's one full pass."""
    ours, theirs = weights
    prompt = 13
    tokens = _tokens(2, prompt + 3 * WINDOW + 1)
    want = _reference(theirs, tokens)
    cache = init_cache(CONFIG, 1, 64, dtype=jnp.float32)
    logits, cache = forward(ours, _one(tokens[:prompt]), CONFIG, cache=cache)
    worst = np.abs(np.asarray(logits[0]) - want[:prompt]).max()
    for at in range(prompt, len(tokens)):
        logits, cache = _decode(ours, _one(tokens[at : at + 1]), cache)
        worst = max(worst, np.abs(np.asarray(logits[0, 0]) - want[at]).max())
    assert int(cache.lengths[0]) == len(tokens) > prompt + 2 * WINDOW
    assert worst < TOL


@pytest.mark.parametrize(
    "sizes",
    [
        (3, 3, 3, 3, 3, 3),        # chunk < window, offsets straddle the ring's end (6 -> 9)
        (8, 8, 8, 8),              # chunk = window
        (12, 12, 12),              # chunk > window
        (5, 16, 3, 8, 1),          # mixed, a chunk of one token among them
        (1,),                      # a prompt of one token
    ],
    ids=["below-window", "window", "above-window", "mixed", "one-token"],
)
def test_chunked_prefill_at_offsets_then_decode(weights, sizes):
    ours, theirs = weights
    total = sum(sizes)
    tokens = _tokens(3, total + WINDOW + 2)
    want = _reference(theirs, tokens)
    cache = init_cache(CONFIG, 1, 64, dtype=jnp.float32)
    offset, worst = 0, 0.0
    for size in sizes:
        logits, cache = _chunk(
            ours, _one(tokens[offset : offset + size]), cache, jnp.asarray(offset, jnp.int32)
        )
        worst = max(worst, np.abs(np.asarray(logits[0]) - want[offset : offset + size]).max())
        offset += size
    for at in range(total, len(tokens)):
        logits, cache = _decode(ours, _one(tokens[at : at + 1]), cache)
        worst = max(worst, np.abs(np.asarray(logits[0, 0]) - want[at]).max())
    assert worst < TOL


def test_a_chunks_padding_leaves_ring_state_and_tail_alone(weights):
    """The engine's last chunk is padded past a row's last token: with the
    padding masked, the rows' next steps are those of unpadded prompts."""
    ours, theirs = weights
    lengths = (21, 27)  # two rows of one wave: 16 + 5 and 16 + 11 real tokens
    rows = [_tokens(10 + i, n + 6) for i, n in enumerate(lengths)]
    want = [_reference(theirs, row) for row in rows]
    cache = init_cache(CONFIG, 2, 64, dtype=jnp.float32)
    first = jnp.asarray([row[:16] for row in rows], jnp.int32)
    _, cache = _chunk_at_last(ours, first, cache, jnp.asarray(0, jnp.int32),
                              jnp.asarray([15, 15]), jnp.ones((2, 16), bool))
    padded = np.zeros((2, 16), np.int32)
    for i, n in enumerate(lengths):
        padded[i, : n - 16] = rows[i][16:n]
    last = jnp.asarray([n - 17 for n in lengths])
    live = jnp.arange(16)[None, :] <= last[:, None]
    logits, cache = _chunk_at_last(ours, jnp.asarray(padded), cache, jnp.asarray(16, jnp.int32),
                                   last, live)
    worst = max(np.abs(np.asarray(logits[i, 0]) - want[i][n - 1]).max()
                for i, n in enumerate(lengths))
    cache = cache._replace(lengths=jnp.asarray(lengths, jnp.int32))  # as finalize sets them
    for step in range(6):
        column = jnp.asarray([[rows[i][n + step]] for i, n in enumerate(lengths)], jnp.int32)
        logits, cache = _decode(ours, column, cache)
        worst = max(worst, *(np.abs(np.asarray(logits[i, 0]) - want[i][n + step]).max()
                             for i, n in enumerate(lengths)))
    assert worst < TOL


def test_a_row_that_belongs_to_nobody_leaves_the_others_bit_identical(weights):
    ours, _ = weights
    rows = jnp.asarray([_tokens(20, 12), _tokens(21, 12)], jnp.int32)
    cache = init_cache(CONFIG, 2, 64, dtype=jnp.float32)
    _, cache = forward(ours, rows, CONFIG, cache=cache)
    column = jnp.asarray([[7], [9]], jnp.int32)
    plain, _ = forward(ours, column, CONFIG, cache=cache, decode=True)
    masked, after = forward(ours, column, CONFIG, cache=cache, decode=True,
                            token_mask=jnp.asarray([[True], [False]]))
    assert np.array_equal(np.asarray(plain[0]), np.asarray(masked[0]))
    assert not np.array_equal(np.asarray(plain[1]), np.asarray(masked[1]))
    assert bool(jnp.all(jnp.isfinite(masked)))
    assert after.lengths.tolist() == [13, 13]  # the engine, not forward, holds a dead row's length


def test_a_per_row_offset_a_mesh_and_a_quantized_cache_are_refused(weights):
    ours, _ = weights
    cache = init_cache(CONFIG, 2, 64, dtype=jnp.float32)
    with pytest.raises(ValueError, match="one chunk offset"):
        forward(ours, jnp.zeros((2, 4), jnp.int32), CONFIG, cache=cache,
                prefill_offset=jnp.asarray([0, 3], jnp.int32))
    with pytest.raises(ValueError, match="kv_quant is unsupported"):
        init_cache(CONFIG, 2, 64, quantized=True)
    mesh = jax.make_mesh((2,), ("tp",))
    with pytest.raises(ValueError, match="not laid out for a mesh"):
        forward(ours, jnp.zeros((2, 4), jnp.int32), CONFIG, mesh=mesh)


# ---- the engine: the same slots, admission, chunk prefill, finalize and decode ----


def _engine(ours, slots=2, **kwargs):
    return ContinuousBatchingEngine(
        ours, CONFIG, max_slots=slots, capacity=128, chunk=4, prefill_chunk=16,
        prefix_cache_mb=0, **kwargs,
    )


def _run(engine, requests, ticks=400):
    for _ in range(ticks):
        engine.tick()
        if all(r.done for r in requests):
            break
    assert all(r.done and r.error is None for r in requests)
    return [r.all_tokens(timeout=1) for r in requests]


def _gap(theirs, prompt, answer):
    """By how much the served tokens' reference logits lie below the
    reference's best, at worst: 0 where every served token is the argmax."""
    logits = np.asarray(ref.score(MODEL, theirs, prompt, answer, 128, 64))
    return float((logits.max(axis=-1) - logits[np.arange(len(answer)), answer]).max())


def test_two_requests_admitted_at_different_ticks_match_each_alone(weights):
    ours, theirs = weights
    first, second = _tokens(30, 37), _tokens(31, 5)
    engine = _engine(ours)
    a = engine.submit(first, max_new_tokens=30, temperature=0.0)
    for _ in range(3):
        engine.tick()
    assert not a.done
    b = engine.submit(second, max_new_tokens=3 * WINDOW + 2, temperature=0.0)
    together = _run(engine, [a, b])
    assert [len(t) for t in together] == [30, 3 * WINDOW + 2]
    for prompt, answer in zip((first, second), together):
        assert _gap(theirs, prompt, answer) < TOL
    for prompt, answer, budget in ((first, together[0], 30), (second, together[1], 3 * WINDOW + 2)):
        fresh = _engine(ours)
        request = fresh.submit(prompt, max_new_tokens=budget, temperature=0.0)
        assert _run(fresh, [request])[0] == answer
    values = engine.registry.values()
    assert values["serve_state_resets_total"] == 2
    # decode steps at positions >= the ring's width overwrote a live column
    assert values["serve_ring_wraps_total"] > 0


def test_a_retired_slots_state_never_reaches_the_next_tenant(weights):
    """One slot: a long tenant (ring wrapped, state run on), then a short one
    whose prompt is shorter than the ring. The second is served as by a fresh
    engine: its state starts from zero and the ring's old columns are dead."""
    ours, theirs = weights
    engine = _engine(ours, slots=1)
    old = engine.submit(_tokens(40, 45), max_new_tokens=24, temperature=0.0)
    _run(engine, [old])
    for _ in range(3):  # decode chunks for nobody run the retired row's state on
        engine._dispatch_decode()
        engine._sync_decode()
    prompt = _tokens(41, 3)
    new = engine.submit(prompt, max_new_tokens=12, temperature=0.0)
    answer = _run(engine, [new])[0]
    assert _gap(theirs, prompt, answer) < TOL
    fresh = _engine(ours, slots=1)
    assert _run(fresh, [fresh.submit(prompt, max_new_tokens=12, temperature=0.0)])[0] == answer


def test_the_engine_counts_what_the_slots_hold_and_what_a_prefill_computed(weights):
    ours, _ = weights
    engine = _engine(ours)
    snapshot = engine.registry.snapshot()
    by_kind = {s["labels"]["kind"]: s["value"] for s in snapshot["serve_cache_bytes"]["series"]}
    held = cache_leaves(engine._cache)
    for kind in ("full", "ring", "state"):
        assert by_kind[kind] == sum(leaf.nbytes for leaf, spec in held.values() if spec.kind == kind)
    assert by_kind["ring"] == 2 * 2 * 2 * 32 * WINDOW * 4  # leaves x layers x slots x width x columns x f32
    request = engine.submit(_tokens(50, 21), max_new_tokens=2, temperature=0.0)
    _run(engine, [request])
    series = engine.registry.snapshot()["serve_prefill_layer_tokens_total"]["series"]
    tokens = {s["labels"]["half"]: s["value"] for s in series}
    # 21 prompt tokens through the 6 layers that keep K/V or state; the 2 cross
    # layers at the last position of each of the prompt's two chunks
    assert tokens == {"self": 21 * 6, "cross": 2 * 2}


def test_the_engine_refuses_a_prefix_cache_and_speculation_for_state_in_the_slots(weights):
    ours, _ = weights
    with pytest.raises(ValueError, match="slots hold a ring.*and no ring"):
        ContinuousBatchingEngine(ours, CONFIG, max_slots=2, capacity=128, prefix_cache_mb=8)
    with pytest.raises(ValueError, match="cannot rewind"):
        ContinuousBatchingEngine(ours, CONFIG, max_slots=2, capacity=128, prefix_cache_mb=0,
                                 speculative=True)
    # the default budget does not apply to such a model: no cache, no error
    assert ContinuousBatchingEngine(ours, CONFIG, max_slots=2, capacity=128).prefix_cache is None


# ---- the tolerance tells each named fault ----


def _halves(j, heads, kv_heads):
    g = j // 2
    return (j, j + heads // 2), (g, g + kv_heads // 2)


FAULTS = {
    "memory-after-the-gate": dict(patch=("_memory_of", lambda y, z: y * jax.nn.silu(z))),
    "d-dropped": dict(zero="D"),
    "conv-bias-dropped": dict(zero="conv_b"),
    "window-one-short": dict(model={"sliding_window": WINDOW - 1}),
    "window-one-long": dict(model={"sliding_window": WINDOW + 1}),
    "lambda-init-of-the-layer-below": dict(
        patch=("_lambda_init", lambda index: 0.8 - 0.6 * jnp.exp(-0.3 * (index - 2)))),
    "last-window-layers-kv-shared": dict(patch=("_shared_layer", lambda layers: layers // 2 - 1)),
    "pairs-by-halves": dict(patch=("_pair_heads", _halves)),
    "norm-scale-dropped": dict(patch=("_norm_scale", lambda lam_init: 1.0)),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_the_tolerance_tells_each_fault(weights, monkeypatch, fault):
    _, theirs = weights
    tokens = _tokens(60, 40)
    jax.clear_caches()  # the reference's jitted layers are traced anew, with or without a fault
    clean = _reference(theirs, tokens)
    spec = FAULTS[fault]
    faulty_weights = theirs
    if "zero" in spec:
        faulty_weights = {
            **theirs,
            **{group: tuple({**layer, spec["zero"]: jnp.zeros_like(layer[spec["zero"]])}
                            if spec["zero"] in layer else layer for layer in theirs[group])
               for group in ("window", "middle")},
        }
    if "patch" in spec:
        monkeypatch.setattr(ref, *spec["patch"])
    jax.clear_caches()
    try:
        faulty = _reference(faulty_weights, tokens, {**MODEL, **spec.get("model", {})})
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    assert np.abs(faulty - clean).max() > 100 * TOL, fault
