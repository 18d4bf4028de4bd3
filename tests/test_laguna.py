"""The standard block over layers of more than one kind (Laguna): full attention
on K/V rows beside window attention on rings, at two head counts and two rotary
settings, per-head output gates, a dense first layer and a share of the experts
beside a shared expert: the program against the plain reference
``benchmark/reference/laguna_s.py`` at the tiny preset (9 layers: the dense
layer 0 and two whole periods; 4 and 6 query heads over 2 K/V heads, a window
of 8, 8 experts of which experts 2-5 are held, top-3), seeded random weights, on
the CPU.

Everything here runs in float32 (the CPU's float32 matmul is exact float32), so
program and reference differ only by the order of their sums: the tolerance
``TOL`` is 1e-4 on logits of magnitude 1-5, some ten times the 1e-5 they were
seen to differ by at most. Each named fault moves the logits by a hundred times
``TOL`` or more (``test_the_tolerance_tells_each_fault``), so a program that had
it could not pass.
"""

import dataclasses
import functools
import json
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.reference import laguna_s as ref
from prime_tpu.models import get_config
from prime_tpu.models.llama import CACHE_LEAVES, _mlp_block, forward, init_cache, init_params
from prime_tpu.serve.engine import ContinuousBatchingEngine, cache_leaves, prefill_shapes

TOL = 1e-4
CONFIG = get_config("tiny-laguna")
FILE = json.loads((Path(__file__).resolve().parent.parent / "benchmark/configs/laguna-s-ep2.json").read_text())
# the configuration file's keys for the tiny preset (the cell's rehearsal model)
MODEL = FILE["rehearsal"]["model"]
WINDOW = CONFIG.sliding_window


def _flat(ours):
    """The program's leaves under the reference's names."""
    stack = ours["layers"]
    return {
        **{f"{kind}.{name}": leaf for kind in ("full", "window", "dense", "sparse")
           for name, leaf in stack[kind].items()},
        "embed": ours["embed"], "lm_head": ours["lm_head"],
    }


@pytest.fixture(scope="module")
def weights():
    """The reference's own weights (bf16-rounded values) and the program's,
    leaf for leaf the same, the program's widened to float32."""
    # (one program a recipe: a draw a leaf is one small compile a leaf otherwise)
    ours = jax.jit(lambda: init_params(jax.random.PRNGKey(0), CONFIG, dtype=jnp.bfloat16))()
    theirs = jax.jit(lambda: ref.make_weights(MODEL, 0, jax.devices()))()
    mine = _flat(ours)
    assert set(mine) == set(theirs)
    for name, value in theirs.items():
        assert mine[name].dtype == value.dtype and bool(jnp.all(mine[name] == value)), name
    # what the reference states and does not store: norm weights one
    for norm in (ours["layers"]["attn_norm"], ours["layers"]["mlp_norm"], ours["final_norm"]):
        assert bool(jnp.all(norm == 1))
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
def _chunk(params, tokens, cache, offset, live):
    return forward(params, tokens, CONFIG, cache=cache, prefill_offset=offset, token_mask=live)


@pytest.fixture(scope="module")
def full_pass(weights):
    """One sequence of eleven windows and the reference's logits at each position."""
    tokens = _tokens(1, 90)
    return tokens, _reference(weights[1], tokens)


_experts = jax.jit(_mlp_block, static_argnames=("config", "dropless"))

# the decode step's two paths: plain XLA (what the CPU backend takes by itself),
# and the kernels a chip takes (the column write and flash-decode over rows and
# rings, at query groups of 2 and 3), interpreted
STEP_PATHS = {"xla-step": "auto", "kernel-step": "pallas"}


@pytest.fixture(params=sorted(STEP_PATHS))
def step_impl(request, monkeypatch):  # (or one path, named by an indirect parameter)
    if request.param == "kernel-step":
        monkeypatch.setenv("PRIME_TPU_PALLAS_INTERPRET", "1")
    return STEP_PATHS[request.param]


def test_the_presets_state_the_kinds_and_the_cache_holds_rows_and_rings():
    served = get_config("laguna-s-ep2")
    model = FILE["model"]
    # 11.14 GB of bfloat16: the dense layer 0 and one whole period at the published
    # widths, 128 of 256 experts, half the vocabulary
    assert served.param_count == 5_572_076_544
    assert (served.d_model, served.n_heads, served.window_heads, served.n_kv_heads, served.head_dim) == (
        3072, 48, 72, 8, 128)
    assert (served.sliding_window, served.dense_ff, served.d_ff, served.shared_ff) == (512, 12288, 1024, 1024)
    assert (served.n_experts, served.experts_held, served.experts_per_token) == (256, 128, 10)
    assert served.routed_scaling_factor == 2.5 and served.attn_head_gate
    full, sliding = (model["rope_parameters"][k] for k in ("full_attention", "sliding_attention"))
    assert (served.rope_theta, served.partial_rotary, served.rope_local_theta) == (
        full["rope_theta"], full["partial_rotary_factor"], sliding["rope_theta"])
    assert served.rope_yarn == (full["factor"], full["beta_fast"], full["beta_slow"],
                                full["original_max_position_embeddings"], full["attention_factor"])
    # the lists are the published config's own, cut with the depth
    assert list(served.layer_types) == model["layer_types"]
    assert list(served.mlp_layer_types) == model["mlp_layer_types"]
    assert list(served.heads_per_layer) == model["num_attention_heads_per_layer"]
    assert (served.cache_rows, served.full_layers, served.window_layers) == (2, 2, 3)
    assert list(CONFIG.layer_types) == MODEL["layer_types"]
    assert list(CONFIG.heads_per_layer) == MODEL["num_attention_heads_per_layer"]
    cache = init_cache(CONFIG, 3, 128)
    # a K/V row a FULL layer, a ring of `window` columns a SLIDING one
    assert cache.k.shape == cache.v.shape == (3, 3, 2, 16, 128)
    assert cache.ring_k.shape == cache.ring_v.shape == (6, 3, 2, 16, WINDOW)
    held = cache_leaves(cache)
    assert {name: spec.kind for name, (_, spec) in held.items()} == {
        "k": "full", "v": "full", "ring_k": "ring", "ring_v": "ring",
    }
    assert set(CACHE_LEAVES) >= set(held)
    params = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), CONFIG))
    stack = params["layers"]
    # stacks a kind: the experts HELD, never the router's width; each attention at its own head count
    assert stack["sparse"]["w_gate"].shape == (8, 4, 64, 32) and stack["sparse"]["router"].shape == (8, 64, 8)
    assert stack["full"]["wq"].shape == (3, 64, 4 * 16) and stack["window"]["wq"].shape == (6, 64, 6 * 16)
    assert stack["window"]["w_head_gate"].shape == (6, 64, 6) and stack["dense"]["w_up"].shape == (1, 64, 128)
    assert sum(leaf.size for leaf in jax.tree.leaves(params)) == CONFIG.param_count


def test_the_published_lists_build_the_48_layer_model_at_tiny_widths():
    """The walk is over the config's own lists, not a five-layer special case:
    the published 48 entries give three runs (layer 0; eleven periods of
    window x 3 + full; three window layers) and the model runs."""
    layer_types = (("full_attention",) + ("sliding_attention",) * 3) * 12  # FILE["published"]
    mlp = ("dense",) + ("sparse",) * 47
    config = dataclasses.replace(CONFIG, n_layers=48, layer_types=layer_types, mlp_layer_types=mlp)
    assert config.kind_segments == ((0, 1, 1), (1, 4, 11), (45, 1, 3))
    assert (config.full_layers, config.window_layers, config.cache_rows) == (12, 36, 12)
    make = lambda: init_params(jax.random.PRNGKey(0), config, dtype=jnp.float32)  # noqa: E731
    assert sum(leaf.size for leaf in jax.tree.leaves(jax.eval_shape(make))) == config.param_count
    logits = jax.jit(lambda t: forward(make(), t, config)[0])(_one(_tokens(3, 20)))
    assert logits.shape == (1, 20, 512) and bool(jnp.all(jnp.isfinite(logits)))
    # and the served cut is that model's first five layers, by the same lists
    served = get_config("laguna-s-ep2")
    assert served.layer_types == layer_types[:5] and served.mlp_layer_types == mlp[:5]


def test_forward_without_a_cache_matches_the_reference(weights, full_pass):
    ours, _ = weights
    tokens, want = full_pass
    got = _plain(ours, _one(tokens))
    assert np.abs(np.asarray(got[0]) - want).max() < TOL


def test_bf16_weights_fail_the_tolerance(weights, full_pass):
    """The comparison is tight enough that serving in bfloat16 fails it."""
    tokens, want = full_pass
    low = jax.tree.map(lambda x: x.astype(jnp.bfloat16), weights[0])  # as they were drawn
    got = _plain(low, _one(tokens))
    assert np.abs(np.asarray(got[0]) - want).max() > 100 * TOL


def _chunks_then_decode(ours, want, tokens, sizes, attn_impl="auto", pad=0, masked=True):
    """The worst gap of a chunked prefill of ``sizes`` and a decode of the rest.
    ``pad``: the last chunk carries that many padding positions past the
    prompt's end, marked as nobody's (``masked`` False: the fault, unmarked)."""
    cache = init_cache(CONFIG, 1, 256, dtype=jnp.float32)
    offset, worst = 0, 0.0
    for i, size in enumerate(sizes):
        extra = pad if i == len(sizes) - 1 else 0
        chunk = tokens[offset : offset + size] + [0] * extra
        live = jnp.arange(size + extra)[None, :] < (size if masked else size + extra)
        logits, cache = _chunk(ours, _one(chunk), cache, jnp.asarray(offset, jnp.int32), live)
        worst = max(worst, np.abs(np.asarray(logits[0, :size]) - want[offset : offset + size]).max())
        offset += size
        # (the engine's finalize sets a row's length to its prompt's; a padded chunk ran past it)
        cache = cache._replace(lengths=jnp.asarray([offset], jnp.int32))
    for at in range(offset, len(tokens)):
        logits, cache = _decode(ours, _one(tokens[at : at + 1]), cache, attn_impl=attn_impl)
        worst = max(worst, np.abs(np.asarray(logits[0, 0]) - want[at]).max())
    assert int(cache.lengths[0]) == len(tokens)
    return worst


# (the interpreted kernels' steps are slow: one plan through them, every plan as XLA)
@pytest.mark.parametrize("sizes, step_impl", [
    ((32, 32), "xla-step"), ((32, 32), "kernel-step"), ((16, 32, 16), "xla-step"),
    ((16, 3, 3, 32), "xla-step"), ((3,), "xla-step"),
], ids=["two-chunks", "two-chunks-kernel-step", "three-chunks", "odd-chunks", "shorter-than-the-window"],
    indirect=["step_impl"])
def test_chunked_prefill_then_decode_through_rings_and_rows_matches_the_full_pass(
        weights, full_pass, sizes, step_impl):
    """Prompts longer than the window, chunks that wrap the ring of 8 more than
    once (32 is four turns of it) or end inside it, then decode steps that wrap
    it again: the full pass's logits at every position."""
    tokens, want = full_pass
    count = sum(sizes) + 16
    worst = _chunks_then_decode(weights[0], want[:count], tokens[:count], sizes, attn_impl=step_impl)
    assert worst < TOL


def test_padding_past_a_prompts_end_evicts_no_live_ring_column(weights, full_pass):
    """A last chunk of 6 real tokens padded to 16: unmarked, the padding's ten
    columns would overwrite the ring's live ones (the fault); marked as nobody's
    it leaves them, and decode matches the full pass."""
    ours, (tokens, want) = weights[0], full_pass
    tokens, want = tokens[:50], want[:50]
    assert _chunks_then_decode(ours, want, tokens, (32, 6), pad=10) < TOL
    assert _chunks_then_decode(ours, want, tokens, (32, 6), pad=10, masked=False) > 100 * TOL


# ---- the tolerance tells each named fault ----

# the first three layers hold every kind: full and dense, then two of sliding and sparse
THREE = {**MODEL, "num_hidden_layers": 3, **{key: MODEL[key][:3] for key in (
    "layer_types", "mlp_layer_types", "num_attention_heads_per_layer")}}
_SWAPPED = {"full_attention": MODEL["rope_parameters"]["sliding_attention"],
            "sliding_attention": MODEL["rope_parameters"]["full_attention"]}
FAULTS = {
    "head-gate-left-out": dict(patch=("_head_gate", lambda score: jnp.ones_like(score))),
    "window-off-by-one": dict(model={"sliding_window": WINDOW + 1}),
    "rotary-settings-swapped": dict(model={"rope_parameters": _SWAPPED}),
    "routed-scaling-left-out": dict(model={"moe_routed_scaling_factor": 1.0}),
    # layer 0 through the expert layer (and the dense matrices a layer late)
    "dense-first-layer-as-an-expert-layer": dict(
        model={"mlp_layer_types": ["sparse", "dense", "sparse"]}),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_the_tolerance_tells_each_fault(weights, monkeypatch, fault):
    """(A ring column evicted by padding is the program's own fault to make:
    ``test_padding_past_a_prompts_end_evicts_no_live_ring_column``.)"""
    _, theirs = weights
    tokens = _tokens(60, 40)
    clean = _reference(theirs, tokens, THREE)
    spec = FAULTS[fault]
    if "patch" in spec:
        monkeypatch.setattr(ref, *spec["patch"])
        ref._score.clear_cache()  # the reference's jitted pass is traced anew under the patch
    try:
        faulty = _reference(theirs, tokens, {**THREE, **spec.get("model", {})})
    finally:
        monkeypatch.undo()
        ref._score.clear_cache()
    assert np.abs(faulty - clean).max() > 100 * TOL, fault


def test_a_row_that_belongs_to_nobody_leaves_the_others_bit_identical(weights, step_impl):
    ours, _ = weights
    rows = jnp.asarray([_tokens(20, 12), _tokens(21, 12)], jnp.int32)
    cache = init_cache(CONFIG, 2, 64, dtype=jnp.float32)
    _, cache = _chunk(ours, rows, cache, jnp.zeros((), jnp.int32), jnp.ones((2, 12), bool))
    column = jnp.asarray([[7], [9]], jnp.int32)

    @jax.jit
    def step(cache, live):
        return forward(ours, column, CONFIG, cache=cache, decode=True, positions=cache.lengths[:, None],
                       attn_impl=step_impl, token_mask=live, return_attn_steps=True,
                       return_column_writes=True)

    plain = step(cache, jnp.ones((2, 1), bool))[0]
    masked, after, steps, writes = step(cache, jnp.asarray([[True], [False]]))
    assert np.array_equal(np.asarray(plain[0]), np.asarray(masked[0]))
    assert not np.array_equal(np.asarray(plain[1]), np.asarray(masked[1]))
    assert bool(jnp.all(jnp.isfinite(masked)))
    if step_impl == "pallas":
        # one work list a kind: the live row of the two, in every layer's call and write
        assert np.asarray(writes).tolist() == [9, 18]
        assert 0 < int(steps[0]) < int(steps[1])
        # the dead row's bytes stay as they were, rows and rings
        for leaf in ("k", "v", "ring_k", "ring_v"):
            assert np.array_equal(np.asarray(getattr(after, leaf)[:, 1]), np.asarray(getattr(cache, leaf)[:, 1]))
    else:
        assert np.asarray(writes).tolist() == [0, 0] and np.asarray(steps).tolist() == [0, 0]


def test_what_the_path_does_not_serve_is_refused_by_name(weights):
    from prime_tpu.models.quantize import quantize_params_int4, quantize_params_int8

    ours, _ = weights
    cache = init_cache(CONFIG, 2, 64, dtype=jnp.float32)
    tokens = jnp.zeros((2, 4), jnp.int32)
    with pytest.raises(ValueError, match="per-row chunk offset.*R7"):
        forward(ours, tokens, CONFIG, cache=cache, prefill_offset=jnp.asarray([0, 3], jnp.int32))
    with pytest.raises(ValueError, match="kv_quant is unsupported.*R7"):
        init_cache(CONFIG, 2, 64, quantized=True)
    with pytest.raises(ValueError, match="attention over a mesh.*R7"):
        forward(ours, tokens, CONFIG, attn_impl="sharded", mesh=jax.make_mesh((2,), ("tp",)))
    with pytest.raises(ValueError, match="a LoRA bank.*R7"):
        forward(ours, tokens, CONFIG, adapters={"layers": {}})
    for quantize in (quantize_params_int8, quantize_params_int4):
        with pytest.raises(ValueError, match="int8 or int4 weights.*R7"):
            quantize(ours)
    # the engine: a prefix cache over slots that hold rings, speculation, a mesh
    with pytest.raises(ValueError, match="hold a ring.*prefix_cache_mb=0"):
        ContinuousBatchingEngine(ours, CONFIG, max_slots=2, capacity=128, prefix_cache_mb=8)
    with pytest.raises(ValueError, match="cannot rewind"):
        ContinuousBatchingEngine(ours, CONFIG, max_slots=2, capacity=128, prefix_cache_mb=0,
                                 speculative=True)
    with pytest.raises(ValueError, match="does not serve a mesh.*R7"):
        ContinuousBatchingEngine(ours, CONFIG, max_slots=2, capacity=128,
                                 mesh=jax.make_mesh((2,), ("tp",)))
    assert ContinuousBatchingEngine(ours, CONFIG, max_slots=2, capacity=128).prefix_cache is None
    # lists that do not fit the depth, or name no kind, are refused where the config is made
    with pytest.raises(ValueError, match="one entry a layer"):
        dataclasses.replace(CONFIG, n_layers=8)


def test_prefill_shapes_read_heads_and_attended_columns_a_kind():
    """48 heads over a row of 8192 bind (805 MB of scores a row of the wave;
    72 heads over a ring of 512 and the chunk's 512 are 151 MB): chunk 512, wave
    1, what the configuration file states; and a window layer binds where the
    slots are short."""
    served = get_config("laguna-s-ep2")
    assert prefill_shapes(served, 8192, dropless_experts=True) == (512, 1)
    assert "chunk 512 and wave 1" in FILE["about_serve"]
    assert (FILE["serve"]["slot_capacity"], FILE["serve"]["max_slots"]) == (8192, 32)
    # slots of 1024: the window layers' 72 x (512 + chunk) pass the full layers' 48 x 1024
    assert prefill_shapes(served, 1024, dropless_experts=True) == (512, 4)
    assert prefill_shapes(dataclasses.replace(served, window_heads=48), 1024, dropless_experts=True) == (512, 8)
    # a model of one kind of layer reads as it did
    assert prefill_shapes(get_config("qwen3-4b"), 2048) == (512, 8)


# ---- the shares add up ----


def test_the_two_shares_and_the_shared_expert_once_are_the_uncut_layer(weights):
    """The expert layer's routed output for the held ranges [0, E/2) and
    [E/2, E), plus the shared expert counted once, is the uncut reference's
    layer output: the cut ties to the whole layer."""
    whole = dict(MODEL, num_experts=8, first_expert=0)
    theirs = jax.jit(lambda: ref.make_weights(whole, 0, jax.devices()))()
    layer = {name: theirs[f"sparse.{name}"][3].astype(jnp.float32) for name in
             ("router", "w_gate", "w_up", "w_down", "w_shared_gate", "w_shared_up", "w_shared_down")}
    # rows of unit rms: ``_mlp_block`` norms its input, and such a row is its own norm
    x = jnp.asarray(np.random.default_rng(9).normal(size=(50, 64)), jnp.float32)
    x = x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True))
    same = lambda w: w  # noqa: E731
    with jax.default_matmul_precision("highest"):
        want, routed = (np.asarray(y) for y in jax.jit(lambda: (
            ref.moe(x, layer, whole, same), ref.moe(x, layer, whole, same, shared=False)))())

    def share(first, with_shared):
        config = dataclasses.replace(CONFIG, n_experts_held=4, first_expert=first)
        lp = {name: (leaf[first : first + 4] if name in ("w_gate", "w_up", "w_down") else leaf)
              for name, leaf in layer.items() if with_shared or not name.startswith("w_shared")}
        lp["mlp_norm"] = jnp.ones((64,), jnp.float32)
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
        ours, CONFIG, max_slots=slots, capacity=256, chunk=4, prefill_chunk=32,
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


@pytest.fixture(scope="module")
def engine(weights):
    """One engine for the tests below (its programs are a few seconds to build)."""
    return _engine(weights[0])


def test_two_requests_of_one_wave_match_each_alone_and_the_reference(weights, engine):
    """Lengths 70 and 21 in one admission wave: three chunks of 32 for the one
    (the last padded), one padded chunk of 32 for the other, then finalize
    copies each row's rings whole into its slot."""
    _, theirs = weights
    first, second = _tokens(30, 70), _tokens(31, 21)
    a = engine.submit(first, max_new_tokens=20, temperature=0.0)
    b = engine.submit(second, max_new_tokens=30, temperature=0.0)
    together = _run(engine, [a, b])
    assert [len(t) for t in together] == [20, 30]
    for prompt, answer in zip((first, second), together):
        assert _gap(theirs, prompt, answer) < TOL
    # each alone, in a slot whose rings the other wave wrote: the same tokens
    for prompt, answer in zip((first, second), together):
        request = engine.submit(prompt, max_new_tokens=len(answer), temperature=0.0)
        assert _run(engine, [request])[0] == answer


def test_a_retired_slots_ring_never_reaches_the_next_tenant(weights, engine):
    """Both slots' rings full of two long tenants' columns, then a tenant
    shorter than the window in one of them: it reads none of what was left
    there (its answer is the reference's)."""
    _, theirs = weights
    long = [engine.submit(_tokens(seed, 90), max_new_tokens=12, temperature=0.0) for seed in (40, 42)]
    _run(engine, long)
    short = _tokens(41, 5)
    after = _run(engine, [engine.submit(short, max_new_tokens=12, temperature=0.0)])[0]
    assert _gap(theirs, short, after) < TOL


def test_the_engine_counts_cache_bytes_and_attended_columns_by_kind(engine):
    """``serve_cache_bytes`` by kind of leaf, and ``serve_decode_attn_columns_total``
    so that full + window + window_spared is layers x live positions."""
    held = engine.registry.get("serve_cache_bytes")
    floats = 2 * 2 * 16 * 4  # K and V, 2 K/V heads of 16, float32
    assert held.value(kind="full") == 3 * 2 * 256 * floats
    assert held.value(kind="ring") == 6 * 2 * WINDOW * floats
    assert held.value(kind="state") == 0
    columns = engine.registry.get("serve_decode_attn_columns_total")
    moe = engine.registry.get("serve_moe_layer_calls_total")
    kinds = ("full", "window", "window_spared")

    def read():  # once nothing is in flight: a chunk's counts land when it is synced
        while engine._inflight:
            engine.tick()
        return [columns.value(kind=k) for k in kinds] + [moe.value(phase="decode")]

    before = read()
    _run(engine, [engine.submit(_tokens(50, 30), max_new_tokens=9, temperature=0.0)])
    full, window, spared, calls = (now - was for now, was in zip(read(), before))
    # the first token is the prefill's; decode chunks of 4 steps ran from length 30 on while
    # the row was live: the 2 that made its 8 tokens, and what was dispatched before they were seen
    assert calls >= 8 * 8 and calls % (8 * 4) == 0
    steps = [n + 1 for n in range(30, 30 + int(calls) // 8)]
    assert full == 3 * sum(steps)
    assert window == 6 * WINDOW * len(steps) and spared == 6 * sum(n - WINDOW for n in steps)
    assert full + window + spared == 9 * sum(steps)
    assert calls == 8 * len(steps)  # the 8 expert layers, a call a step
