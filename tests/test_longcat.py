"""The shortcut-connected double layer (LongCat-Flash), one chip's share: the
program against the plain reference ``benchmark/reference/longcat_flash.py``
at the tiny preset of the same block kind, seeded random weights, on the CPU.

Everything here runs in float32 (the CPU's float32 matmul is exact float32),
so program and reference differ only by the order of their sums: the tolerance
``TOL`` is 1e-4 on logits of magnitude 1-4, ten times the 8e-6 they were seen
to differ by. Each named fault moves the reference's own logits by a hundred
times ``TOL`` or more (``test_the_tolerance_tells_each_fault``), so a program
that had it could not pass.
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.reference import longcat_flash as ref
from prime_tpu.models import get_config
from prime_tpu.models.llama import forward, init_cache, init_params
from prime_tpu.ops import moe as moe_ops
from prime_tpu.serve.engine import ContinuousBatchingEngine, _power_batches

TOL = 1e-4
CONFIG = get_config("tiny-longcat")
# the configuration file's keys for the tiny preset (the cell's rehearsal model)
MODEL = dict(
    hidden_size=128, ffn_hidden_size=256, expert_ffn_hidden_size=64, num_layers=2,
    num_attention_heads=4, kv_lora_rank=32, q_lora_rank=48, qk_rope_head_dim=16,
    qk_nope_head_dim=32, v_head_dim=32, routed_scaling_factor=6, n_routed_experts=4,
    n_routed_experts_published=8, first_expert=2, zero_expert_num=4, moe_topk=3,
    vocab_size=512, rms_norm_eps=1e-5, rope_theta=10000000,
)


@pytest.fixture(scope="module")
def weights():
    """The reference's own weights (bf16-rounded values) and the program's,
    both widened to float32."""
    ours = init_params(jax.random.PRNGKey(0), CONFIG, dtype=jnp.bfloat16)
    theirs = ref.make_weights(MODEL, 0, jax.devices())
    for name, value in theirs.items():
        mine = ours[name] if name in ours else ours["layers"][name]
        assert mine.dtype == value.dtype and bool(jnp.all(mine == value)), name
    return jax.tree.map(lambda x: x.astype(jnp.float32), ours), theirs


def _sequence(seed, n_prompt, n_answer):
    rng = np.random.default_rng(seed)
    ids = rng.integers(3, 500, size=n_prompt + n_answer).tolist()
    return ids[:n_prompt], ids[n_prompt:]


def _reference_logits(theirs, prompt, answer, model=MODEL):
    return np.asarray(ref.score(model, theirs, prompt, answer, 64, 32))


def test_the_recipe_is_restated_and_the_cache_has_a_row_an_attention(weights):
    assert CONFIG.cache_rows == 2 * CONFIG.n_layers == 4
    cache = init_cache(CONFIG, 3, 128)
    assert cache.k.shape == (4, 3, 1, CONFIG.mla_cache_dim, 128)
    assert cache.v.shape == (4, 3, 1, 1, 128)
    served = get_config("longcat-flash-ep32")
    assert served.cache_rows == 8
    # 4 x (638.9M outside the experts + 16 x 37.75M) + embedding and head slices + norms
    assert served.param_count == pytest.approx(4 * 1_242_824_704 + 2 * 100_663_296, rel=1e-4)
    assert get_config("tiny-test").cache_rows == get_config("tiny-test").n_layers


def test_offline_prefill_then_decode_matches_the_reference(weights):
    """A whole-prompt prefill, a chunked prefill at an offset and then decoding
    through the cache, all against the reference's one full causal pass."""
    ours, theirs = weights
    prompt, answer = _sequence(1, 20, 10)
    want = _reference_logits(theirs, prompt, answer)
    full, _ = forward(ours, jnp.asarray([prompt + answer], jnp.int32), CONFIG)
    got = np.asarray(full[0, len(prompt) - 1 : len(prompt) - 1 + len(answer)])
    assert np.abs(got - want).max() < TOL

    cache = init_cache(CONFIG, 1, 128, dtype=jnp.float32)
    _, cache = forward(ours, jnp.asarray([prompt[:16]], jnp.int32), CONFIG, cache=cache)
    logits, cache = forward(  # the rest of the prompt as a chunk at offset 16
        ours, jnp.asarray([prompt[16:]], jnp.int32), CONFIG, cache=cache,
        prefill_offset=jnp.asarray(16, jnp.int32),
    )
    steps = [np.asarray(logits[0, -1])]
    for token in answer[:-1]:
        logits, cache = forward(
            ours, jnp.asarray([[token]], jnp.int32), CONFIG, cache=cache, decode=True,
            positions=cache.lengths[:, None],
        )
        steps.append(np.asarray(logits[0, 0]))
    assert np.abs(np.stack(steps) - want).max() < TOL


def test_bf16_weights_fail_the_tolerance(weights):
    """The comparison is tight enough that serving in bfloat16 fails it."""
    _, theirs = weights
    prompt, answer = _sequence(1, 20, 10)
    want = _reference_logits(theirs, prompt, answer)
    low = init_params(jax.random.PRNGKey(0), CONFIG, dtype=jnp.bfloat16)
    full, _ = forward(low, jnp.asarray([prompt + answer], jnp.int32), CONFIG)
    got = np.asarray(full[0, len(prompt) - 1 : len(prompt) - 1 + len(answer)])
    assert np.abs(got - want).max() > 100 * TOL


def _faulty_layer(fault):
    """The reference's double layer with one named fault put in."""

    def layer(x, lp, positions, c, up):
        z, eps, theta = ref._sizes(c), float(c["rms_norm_eps"]), float(c["rope_theta"])
        sub = lambda i: {n: lp[n][i] for n in ("wq_a", "wq_b", "wkv_a", "wkv_b", "wo")}
        ffn = lambda u, i: (jax.nn.silu(u @ up(lp["w_gate"][i])) * (u @ up(lp["w_up"][i]))) @ up(lp["w_down"][i])
        n_routed = c["n_routed_experts_published"]
        if fault == "no_zero_experts":  # their gate * u left out
            n_routed += c["zero_expert_num"]
        a0 = x + ref.attention(ref._rms(x, eps), sub(0), positions, z, eps, theta, up)
        u0 = ref._rms(a0, eps)
        moe_in = u0.at[3].set(0.0) if fault == "a_dropped_token" else u0
        m = ref.moe(moe_in, lp["router"], jnp.zeros((z["width"],)), tuple(lp[n] for n in ref.EXPERTS),
                    c["first_expert"], n_routed, c["moe_topk"], float(c["routed_scaling_factor"]), up)
        if fault == "a_dropped_token":
            m = m.at[3].set(0.0)
        b0 = a0 + ffn(u0, 0) + (m if fault == "shortcut_added_early" else 0.0)
        a1 = b0 + ref.attention(ref._rms(b0, eps), sub(1), positions, z, eps, theta, up)
        b1 = a1 + ffn(ref._rms(a1, eps), 1)
        return b1 if fault == "shortcut_added_early" else b1 + m

    return layer


@pytest.mark.parametrize("fault", ["no_zero_experts", "a_dropped_token", "shortcut_added_early"])
def test_the_tolerance_tells_each_fault(weights, fault, monkeypatch):
    _, theirs = weights
    prompt, answer = _sequence(1, 20, 10)
    sound = _reference_logits(theirs, prompt, answer)
    monkeypatch.setattr(ref, "double_layer", _faulty_layer(fault))
    ref._score.clear_cache()
    try:
        faulty = _reference_logits(theirs, prompt, answer)
    finally:
        monkeypatch.undo()
        ref._score.clear_cache()
    assert np.abs(faulty - sound).max() > 100 * TOL


def _gaps(theirs, prompt, served):
    logits = _reference_logits(theirs, prompt, served)
    return logits.max(axis=-1) - logits[np.arange(len(served)), served]


@pytest.mark.parametrize("prefix_cache_mb", [0, 16])
def test_through_the_continuous_engine(weights, prefix_cache_mb):
    """Admission, chunked prefill at an offset (prompts longer than the
    16-token prefill chunk), decode chunks, two requests of different length
    side by side and a third beside them: every served token is the
    reference's best (its logit within TOL of it). With the prefix cache on,
    the second request's shared 32 tokens are seeded from latent segments
    the first one stored, and nothing changes."""
    ours, theirs = weights
    engine = ContinuousBatchingEngine(
        ours, CONFIG, max_slots=4, capacity=128, chunk=4, prefill_chunk=16,
        prefix_cache_mb=prefix_cache_mb,
    )
    rng = np.random.default_rng(0)
    shared = rng.integers(3, 500, size=40).tolist()
    prompts = [shared + rng.integers(3, 500, size=9).tolist(),
               shared + rng.integers(3, 500, size=3).tolist(),
               rng.integers(3, 500, size=21).tolist()]
    lengths = [10, 12, 7]
    first = engine.submit(prompts[0], max_new_tokens=lengths[0])
    for _ in range(3):
        engine.tick()
    rest = [engine.submit(p, max_new_tokens=n) for p, n in zip(prompts[1:], lengths[1:])]
    for _ in range(40):
        engine.tick()
    for request, prompt, n in zip([first] + rest, prompts, lengths):
        served = request.all_tokens(timeout=5)
        assert len(served) == n
        assert _gaps(theirs, prompt, served).max() < TOL
    assert engine.prefix_hits == (1 if prefix_cache_mb else 0)
    counts = engine.registry.snapshot()
    pairs = {tuple(sorted(s["labels"].items())): s["value"] for s in counts["serve_moe_pairs_total"]["series"]}
    decode = sum(v for k, v in pairs.items() if ("phase", "decode") in k)
    # every decode step of a live slot picks top-3 at 2 layers: the 26 decoded
    # tokens (the first of each answer comes from the prefill) and what the
    # chunks of 4 in flight decode past an answer's end; a retired slot routes nowhere
    assert decode % (3 * 2) == 0 and decode >= (sum(lengths) - 3) * 6
    assert pairs[(("kind", "local"), ("phase", "decode"))] > 0
    assert pairs[(("kind", "zero"), ("phase", "decode"))] > 0


def _moe_inputs(seed=0, tokens=24, published=8, zero=4, d=32, ff=16):
    rng = np.random.default_rng(seed)
    normal = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    return dict(
        u=normal(tokens, d), router=normal(d, published + zero) * 0.5,
        gate=normal(published, d, ff) * d**-0.5, up=normal(published, d, ff) * d**-0.5,
        down=normal(published, ff, d) * ff**-0.5,
    )


def test_the_shares_add_up_to_the_uncut_layer():
    """model-configs guide, section 4: the parts all R shares give, with what
    every chip computes alike (the zero experts) counted once, add up to the
    uncut reference's MoE output."""
    w = _moe_inputs()
    identity = lambda m: m
    uncut = ref.moe(w["u"], w["router"], jnp.zeros((12,)), (w["gate"], w["up"], w["down"]),
                    0, 8, 3, 6.0, identity)
    zero_only = ref.moe(w["u"], w["router"], jnp.zeros((12,)),  # experts that give nothing
                        (w["gate"], w["up"], 0.0 * w["down"]), 0, 8, 3, 6.0, identity)
    total = zero_only
    for first in (0, 4):  # two shares of four experts
        share, _ = moe_ops.moe_infer(
            w["u"][None], w["router"], w["gate"][first : first + 4], w["up"][first : first + 4],
            w["down"][first : first + 4], k=3, first_expert=first, n_zero=4, norm_topk=False,
            routed_scale=6.0,
        )
        total = total + (share[0] - zero_only)
    assert np.abs(np.asarray(total - uncut)).max() < 1e-5
    assert np.abs(np.asarray(uncut)).max() > 0.1


def test_dropless_under_a_skew_onto_one_expert():
    """Every token routed to ONE held expert (a capacity einsum at
    C = max(8, ceil8(T k cf / E)) would drop most of them): all are computed."""
    w = _moe_inputs(tokens=40)
    router = w["router"].at[:, 5].set(0.0)
    bias = jnp.zeros((12,)).at[5].set(10.0)  # selection only: everyone picks expert 5
    out, stats = moe_ops.moe_infer(
        w["u"][None], router, w["gate"][4:8], w["up"][4:8], w["down"][4:8], k=3,
        first_expert=4, n_zero=4, norm_topk=False, routed_scale=6.0, select_bias=bias,
    )
    want = ref.moe(w["u"], router, bias, (w["gate"][4:8], w["up"][4:8], w["down"][4:8]),
                   4, 8, 3, 6.0, lambda m: m)
    assert np.abs(np.asarray(out[0] - want)).max() < 1e-5
    counts = dict(zip(moe_ops.MOE_STATS, np.asarray(stats).tolist()))
    assert counts["local"] >= 40 and counts["local"] + counts["zero"] + counts["elsewhere"] == 120
    assert moe_ops.expert_capacity(40, 12, 3, 1.25) < 40  # what the training path would keep
    # a masked token routes nowhere
    mask = jnp.ones((1, 40), bool).at[0, 7].set(False)
    masked, stats = moe_ops.moe_infer(
        w["u"][None], router, w["gate"][4:8], w["up"][4:8], w["down"][4:8], k=3,
        first_expert=4, n_zero=4, norm_topk=False, routed_scale=6.0, select_bias=bias,
        token_mask=mask,
    )
    assert not np.asarray(masked[0, 7]).any() and int(np.asarray(stats)[:3].sum()) == 117
    assert np.abs(np.asarray(masked[0, :7] - want[:7])).max() < 1e-5


def test_token_groups_change_nothing():
    """A long prefill goes through the experts in groups of tokens (bounded row
    buffers): the same output and the same pairs as one group, the padding of
    the last group routed nowhere."""
    w = _moe_inputs(tokens=27)
    share = lambda **kw: moe_ops.moe_infer(
        w["u"][None], w["router"], w["gate"][:4], w["up"][:4], w["down"][:4], k=3, n_zero=4,
        norm_topk=False, routed_scale=6.0, **kw,
    )
    (whole, counts), (grouped, group_counts) = share(), share(group_size=8)
    assert np.abs(np.asarray(whole - grouped)).max() < 1e-5
    assert np.asarray(counts)[:3].tolist() == np.asarray(group_counts)[:3].tolist()
    assert int(counts[5]) == 1 and int(group_counts[5]) == 4


def test_the_selection_bias_moves_the_picks_and_never_the_gates():
    w = _moe_inputs()
    logits = w["u"] @ w["router"]
    scores = np.asarray(jax.nn.softmax(logits, axis=-1))
    plain, plain_gates = moe_ops.select_experts(logits, 3, norm_topk=False, routed_scale=6.0)
    bias = jnp.zeros((12,)).at[2].set(1.0)
    picks, gates = moe_ops.select_experts(logits, 3, norm_topk=False, routed_scale=6.0, select_bias=bias)
    assert (np.asarray(picks) == 2).any(axis=-1).all()       # expert 2 is always picked now
    assert not np.array_equal(np.asarray(picks), np.asarray(plain))
    want = 6.0 * np.take_along_axis(scores, np.asarray(picks), axis=-1)
    assert np.abs(np.asarray(gates) - want).max() < 1e-6      # the unbiased score x 6, no renormalisation
    assert np.abs(np.asarray(plain_gates).sum(-1) - 1.0).max() > 0.05


@pytest.mark.parametrize("interpret", [False, True])
def test_grouped_matmul_on_a_layer_stack(interpret, monkeypatch):
    """The kernel path (megablox, interpret mode) and the XLA path agree, with
    the experts handed over as a whole layer stack."""
    monkeypatch.setattr(moe_ops, "_kernel_path", lambda: (interpret, interpret))
    rng = np.random.default_rng(3)
    rows = jnp.asarray(rng.normal(size=(64, 128)), jnp.float32)
    stack = jnp.asarray(rng.normal(size=(3, 4, 128, 256)), jnp.float32)
    sizes = jnp.asarray([5, 0, 30, 9], jnp.int32)
    out = np.asarray(moe_ops.grouped_matmul(rows, stack, sizes, jnp.asarray(1, jnp.int32)))
    start = 0
    for expert, size in ((0, 5), (2, 30), (3, 9)):
        want = np.asarray(rows[start : start + size] @ stack[1, expert])
        assert np.abs(out[start : start + size] - want).max() < 1e-3
        start += size


def test_waves_are_capped_by_the_score_tensor(weights):
    """A chunk prefill's fp32 scores are wave x heads x chunk x capacity:
    64 heads at 512 x 2048 cap the wave at 4, 32 heads at 8, and the
    power-of-two sub-batches _admit forms stay under the cap."""
    assert _power_batches(7) == [4, 2, 1]
    assert _power_batches(9, 4) == [4, 4, 1]
    assert _power_batches(3, 1) == [1, 1, 1]
    ours, _ = weights
    for heads, want in ((64, 4), (32, 8), (4, 64)):
        engine = ContinuousBatchingEngine(
            ours, dataclasses.replace(CONFIG, n_heads=heads), max_slots=2, capacity=2048,
            prefill_chunk=512, prefix_cache_mb=0,
        )
        assert engine.max_wave == want


@pytest.mark.parametrize("max_wave, want", [(None, (512, 4)), (2, (1024, 2)), (1, (2048, 1))])
def test_a_budget_spent_on_width_leaves_the_chunk_at_512(weights, max_wave, want):
    """LongCat's shapes (64 heads, slots of 2048, top-12 of 512: 12 rows an
    expert a 512-token chunk) with no ``prefill_chunk`` given: the score budget
    is spent on a wave of 4, so the cell's engine keeps the parent's chunk,
    width and rows. A deployment that asked for narrower waves would get the
    length the width gave up: the same gibibyte of scores either way."""
    from prime_tpu.serve.engine import prefill_shapes, row_capacities

    config = get_config("longcat-flash-ep32")
    got = prefill_shapes(config, 2048, max_wave=max_wave, dropless_experts=True)
    assert got == want
    if got[0] == 512:
        assert row_capacities(got[0], 2048) == [16, 32, 64, 128, 256, 512, 1024, 1536, 2048]
    assert 4 * 64 * got[0] * 2048 * got[1] == 2**30
    # the tiny engine of the same block kind runs the dropless layer and asks the
    # rule so; its 8 experts at top-3 see 192 rows of a 512-token chunk: 512
    ours, _ = weights
    engine = ContinuousBatchingEngine(
        ours, dataclasses.replace(CONFIG, n_heads=64), max_slots=2, capacity=2048,
        prefix_cache_mb=0, max_wave=max_wave,
    )
    assert engine._moe and engine.prefill_chunk == 512 and engine.max_wave == want[1]


@pytest.mark.parametrize(
    "preset, max_wave",
    [("tiny-longcat", None), ("tiny-test", None), ("tiny-qwen3next", None), ("tiny-qwen3next", 1)],
)
def test_a_warmed_engine_builds_no_program_whatever_the_wave(preset, max_wave):
    """With the prefix cache off, warm-up reaches every program a request can
    run: admission waves of any size, one chunk or several, build nothing on
    the loop thread, at the widths the shapes allow and at a ``max_wave`` of
    one (the qwen3-next cell's) alike. (A list uploaded with ``jnp.asarray(..., dtype=)``, an
    eager add of the experts' counts or a slice of the staged row is one
    program a shape on first use, and which wave shapes a run of the
    benchmark meets depends on its timing: one compile inside a window and the
    harness discards the window and measures again.)"""
    config = get_config(preset)
    params = init_params(jax.random.PRNGKey(0), config, dtype=jnp.float32)
    engine = ContinuousBatchingEngine(
        params, config, max_slots=8, capacity=128, chunk=4, prefill_chunk=32,
        prefix_cache_mb=0, warmup=True, max_wave=max_wave,
    )
    engine.start()
    try:
        warmed = engine._clock.build_totals()
        assert warmed["total"] > 0
        rng = np.random.default_rng(0)
        for wave in (1, 2, 4, 3, 7):
            requests = [
                engine.submit(rng.integers(3, 200, size=int(size)).tolist(), max_new_tokens=6)
                for size in rng.integers(5, 60, size=wave)
            ]
            for request in requests:
                assert len(request.all_tokens(timeout=300)) == 6
        after = engine._clock.build_totals()
        built = [b["name"] for b in engine._clock.builds()][-(after["total"] - warmed["total"]):]
        assert after["total"] == warmed["total"], built
    finally:
        engine.shutdown()


def test_a_second_identical_hit_builds_no_program():
    """With a paged prefix cache, a hit's first admission builds what the
    page path builds lazily (gather, store, the tail's slice); the same
    prompt again builds nothing: a hit is a wave of one that stores its row
    as it is, with no member taken out of it."""
    config = get_config("tiny-test")
    params = init_params(jax.random.PRNGKey(0), config, dtype=jnp.float32)
    engine = ContinuousBatchingEngine(
        params, config, max_slots=2, capacity=128, chunk=4, prefill_chunk=32,
        prefix_cache_mb=8, warmup=True,
    )
    assert engine.paged_prefix
    engine.start()
    try:
        system = list(range(3, 51))  # three blocks
        question = system + [60 + i for i in range(20)]
        for prompt in (system, question):
            assert len(engine.submit(prompt, max_new_tokens=6).all_tokens(timeout=300)) == 6
        assert engine.prefix_hits == 1
        first_hit = engine._clock.build_totals()
        assert len(engine.submit(question, max_new_tokens=6).all_tokens(timeout=300)) == 6
        after = engine._clock.build_totals()
        built = [b["name"] for b in engine._clock.builds()][-(after["total"] - first_hit["total"]):]
        assert engine.prefix_hits == 2 and engine.stats()["prefix_paged_seeds"] == 2
        assert (after["total"], after["loads"]) == (first_hit["total"], first_hit["loads"]), built
    finally:
        engine.shutdown()


@pytest.mark.parametrize("preset,sliced", [("tiny-longcat", True), ("tiny-test", False)])
def test_a_vocabulary_slice_is_served_without_an_end_of_sequence_id(preset, sliced):
    """serve_model: a preset that states a slice of a sharded vocabulary
    (``vocab_published``) holds none of the tokenizer's special rows, so its
    engine has no end-of-sequence id and an answer runs to ``max_tokens``
    though row 2 (the byte tokenizer's eos) comes up in it; a whole vocabulary
    keeps the tokenizer's. (On the chip 16 answers of 396 ended early on row 2
    and one first token in 16384 made an empty answer.)"""
    from prime_tpu.serve import serve_model
    from prime_tpu.serve.engine import EngineRequest

    assert (get_config(preset).vocab_published is not None) == sliced
    server = serve_model(preset, port=0, continuous=True, max_slots=2, slot_capacity=64, chunk=4)
    with server:
        engine = server.generator.engine
        assert server.generator.tokenizer.eos_id == 2
        assert engine.eos_id == (-1 if sliced else 2)
        request = EngineRequest(id=0, prompt_ids=[5], max_new_tokens=4, temperature=0.0, top_p=1.0)
        engine._emit(request, [9, 2, 11, 12])  # a chunk that holds row 2
        assert request.emitted == (4 if sliced else 1) and request.done


def test_a_long_answers_timeline_keeps_its_milestones():
    """obs/flight.py: 1024 tokens at a chunk of 8 are 128 ``chunk`` events
    against 64 a timeline; admitted, first_token and completed survive for
    benchmark/layer_metrics/_flight.py, and a window of 300 requests fits."""
    from prime_tpu.obs.flight import FlightRecorder

    recorder = FlightRecorder(slow_ms=0)
    for request in range(300):
        recorder.begin(request, trace_id=f"{request:032x}", prompt_tokens=128)
        recorder.event(request, "admitted", slot=1)
        recorder.event(request, "prefill_done", ms=30.0)
        recorder.event(request, "first_token")
        for seq in range(128):
            recorder.event(request, "chunk", seq=seq)
        recorder.end(request, "completed", tokens=1024)
    assert len(recorder.summaries(limit=1000)["recent"]) == 300
    timeline = recorder.get(f"{0:032x}")
    names = [e["event"] for e in timeline["events"]]
    assert names[:3] == ["admitted", "prefill_done", "first_token"] and names[-1] == "completed"
    assert len(names) == 64 and timeline["events_dropped"] == 132 - 64
    from benchmark.layer_metrics._flight import times

    joined = times(timeline, 0.0)
    assert joined["first_token"] is not None and joined["tokens"] == 1024
