"""The prefix cache for a model whose slots hold recurrent state: snapshots of
the state on the radix tree's nodes (serve/prefix_cache.py), taken at a stride
that is a rule from shapes, matched, restored into an admission's row, split
and evicted with their token path (serve/engine.py). The tree alone with numpy
segments, then the engine at the tiny Olmo-Hybrid preset against a cold prefill
and the plain reference.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from prime_tpu.models import get_config
from prime_tpu.models.llama import init_cache, init_params
from prime_tpu.serve.engine import ContinuousBatchingEngine, cache_leaves, snapshot_stride
from prime_tpu.serve.prefix_cache import BlockPrefixCache

BLOCK = 4
KV_BYTES = 2 * 8  # a token of the numpy segments below: 2 float64 values
STATE = np.zeros((3, 5))  # 120 bytes a snapshot


def _tree(budget=0, stride=8):
    return BlockPrefixCache(budget, block=BLOCK, state_stride=stride)


def _insert(tree, ids, at=()):
    row = np.arange(2 * len(ids), dtype=np.float64).reshape(2, len(ids))
    return tree.insert(ids, lambda a, b: {"k": row[:, a:b]},
                       {p: {"s": STATE + p} for p in at} or None)


def _path(n, salt=0):
    return [salt * 1000 + i for i in range(n)]


def _where(tree):
    """{absolute position: the snapshot's tag} over the whole tree, by walking
    every node with the depth its edge starts at."""
    found, stack = {}, [(tree._root, 0, ())]
    while stack:
        node, start, path = stack.pop()
        for child in node.children.values():
            for offset, state in child.states.items():
                assert 0 < offset <= len(child.tokens) and offset % BLOCK == 0
                found[path + child.tokens[:offset]] = float(state["s"][0, 0])
            stack.append((child, start + len(child.tokens), path + child.tokens))
    return found


def test_a_match_is_cut_back_to_the_deepest_snapshot_under_it():
    tree = _tree()
    ids = _path(40)
    _insert(tree, ids, at=(8, 16, 24))
    # K/V reaches 36 of a 39-token limit; the deepest snapshot under it is at 24
    match = tree.match(ids, limit=39)
    assert (match.length, match.matched) == (24, 36) and float(match.state["s"][0, 0]) == 24
    assert sum(match.takes()) == 24
    tree.release(match)
    assert tree.match_lens(ids, limit=39) == (24, 36)
    # a limit under a snapshot's position passes it by
    assert tree.match_len(ids, limit=23) == 16 and tree.match_len(ids, limit=15) == 8
    assert tree.match_len(ids, limit=7) == 0 and tree.match(ids, limit=7) is None


def test_a_path_with_kv_and_no_snapshot_is_a_miss():
    tree = _tree()
    _insert(tree, _path(24))
    assert tree.match_lens(_path(24) + [9], limit=24) == (0, 24)
    assert tree.match(_path(24) + [9], limit=24) is None
    # the same tree without the state rule serves the K/V (a K/V-only model's cache)
    plain = BlockPrefixCache(0, block=BLOCK)
    _insert(plain, _path(24))
    match = plain.match(_path(24) + [9], limit=24)
    assert (match.length, match.matched, match.state) == (24, 24, None)


def test_a_split_hands_each_half_the_snapshots_on_it_and_bytes_stay_exact():
    tree = _tree()
    ids = _path(32)
    _insert(tree, ids, at=(8, 16, 32))
    total = 32 * KV_BYTES + 3 * STATE.nbytes
    assert (tree.bytes, tree.state_bytes, tree.snapshots, tree.nodes) == (total, 3 * STATE.nbytes, 3, 1)
    # a second prompt leaves the path at 12: inside the edge, at no snapshot's position
    other = ids[:12] + _path(12, salt=1)
    _insert(tree, other, at=(16,))
    assert tree.nodes == 3
    assert tree.bytes == total + 12 * KV_BYTES + STATE.nbytes and tree.state_bytes == 4 * STATE.nbytes
    where = _where(tree)
    assert where == {tuple(ids[:8]): 8.0, tuple(ids[:16]): 16.0, tuple(ids[:32]): 32.0,
                     tuple(other[:16]): 16.0}
    upper = tree._root.children[tuple(ids[:BLOCK])]
    assert len(upper.tokens) == 12 and sorted(upper.states) == [8]
    lower = upper.children[tuple(ids[12:16])]
    assert sorted(lower.states) == [4, 20] and lower.nbytes == 20 * KV_BYTES + 2 * STATE.nbytes
    # a split exactly AT a snapshot's position leaves it at the upper half's end
    _insert(tree, ids[:16] + _path(8, salt=2))
    where = _where(tree)
    assert where[tuple(ids[:16])] == 16.0 and len(where) == 4
    assert tree.bytes == total + 12 * KV_BYTES + STATE.nbytes + 8 * KV_BYTES
    # each prompt still starts from its own deepest snapshot
    assert tree.match_len(ids + [1], limit=32) == 32 and tree.match_len(other + [1], limit=24) == 16
    assert tree.match_len(ids[:16] + _path(8, salt=2) + [1], limit=24) == 16
    # a position that has a snapshot keeps the one it has
    _insert(tree, ids, at=(8,))
    assert tree.snapshots == 4 and tree.snapshots_stored == 4


def test_eviction_takes_a_nodes_snapshots_with_it_and_the_budget_counts_them():
    # room for the shared 16 tokens with their snapshot and ONE 16-token tail with its own
    budget = 16 * KV_BYTES + STATE.nbytes + 16 * KV_BYTES + STATE.nbytes
    tree = _tree(budget)
    shared = _path(16)
    first, second = shared + _path(16, salt=1), shared + _path(16, salt=2)
    _insert(tree, first, at=(16, 32))
    assert (tree.bytes, tree.evictions) == (budget, 0)
    _insert(tree, second, at=(16, 32))
    # the older tail went, K/V and snapshot together; the shared path keeps its snapshot
    assert (tree.bytes, tree.evictions, tree.snapshots_evicted, tree.snapshots) == (budget, 1, 1, 2)
    assert tree.state_bytes == 2 * STATE.nbytes
    assert tree.match_len(first + [1], limit=32) == 16 and tree.match_len(second + [1], limit=32) == 32
    assert set(_where(tree)) == {tuple(shared), tuple(second)}
    # a pinned path is not evicted from under its match
    match = tree.match(second + [1], limit=32)
    _insert(tree, shared + _path(16, salt=3), at=(32,))
    assert float(match.state["s"][0, 0]) == 32 and tree.match_len(second + [1], limit=32) == 32
    tree.release(match)
    tree.clear()
    assert (tree.bytes, tree.state_bytes, tree.snapshots) == (0, 0, 0)


def test_such_a_cache_has_no_host_tier_and_no_wire_format():
    with pytest.raises(ValueError, match="no host tier"):
        BlockPrefixCache(0, block=BLOCK, state_stride=8, host_budget_bytes=1 << 20)
    with pytest.raises(ValueError, match="multiple of block"):
        BlockPrefixCache(0, block=BLOCK, state_stride=6)
    tree = _tree()
    _insert(tree, _path(16), at=(8,))
    with pytest.raises(ValueError, match="carries K/V segments and no state.*does not export"):
        tree.export_segments(_path(16))
    with pytest.raises(ValueError, match="does not import"):
        tree.insert_segments(_path(16), {"k": np.zeros((2, 16))})
    with pytest.raises(ValueError, match="not on the path"):
        _insert(tree, _path(16), at=(6,))


# ---- the stride: a rule from shapes ----


@pytest.mark.parametrize("preset, slots, capacity, stride", [
    ("olmo-hybrid-7b-pp2", 16, 2048, 512),   # 27.4 MB of state over 61.4 KB of K/V a token: 446
    ("qwen3-next-ep2", 128, 4096, 4096),     # 6.4 MB over 2 KB: 3144, the slot's whole length
    ("tiny-olmohybrid", 2, 256, 32),         # float32 K/V: 64 in the bfloat16 it is rehearsed in
    ("qwen3-4b", 8, 2048, 0),                # no state: no snapshots
])
def test_the_stride_is_a_rule_from_shapes(preset, slots, capacity, stride):
    config = get_config(preset)
    dtype = jnp.float32 if preset.startswith("tiny") else jnp.bfloat16
    cache = jax.eval_shape(lambda: init_cache(config, slots, capacity, dtype=dtype))
    assert snapshot_stride(cache) == stride
    # whatever the slots' count and length: it is of one slot's state and one token's K/V
    assert snapshot_stride(jax.eval_shape(lambda: init_cache(config, 3, 128, dtype=dtype))) == stride


# ---- the engine ----

CONFIG = get_config("tiny-olmohybrid")


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.PRNGKey(0), CONFIG, dtype=jnp.float32)


def _engine(params, **kwargs):
    kwargs.setdefault("prefix_cache_mb", 8)
    return ContinuousBatchingEngine(
        params, CONFIG, max_slots=2, capacity=256, chunk=4, prefill_chunk=32, **kwargs,
    )


def _run(engine, requests, ticks=800):
    for _ in range(ticks):
        engine.tick()
        if all(r.done for r in requests):
            break
    assert all(r.done and r.error is None for r in requests), [r.error for r in requests]
    return [r.all_tokens(timeout=1) for r in requests]


def _tokens(seed, count):
    return np.random.default_rng(seed).integers(3, 500, size=count).tolist()


def _series(engine, name, label):
    family = engine.registry.snapshot().get(name, {"series": []})
    return {s["labels"][label]: s["value"] for s in family["series"]}


def _slot_leaves(engine, slot, length):
    out = {}
    for name, (leaf, spec) in cache_leaves(engine._cache).items():
        part = np.take(np.asarray(leaf), slot, axis=spec.slot_axis)
        if spec.capacity_axis is not None:
            axis = spec.capacity_axis - (spec.capacity_axis > spec.slot_axis)
            part = np.take(part, np.arange(length), axis=axis)
        out[name] = part
    return out


def test_a_turn_admitted_from_a_snapshot_is_the_cold_prefill_of_its_prompt(params):
    """Three turns of one session; turns 2 and 3 start from a snapshot. Each
    serves the tokens a cold engine serves for the same prompt, every served
    token the plain reference's best within the tolerance of
    tests/test_olmo_hybrid.py, and leaves the slot the K/V, matrix state and
    conv tail the cold prefill leaves there, to the chunk-invariance tolerance
    (the suffix is cut into other chunks than the whole prompt is)."""
    from benchmark.reference import olmo_hybrid as ref
    from tests.test_olmo_hybrid import MODEL, TOL

    theirs = ref.make_weights(MODEL, 0, jax.devices())
    bf16_values = jax.tree.map(  # the reference's weights are the recipe's bf16 draws
        lambda x: x.astype(jnp.float32), init_params(jax.random.PRNGKey(0), CONFIG, dtype=jnp.bfloat16))
    engine, cold = _engine(bf16_values), _engine(bf16_values, prefix_cache_mb=0)
    assert engine.state_stride == 32 and cold.prefix_cache is None
    prompt = _tokens(1, 100)
    for turn in range(3):
        request = engine.submit(prompt, max_new_tokens=10, temperature=0.0)
        answer = _run(engine, [request])[0]
        again = cold.submit(prompt, max_new_tokens=10, temperature=0.0)
        assert _run(cold, [again])[0] == answer
        logits = np.asarray(ref.score(MODEL, theirs, prompt, answer, 256, 64))
        gap = (logits.max(axis=-1) - logits[np.arange(len(answer)), answer]).max()
        assert gap < TOL * np.abs(logits).max()
        held, want = (_slot_leaves(e, r.slot, len(prompt) + 9) for e, r in ((engine, request), (cold, again)))
        assert sorted(held) == ["conv_tail", "delta_state", "k", "v"]
        for name, leaf in want.items():
            assert np.abs(leaf).max() > 0.01
            assert np.abs(held[name] - leaf).max() < 2e-4 * max(1.0, np.abs(leaf).max()), (turn, name)
        prompt = prompt + answer + _tokens(10 + turn, 37)
    # turn 1 (100 tokens) from zero, storing the snapshots at 32, 64 and 96; turn 2 (147) from
    # 96, storing 128; turn 3 (194) from 128, storing 160 and 192
    assert _series(engine, "serve_state_starts_total", "from") == {"zero": 1, "snapshot": 2}
    assert _series(engine, "serve_prefix_state_snapshots_total", "event") == {"stored": 6, "restored": 2}
    # turn 2 matched the 96 tokens of turn 1's K/V and used them all; turn 3 matched 144, used 128
    assert _series(engine, "serve_prefix_match_tokens_total", "kind") == {"used": 224, "beyond_snapshot": 16}
    values = engine.registry.values()
    one = sum(leaf.nbytes // 2 for leaf in (engine._cache.delta_state, engine._cache.conv_tail))
    assert values["serve_prefix_state_bytes"] == engine.prefix_cache.state_bytes == 6 * one
    assert values["serve_prefix_state_bytes"] < values["serve_prefix_cache_bytes"]
    assert values["serve_state_resets_total"] == 1 and values["serve_prefix_hits_total"] == 2


def test_a_prompt_under_the_stride_is_stored_and_is_a_miss(params):
    engine = _engine(params)
    prompt = _tokens(2, 28)  # under the 32-token stride: a block of K/V stored, no snapshot
    first = _run(engine, [engine.submit(prompt, max_new_tokens=4, temperature=0.0)])[0]
    longer = prompt + first + _tokens(3, 30)
    _run(engine, [engine.submit(longer, max_new_tokens=4, temperature=0.0)])
    assert engine.prefix_cache.snapshots == 1  # the second prompt's own, at 32 of its 62
    assert _series(engine, "serve_state_starts_total", "from") == {"zero": 2}
    assert _series(engine, "serve_prefix_match_tokens_total", "kind") == {"beyond_snapshot": 16}
    assert engine.registry.values()["serve_prefix_hits_total"] == 0


def test_a_wave_of_two_snapshots_its_stored_member_where_its_prompt_reaches(params):
    engine = _engine(params)
    long, short = _tokens(4, 125), _tokens(5, 100)
    _run(engine, [engine.submit(short, max_new_tokens=3, temperature=0.0),
                  engine.submit(long, max_new_tokens=3, temperature=0.0)])
    # one wave (a 128-column row in four chunks for both); member 0, 100 tokens, reaches the
    # boundaries at 32, 64 and 96 and not the one at 128, where its row's state has stopped at
    # its own end
    assert engine.registry.values()["serve_batched_admission_waves_total"] == 1
    assert engine.prefix_cache.snapshots == 3 and engine.prefix_cache.match_len(short + [1]) == 96
    assert engine.prefix_cache.match_len(long + [1]) == 0


def test_a_kv_only_model_takes_the_same_code_and_stores_no_snapshot():
    config = get_config("tiny-test")
    weights = init_params(jax.random.PRNGKey(0), config, dtype=jnp.float32)
    engine = ContinuousBatchingEngine(weights, config, max_slots=2, capacity=128, chunk=4,
                                      prefill_chunk=32, prefix_cache_mb=8)
    assert engine.state_stride == 0 and engine.prefix_cache.state_stride == 0
    prompt = _tokens(6, 50)
    first = _run(engine, [engine.submit(prompt, max_new_tokens=4, temperature=0.0)])[0]
    _run(engine, [engine.submit(prompt + first + _tokens(7, 20), max_new_tokens=4, temperature=0.0)])
    cache = engine.prefix_cache
    assert (cache.snapshots, cache.state_bytes, cache.snapshots_stored) == (0, 0, 0)
    assert engine._snapshot_fn is None and engine._restore_fn is None
    # the hit uses every matched block, as it always did
    assert engine.registry.values()["serve_prefix_hits_total"] == 1
    assert _series(engine, "serve_prefix_match_tokens_total", "kind") == {"used": 48}
    assert _series(engine, "serve_state_starts_total", "from") == {}
    assert _series(engine, "serve_prefix_state_snapshots_total", "event") == {}
    assert engine.registry.values()["serve_prefix_state_bytes"] == 0
    assert len(engine.export_kv(prompt)) > 0  # and its wire format is what it was


def test_what_is_still_refused_is_refused_by_name(params):
    with pytest.raises(ValueError, match="speculative decoding cannot rewind"):
        _engine(params, prefix_cache_mb=0, speculative=True)
    with pytest.raises(ValueError, match="speculative decoding cannot rewind"):
        _engine(params, speculative=True)
    with pytest.raises(ValueError, match="no host tier"):
        _engine(params, prefix_cache_host_mb=64)
    ring = get_config("tiny-phi4flash")
    weights = init_params(jax.random.PRNGKey(0), ring, dtype=jnp.float32)
    with pytest.raises(ValueError, match="hold a ring.*no ring"):
        ContinuousBatchingEngine(weights, ring, max_slots=2, capacity=128, prefix_cache_mb=8)
    with pytest.raises(ValueError, match="speculative decoding cannot rewind"):
        ContinuousBatchingEngine(weights, ring, max_slots=2, capacity=128, prefix_cache_mb=0,
                                 speculative=True)
    # slots no longer than the stride (what the rule gives qwen3-next-ep2: 4096 of 4096): no
    # prompt reaches a snapshot, so the cache could only store; with the cache off it serves
    with pytest.raises(ValueError, match="every 32 tokens.*slots hold 32: no prompt reaches"):
        ContinuousBatchingEngine(params, CONFIG, max_slots=2, capacity=32, prefix_cache_mb=8)
    assert ContinuousBatchingEngine(params, CONFIG, max_slots=2, capacity=32,
                                    prefix_cache_mb=0).prefix_cache is None
    # a model with state asks for its prefix cache by size: the default budget is not applied
    assert ContinuousBatchingEngine(params, CONFIG, max_slots=2, capacity=128).prefix_cache is None
    engine = _engine(params)
    _run(engine, [engine.submit(_tokens(8, 80), max_new_tokens=2, temperature=0.0)])
    with pytest.raises(ValueError, match="does not export"):
        engine.export_kv(_tokens(8, 80))
    with pytest.raises(ValueError, match="does not import"):
        engine.import_kv(b"{}\n")


def test_a_warmed_engine_builds_no_program_on_a_snapshot_hit(params):
    engine = _engine(params, paged_prefix=False)
    programs = engine.warmup()
    assert engine._snapshot_fn is not None and programs > 0
    prompt = _tokens(9, 100)
    first = _run(engine, [engine.submit(prompt, max_new_tokens=4, temperature=0.0)])[0]
    sizes = [f._cache_size() for f in (engine._snapshot_fn, engine._restore_fn, engine._chunk_fn)]
    _run(engine, [engine.submit(prompt + first + _tokens(10, 20), max_new_tokens=4, temperature=0.0)])
    assert _series(engine, "serve_state_starts_total", "from") == {"zero": 1, "snapshot": 1}
    assert [f._cache_size() for f in (engine._snapshot_fn, engine._restore_fn, engine._chunk_fn)] == sizes
