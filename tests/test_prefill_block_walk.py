"""``ops.attention.cache_prefill_attention`` walks a row in column blocks under a
running softmax and stops at the last column the chunk can see. Held here
against a plain float32 softmax over the whole row, at the head groups, widths,
offsets and row capacities the served models and the engine's rows give it."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from prime_tpu.ops import attention
from prime_tpu.ops.attention import (
    cache_prefill_attention,
    prefill_attention_block,
    prefill_columns_visited,
)


def _whole_row(q, k, v, offset, sm_scale, *, softcap=0.0, window=0, sliding=None,
               k_scale=None, v_scale=None, sinks=None):
    """The same attention, every column of the row at once, in float32."""
    f32 = jnp.float32
    batch, heads, seq, _ = q.shape
    group = heads // k.shape[1]
    k, v = k.astype(f32), v.astype(f32)
    if k_scale is not None:
        k, v = k * k_scale, v * v_scale
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    scores = jnp.einsum("bhsd,bhdc->bhsc", q.astype(f32), k, precision="highest") * sm_scale
    if softcap:
        scores = softcap * jnp.tanh(scores / softcap)
    q_pos = jnp.reshape(offset, (-1, 1, 1)) + jnp.arange(seq)[None, :, None]
    delta = q_pos - jnp.arange(k.shape[-1])[None, None, :]
    seen = delta >= 0
    if window and sliding is not False:
        seen = seen & (delta < window)
    scores = jnp.where(seen[:, None], scores, -jnp.inf)
    if sinks is not None:
        sink = jnp.broadcast_to(sinks.astype(f32)[None, :, None, None], (batch, heads, seq, 1))
        probs = jax.nn.softmax(jnp.concatenate([scores, sink], axis=-1), axis=-1)[..., :-1]
    else:
        probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhsc,bhdc->bhsd", probs, v, precision="highest")


def _case(*, heads=4, kv_heads=1, dim=128, seq=16, capacity=1536, batch=2, offset=0,
          layers=0, layer=0, shared=False, int8=False, **kw):
    """One call's operands, random queries over a random row, and its keywords."""
    keys = jax.random.split(jax.random.PRNGKey(capacity + heads + dim), 6)
    lead = (layers,) if layers else ()
    shape = (*lead, batch, kv_heads, dim, capacity)
    q = jax.random.normal(keys[0], (batch, heads, seq, dim), jnp.float32)
    if int8:
        k = jax.random.randint(keys[1], shape, -127, 128, jnp.int8)
        v = jax.random.randint(keys[2], shape, -127, 128, jnp.int8)
        scale_shape = (*lead, batch, kv_heads, 1, capacity)
        kw["k_scale"] = jax.random.uniform(keys[3], scale_shape, jnp.float32, 0.002, 0.02)
        kw["v_scale"] = jax.random.uniform(keys[4], scale_shape, jnp.float32, 0.002, 0.02)
    else:
        k = jax.random.normal(keys[1], shape, jnp.float32)
        v = k if shared else jax.random.normal(keys[2], shape, jnp.float32)
    if kw.get("sinks") is True:
        kw["sinks"] = jax.random.normal(keys[5], (heads,), jnp.float32)
    return q, k, v, jnp.asarray(offset, jnp.int32), dim**-0.5, layers, layer, kw


def _one_layer(leaf, layers, layer):
    return leaf[layer] if layers and leaf is not None else leaf


CASES = {
    # head groups 1 / 4 / 6 / 64, widths 128 and 576, as the served models have them
    "group1": dict(heads=2, kv_heads=2, capacity=1536, offset=512),
    "group4": dict(heads=8, kv_heads=2, capacity=1536, offset=1024),
    "group6": dict(heads=6, kv_heads=1, capacity=8192, offset=5000, seq=24),
    "group64-d576-one-latent": dict(heads=64, kv_heads=1, dim=576, capacity=1536, offset=700,
                                    seq=8, shared=True),
    # a capacity under one block; one that only 512 divides; the longest row
    "c256": dict(capacity=256, offset=100),
    "c256-first-chunk": dict(capacity=256, offset=0, seq=64),
    "c1536-last-block": dict(capacity=1536, offset=1536 - 16),
    "c8192-first-chunk": dict(capacity=8192, offset=0, seq=32),
    "c8192-off-block": dict(capacity=8192, offset=3001, seq=32),
    "c8192-row-end": dict(capacity=8192, offset=8192 - 32, seq=32),
    "chunk-over-two-blocks": dict(capacity=8192, offset=1000, seq=48),
    # per-row offsets (the speculative verify window), far apart and off the block
    "rows-apart": dict(capacity=8192, offset=[37, 6011], seq=8),
    "rows-apart-1536": dict(capacity=1536, offset=[1500, 3], seq=8, heads=6, kv_heads=1),
    "one-query": dict(capacity=8192, offset=[4095, 4096], seq=1),
    # the window, with the traced flag true / false / absent
    "window-sliding-true": dict(capacity=8192, offset=5000, window=300, sliding=True),
    "window-sliding-false": dict(capacity=8192, offset=5000, window=300, sliding=False),
    "window-sliding-none": dict(capacity=8192, offset=5000, window=300),
    "window-rows-apart": dict(capacity=8192, offset=[100, 7000], seq=8, window=64, sliding=True),
    "window-wider-than-row": dict(capacity=1536, offset=1024, window=4096),
    "softcap": dict(capacity=1536, offset=777, softcap=5.0),
    "sinks": dict(capacity=1536, offset=777, sinks=True),
    "sinks-window-softcap": dict(capacity=8192, offset=2500, sinks=True, window=128,
                                 sliding=True, softcap=30.0),
    "int8": dict(capacity=1536, offset=600, int8=True),
    "int8-stacked": dict(capacity=8192, offset=2100, int8=True, layers=3, layer=1),
    "stacked": dict(capacity=1536, offset=900, layers=4, layer=2),
    "stacked-one-latent": dict(capacity=8192, offset=4500, layers=2, layer=1, heads=16,
                               kv_heads=1, shared=True),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_the_block_walk_is_the_whole_rows_softmax(name):
    q, k, v, offset, sm_scale, layers, layer, kw = _case(**CASES[name])
    static = {n: kw.pop(n) for n in ("softcap", "window") if n in kw}
    traced = dict(kw, **({"sliding": jnp.asarray(kw["sliding"])} if "sliding" in kw else {}))
    shared = v is k  # one leaf for keys and values, handed over as one

    @jax.jit
    def walk(q, k, v, offset, layer, traced):
        return cache_prefill_attention(
            q, k, k if shared else v, offset, sm_scale, layer=layer, **traced, **static
        )

    got = walk(q, k, v, offset, jnp.int32(layer), traced)
    plain = {n: _one_layer(x, layers, layer) if n.endswith("_scale") else x for n, x in kw.items()}
    want = _whole_row(q, _one_layer(k, layers, layer), _one_layer(v, layers, layer), offset,
                      sm_scale, **plain, **static)
    assert got.shape == want.shape and got.dtype == q.dtype
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-5)


def test_bf16_operands_keep_float32_statistics():
    """The served dtype: P goes into the P.V product in the cache's dtype, the
    maxima, sums and the accumulator stay float32, so the walk stays as near the
    whole row's float32 softmax as one rounding of P and of the output allows."""
    q, k, v, offset, sm_scale, *_ = _case(heads=6, kv_heads=1, capacity=8192, offset=6000, seq=32)
    bf = jnp.bfloat16
    got = cache_prefill_attention(q.astype(bf), k.astype(bf), v.astype(bf), offset, sm_scale)
    want = _whole_row(q.astype(bf), k.astype(bf), v.astype(bf), offset, sm_scale)
    assert got.dtype == bf
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want), atol=8e-3)


@pytest.mark.parametrize("offset", [0, 512, 1000, [200, 2900]])
def test_columns_past_the_chunk_are_never_read(offset):
    """Every column past ``offset + S`` filled with large finite junk, against the
    same call on zeros, to the bit: the blocks past the chunk are not visited and
    the rest of the chunk's last block is masked."""
    q, k, v, offset, sm_scale, *_ = _case(heads=6, kv_heads=1, capacity=8192, offset=offset, seq=24)
    past = jnp.arange(8192) >= jnp.max(offset) + 24
    filled = [jnp.where(past, junk, leaf) for leaf, junk in ((k, 3e4), (v, -7e4))]
    zeroed = [jnp.where(past, 0.0, leaf) for leaf in (k, v)]
    got = cache_prefill_attention(q, *filled, offset, sm_scale)
    want = cache_prefill_attention(q, *zeroed, offset, sm_scale)
    assert np.array_equal(np.asarray(got), np.asarray(want))
    assert np.isfinite(np.asarray(got)).all()


@pytest.mark.parametrize("capacity", [64, 256, 512, 640, 1024, 1536, 2048, 2560, 4096, 7680, 8192])
def test_the_block_is_a_rule_from_the_rows_shape(capacity):
    """512 where it divides the row, the whole row where it does not: it divides
    every row the engine builds, at a chunk cap of 512 and of 2048."""
    from prime_tpu.serve.engine import row_capacities

    block = prefill_attention_block(capacity)
    assert block == (capacity if capacity % 512 else 512) and capacity % block == 0
    for cap in (512, 2048):
        assert all(row % prefill_attention_block(row) == 0 for row in row_capacities(cap, capacity))


@pytest.mark.parametrize("offset,seq,capacity,window", [
    (0, 512, 512, 0), (0, 512, 8192, 0), (512, 512, 1536, 0), (3584, 512, 8192, 0),
    (7680, 512, 8192, 0), (1024, 256, 2048, 0), (0, 2048, 4096, 0), (2048, 2048, 4096, 0),
    (5000, 16, 8192, 300), (100, 16, 8192, 300), (1024, 512, 1536, 4096), (96, 32, 256, 0),
])
def test_the_host_counts_the_walks_own_trips(monkeypatch, offset, seq, capacity, window):
    """``prefill_columns_visited`` (what the engine counts into
    ``serve_prefill_attn_columns_total``) is the loop's trip count x its block."""
    block = prefill_attention_block(capacity)
    trips = []
    real = jax.lax.fori_loop

    def counting(first, last, body, init):
        trips.append((int(first), int(last)))
        return real(first, last, body, init)

    q, k, v, at, sm_scale, *_ = _case(heads=2, kv_heads=1, dim=8, seq=seq, capacity=capacity,
                                      batch=1, offset=offset)
    monkeypatch.setattr(jax.lax, "fori_loop", counting)
    with jax.disable_jit():
        cache_prefill_attention(q, k, v, at, sm_scale, window=window)
    ((first, last),) = trips
    visited = prefill_columns_visited(offset, seq, capacity, window)
    assert visited == (last - first) * block and 0 < visited <= capacity


# ---- the engine's count of what the walk visited ---------------------------------------


@pytest.mark.parametrize("preset,calls", [
    ("tiny-test", "n_layers"), ("tiny-longcat", "2 x n_layers"), ("tiny-phi4flash", "1 + cross_pairs"),
    ("tiny-qwen3next", "n_layers // full_attention_interval"), ("tiny-laguna", "full_layers"),
])
def test_a_configuration_says_how_many_attentions_run_over_a_row(preset, calls):
    """``row_attentions``: the calls of ``cache_prefill_attention`` one chunk
    prefill makes, which is what a row's columns are counted once for."""
    from prime_tpu.models.config import get_config

    config = get_config(preset)
    want = {
        "n_layers": config.n_layers,
        "2 x n_layers": 2 * config.n_layers,
        "1 + cross_pairs": 1 + config.cross_pairs,
        "n_layers // full_attention_interval": config.n_layers // max(config.full_attention_interval, 1),
        "full_layers": config.full_layers,
    }[calls]
    assert config.row_attentions == want > 0


@pytest.mark.parametrize("preset,sliding", [
    ("tiny-test", 0), ("gemma2-2b", 13), ("phi3-mini", 32), ("gemma3-1b", 22), ("tiny-gptoss", 1),
    ("phi4-mini-flash", 0), ("laguna-s-ep2", 0), ("longcat-flash-ep32", 0),
])
def test_a_configuration_says_which_row_attentions_slide(preset, sliding):
    """``sliding_row_attentions``: the standard block's layers whose window runs
    over a full-length row, as the forward's own flags have them; a ring (a
    ``layer_types`` model's, the sambay block's) is no row."""
    from prime_tpu.models.config import get_config
    from prime_tpu.models.llama import sliding_layer_flags

    config = get_config(preset)
    assert config.sliding_row_attentions == sliding <= config.row_attentions
    if sliding:
        assert int(np.sum(sliding_layer_flags(config))) == sliding


@pytest.mark.parametrize("window", [0, 300], ids=["global", "even_layers_slide"])
def test_the_engine_counts_visited_and_spared_columns_of_its_chunk_prefills(window):
    """``serve_prefill_attn_columns_total``: a 1100-token prompt is three chunks
    of 512 over a row of 1536 (which only a block of 512 divides): the chunks at
    0 / 512 / 1024 visit 512 / 1024 / 1536 columns a layer and spare the rest; a
    layer that slides starts its last chunk at its window's block. The
    benchmark's reader over the same snapshots reads the spared share, and
    nothing where there is no counter (the parent) or no prefill."""
    import dataclasses

    from benchmark.layer_metrics import prefill_columns_spared_share
    from benchmark.systems.serve_http import counter_delta
    from prime_tpu.models.config import get_config
    from prime_tpu.models.llama import init_params
    from prime_tpu.serve.engine import ContinuousBatchingEngine

    config = dataclasses.replace(get_config("tiny-test"), max_seq_len=2048, sliding_window=window)
    params = init_params(jax.random.PRNGKey(0), config, dtype=jnp.float32)
    engine = ContinuousBatchingEngine(
        params, config, max_slots=2, capacity=1536, chunk=4, prefix_cache_mb=0
    )
    assert prefill_attention_block(1536) == 512
    before = engine.registry.snapshot()
    request = engine.submit([3 + i % 400 for i in range(1100)], max_new_tokens=2, temperature=0.0)
    for _ in range(50):
        engine.tick()
        if request.done:
            break
    assert request.done
    columns = engine.registry.get("serve_prefill_attn_columns_total")
    every, sliding = (2, 0) if not window else (1, 1)  # the even layer of two slides
    visited = every * (512 + 1024 + 1536) + sliding * (512 + 1024 + 1024)
    assert columns.value(kind="visited") == visited
    assert columns.value(kind="spared") == 2 * 3 * 1536 - visited
    after = engine.registry.snapshot()

    def share(pair):
        return prefill_columns_spared_share.read({"counters": pair, "counter_delta": counter_delta})

    assert share((before, after)) == pytest.approx(100 * (1 - visited / (2 * 3 * 1536)))
    assert share((after, after)) is None and share(({}, {})) is None
    engine.shutdown()
