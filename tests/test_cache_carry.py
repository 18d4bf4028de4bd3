"""The stacked KV cache is the layer scan's carry (PR 25).

Three things are held here, on the CPU:

- ``flash_decode`` given the stacked ``(L, B, KH, D, C)`` buffer and a layer
  reads exactly what it reads from that layer's 4-D slice (interpret mode);
- ``write_cache`` puts a block where an indexed assignment would;
- ``forward`` with the cache as a carry gives the logits and the cache bytes
  of the same run with the cache as the scan's ``xs``/``ys``, the structure
  this repository had before: ``_forward_xs_ys`` below is that scan, kept
  here as the reference and nowhere in the package.
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from prime_tpu.models.config import get_config
from prime_tpu.models.llama import (
    KVCache,
    _attention_block,
    _mlp_block,
    _norm,
    forward,
    init_cache,
    init_params,
    sliding_layer_flags,
)
from prime_tpu.ops.attention import (
    cache_prefill_attention,
    decode_attention,
    write_cache,
)
from prime_tpu.ops.rope import rope_frequencies

# ---- the kernel and the ops read their layer out of the stacked buffer ---------

L, B, H, KH, D, C = 3, 2, 4, 2, 16, 256


def _stacked(seed, quant):
    """(k, v, scales-or-None) stacked over L layers, every layer different."""
    rng = np.random.default_rng(seed)
    k = jnp.asarray(rng.normal(size=(L, B, KH, D, C)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(L, B, KH, D, C)).astype(np.float32))
    if quant is None:
        return k.astype(jnp.bfloat16), v.astype(jnp.bfloat16), None, None
    if quant == "int8":
        from prime_tpu.models.llama import quantize_kv as quantize
    else:
        from prime_tpu.models.quantize import quantize_kv_int4 as quantize
    (kq, ks), (vq, vs) = quantize(k), quantize(v)
    return kq, vq, ks, vs


@pytest.mark.parametrize("variant", ["bf16", "int8", "int4", "window_sinks"])
@pytest.mark.parametrize("layer", [1, 2])
def test_stacked_flash_decode_equals_the_layers_slice(variant, layer):
    from prime_tpu.ops.pallas_attention import flash_decode

    quant = variant if variant in ("int8", "int4") else None
    k, v, ks, vs = _stacked(7, quant)
    rng = np.random.default_rng(8)
    q = jnp.asarray(rng.normal(size=(B, H, 1, D)).astype(np.float32)).astype(
        jnp.bfloat16 if quant is None else jnp.float32
    )
    lengths = jnp.asarray([C, 131], dtype=jnp.int32)
    kw = dict(sm_scale=D**-0.5, interpret=True)
    if variant == "window_sinks":
        kw |= dict(
            window=96, sliding=jnp.asarray(True),
            sinks=jnp.asarray(rng.normal(size=(H,)).astype(np.float32)),
        )
    scales = {} if quant is None else dict(k_scale=ks, v_scale=vs)
    sliced = {name: leaf[layer] for name, leaf in scales.items()}
    # the layer is traced, as the scan hands it over
    stacked = jax.jit(
        lambda lay: flash_decode(q, k, v, lengths, layer=lay, **scales, **kw)
    )(jnp.int32(layer))
    one_layer = flash_decode(q, k[layer], v[layer], lengths, **sliced, **kw)
    assert np.array_equal(np.asarray(stacked), np.asarray(one_layer))
    other = flash_decode(q, k[0], v[0], lengths, **{n: s[0] for n, s in scales.items()}, **kw)
    assert not np.array_equal(np.asarray(stacked), np.asarray(other))


@pytest.mark.parametrize("quant", [None, "int8", "int4"])
def test_einsum_paths_index_the_layer(quant):
    """decode_attention's XLA path (and the int4 widen path) and
    cache_prefill_attention on the stacked buffer + layer equal the same
    call on that layer's slice."""
    k, v, ks, vs = _stacked(9, quant)
    rng = np.random.default_rng(10)
    dtype = jnp.bfloat16 if quant is None else jnp.float32
    lengths = jnp.asarray([200, 57], dtype=jnp.int32)
    scales = {} if quant is None else dict(k_scale=ks, v_scale=vs)
    layer = jnp.int32(2)
    sliced = {name: leaf[2] for name, leaf in scales.items()}

    q1 = jnp.asarray(rng.normal(size=(B, H, 1, D)).astype(np.float32)).astype(dtype)
    got = decode_attention(q1, k, v, lengths, D**-0.5, impl="xla", layer=layer, **scales)
    want = decode_attention(q1, k[2], v[2], lengths, D**-0.5, impl="xla", **sliced)
    assert np.array_equal(np.asarray(got), np.asarray(want))
    if quant == "int4":
        return  # chunked prefill has no int4 cache
    q5 = jnp.asarray(rng.normal(size=(B, H, 5, D)).astype(np.float32)).astype(dtype)
    got = cache_prefill_attention(q5, k, v, lengths, D**-0.5, layer=layer, **scales)
    want = cache_prefill_attention(q5, k[2], v[2], lengths, D**-0.5, **sliced)
    assert np.array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("start", ["shared", "rows"])
def test_write_cache_is_an_indexed_assignment(start):
    rng = np.random.default_rng(11)
    leaves = tuple(
        jnp.asarray(rng.normal(size=(L, B, KH, d, 32)).astype(np.float32)) for d in (D, 1)
    )
    blocks = tuple(
        jnp.asarray(rng.normal(size=(B, KH, d, 3)).astype(np.float32)) for d in (D, 1)
    )
    starts = jnp.int32(7) if start == "shared" else jnp.asarray([7, 20], dtype=jnp.int32)
    got = jax.jit(write_cache)(leaves, blocks, jnp.int32(1), starts)
    for leaf, block, new in zip(leaves, blocks, got):
        want = np.array(leaf)
        for row in range(B):
            at = int(starts) if start == "shared" else int(starts[row])
            want[1, row, :, :, at : at + 3] = np.asarray(block[row])
        assert np.array_equal(np.asarray(new), want)


# ---- forward: the carry against the parent's xs/ys scan -----------------------


def _forward_xs_ys(params, tokens, config, cache, decode=False, prefill_offset=None):
    """``forward`` for a cached run as it was before the cache became a
    carry: each stack's layers scan with that stack's slice of every cache
    leaf as ``xs`` and the updated layer as ``ys``, and the stacks' parts are
    concatenated. A layer is handed to the block as a one-layer stacked
    buffer at layer 0, so the block and the attention ops are the package's
    own and only the scan's structure differs."""
    batch, seq = tokens.shape
    positions = (
        cache.lengths[:, None]
        if decode
        else jnp.broadcast_to(jnp.arange(seq, dtype=jnp.int32)[None], (batch, seq))
    )
    if prefill_offset is not None:
        off = prefill_offset.astype(jnp.int32)
        positions = positions + (off[:, None] if off.ndim else off)
    head_dim = config.qk_rope_head_dim if config.mla else config.head_dim
    tables = rope_frequencies(
        head_dim, cache.capacity, config.rope_theta, scale=config.rope_scale,
        yarn=config.rope_yarn, yarn_truncate=config.rope_yarn_truncate,
        longrope_select=cache.capacity, partial=config.partial_rotary,
    )
    leaves = (cache.k, cache.v) + ((cache.k_scale, cache.v_scale) if cache.quantized else ())

    def layer_fn(carry, scanned):
        x, aux_sum = carry
        lp, sliding, *layer_leaves = scanned
        one = tuple(leaf[None] for leaf in layer_leaves)
        if config.mla:
            from prime_tpu.models.mla import mla_attention_block

            x, one = mla_attention_block(
                x, lp, positions, tables, config, one, jnp.int32(0),
                cache.lengths, decode, "xla", prefill_offset=prefill_offset,
            )
        else:
            x, one = _attention_block(
                x, lp, positions, tables, config, one, jnp.int32(0),
                cache.lengths, decode, "xla", prefill_offset=prefill_offset,
                sliding=sliding,
            )
        # under a cache the experts run dropless (ops.moe.moe_infer), as forward's do
        x, aux, _ = _mlp_block(x, lp, config, dropless="router" in lp)
        return (x, aux_sum + aux), tuple(leaf[0] for leaf in one)

    kd = config.first_k_dense
    stacks = [(params["layers"], slice(kd, None))]
    if kd:
        stacks.insert(0, (params["dense_layers"], slice(0, kd)))
    carry, parts = (params["embed"][tokens], jnp.zeros((), jnp.float32)), []
    flags = sliding_layer_flags(config)
    for stack, rows in stacks:
        carry, ys = jax.lax.scan(
            layer_fn, carry, (stack, flags[rows], *(leaf[rows] for leaf in leaves))
        )
        parts.append(ys)
    new = [jnp.concatenate(leaf_parts, axis=0) for leaf_parts in zip(*parts)]
    x = _norm(carry[0], params["final_norm"], config)
    head = params["embed"].T if config.tie_embeddings else params["lm_head"]
    logits = (x @ head).astype(jnp.float32)
    lengths = cache.lengths + (1 if decode else seq)
    return logits, KVCache(new[0], new[1], lengths, *new[2:])


CASES = {
    "dense": (get_config("tiny-test"), False),
    "three_layers": (dataclasses.replace(get_config("tiny-test"), n_layers=3), False),
    "int8_cache": (get_config("tiny-test"), True),
    "mla": (get_config("tiny-mla"), False),
    "first_k_dense": (
        dataclasses.replace(get_config("tiny-deepseek"), n_layers=3, first_k_dense=1, dense_ff=96),
        False,
    ),
    "window_sinks_moe": (get_config("tiny-gptoss"), False),
}


def _same(got, want):
    (got_logits, got_cache), (want_logits, want_cache) = got, want
    assert np.array_equal(np.asarray(got_logits), np.asarray(want_logits))
    for name in KVCache._fields:
        a, b = getattr(got_cache, name), getattr(want_cache, name)
        assert (a is None) == (b is None), name
        if a is not None:
            assert np.array_equal(np.asarray(a), np.asarray(b)), name


@pytest.fixture(scope="module", params=list(CASES))
def model(request):
    config, quantized = CASES[request.param]
    params = init_params(jax.random.PRNGKey(0), config, dtype=jnp.float32)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (3, 24), 1, config.vocab_size)
    cache = init_cache(config, 3, 64, dtype=jnp.float32, quantized=quantized)
    # a whole-prompt prefill of 8 tokens: the state the other regimes start from
    _, cache = jax.jit(lambda p, t, c: forward(p, t, config, cache=c))(
        params, tokens[:, :8], cache
    )
    return config, params, tokens, cache


def test_prompt_prefill_matches_xs_ys(model):
    config, params, tokens, filled = model
    cache = init_cache(config, 3, 64, dtype=jnp.float32, quantized=filled.quantized)
    # the prompt path attends over the fresh K/V, not the cache: the logits
    # cannot differ, the staged cache could
    got = jax.jit(lambda p, t, c: forward(p, t, config, cache=c))(params, tokens[:, :8], cache)
    want = jax.jit(lambda p, t, c: _forward_xs_ys(p, t, config, c))(params, tokens[:, :8], cache)
    _same(got, want)


@pytest.mark.parametrize("offsets", ["shared", "rows"])
def test_chunked_prefill_matches_xs_ys(model, offsets):
    config, params, tokens, cache = model
    if offsets == "rows":  # the speculative verify window: each row at its own length
        cache = cache._replace(lengths=jnp.asarray([8, 6, 7], dtype=jnp.int32))
        offset = cache.lengths
    else:
        offset = jnp.int32(8)
    chunk = tokens[:, 8:12]
    got = jax.jit(lambda p, t, c, o: forward(p, t, config, cache=c, prefill_offset=o))(
        params, chunk, cache, offset
    )
    want = jax.jit(lambda p, t, c, o: _forward_xs_ys(p, t, config, c, prefill_offset=o))(
        params, chunk, cache, offset
    )
    _same(got, want)


def test_twelve_decode_steps_match_xs_ys(model):
    config, params, tokens, cache = model
    cache = cache._replace(lengths=jnp.asarray([8, 5, 7], dtype=jnp.int32))
    new = jax.jit(
        lambda p, t, c: forward(
            p, t, config, cache=c, decode=True, positions=c.lengths[:, None]
        )
    )
    old = jax.jit(lambda p, t, c: _forward_xs_ys(p, t, config, c, decode=True))
    tok, got_cache, want_cache = tokens[:, 8:9], cache, cache
    for _ in range(12):
        got = new(params, tok, got_cache)
        want = old(params, tok, want_cache)
        _same(got, want)
        (logits, got_cache), (_, want_cache) = got, want
        tok = jnp.argmax(logits[:, 0], axis=-1)[:, None].astype(tokens.dtype)
    assert np.array_equal(np.asarray(got_cache.lengths), [20, 17, 19])


# ---- decode's column write as one kernel --------------------------------------


def _column_case(case, batch=B):
    """(leaves, columns, capacity) of a ``write_columns`` call: the stacked
    cache's leaves and a step's columns for them, drawn from one seed."""
    rng = np.random.default_rng(12)
    capacity, dims, dtypes, kv_heads = 256, (D, D), (jnp.bfloat16,) * 2, KH
    if case == "int8_with_scales":
        dims, dtypes = (D, D, 1, 1), (jnp.int8, jnp.int8, jnp.float32, jnp.float32)
    elif case == "f32_short_capacity":
        capacity, dtypes = 48, (jnp.float32,) * 2  # no 128-slot block divides it
    elif case == "heads_split_over_programs":
        kv_heads, dims = 4, (640, 640)  # too wide for all heads in one VMEM block: two a program
    elif case == "latent_one_leaf":
        kv_heads, dims, dtypes = 1, (72,), (jnp.bfloat16,)
    elif case == "laguna_ring":  # 8 K/V heads of 128 on a ring of 512, written at position mod 512
        capacity, kv_heads, dims = 512, 8, (128, 128)
    elif case == "laguna_row":   # ... and on a row of 8192
        capacity, kv_heads, dims = 8192, 8, (128, 128)

    def draw(shape, dtype):
        values = rng.normal(size=shape) * (50 if dtype == jnp.int8 else 1)
        return jnp.asarray(values.astype(np.float32)).astype(dtype)

    leaves = tuple(draw((L, batch, kv_heads, d, capacity), t) for d, t in zip(dims, dtypes))
    columns = tuple(draw((batch, kv_heads, d, 1), t) for d, t in zip(dims, dtypes))
    return leaves, columns, capacity


@pytest.mark.parametrize(
    "case",
    ["bf16", "int8_with_scales", "f32_short_capacity", "heads_split_over_programs", "latent_one_leaf",
     "laguna_ring", "laguna_row"],
)
def test_write_columns_kernel_is_the_per_row_update(case):
    """``write_columns`` (interpret mode) without a list of live rows leaves the
    bytes that the per-row ``dynamic_update_slice`` form of ``write_cache``
    leaves, a slot past the end included (both clamp it to the last slot)."""
    from prime_tpu.ops.pallas_attention import write_columns

    leaves, columns, capacity = _column_case(case)
    for slots in ([5, capacity - 1], [130 % capacity, capacity + 3]):
        slots = jnp.asarray(slots, dtype=jnp.int32)
        got = write_columns(leaves, columns, jnp.int32(2), slots, interpret=True)
        want = write_cache(leaves, columns, jnp.int32(2), slots)
        for new, ref in zip(got, want):
            assert new.dtype == ref.dtype
            assert np.array_equal(np.asarray(new), np.asarray(ref))


@pytest.mark.parametrize(
    "live",
    [(True, False, True, False), (False, False, False, True), (False,) * 4, (True,) * 4],
    ids=["every_other_row", "the_last_row", "no_row", "every_row"],
)
@pytest.mark.parametrize(
    "case", ["bf16", "int8_with_scales", "heads_split_over_programs", "latent_one_leaf"]
)
def test_write_columns_walks_the_live_rows(case, live):
    """With the step's list of live rows (``column_work_list``) a live row's
    leaves are the unlisted call's bit for bit, and a row that belongs to nobody
    keeps the bytes it had, its column's slot included: for the bf16 cache's two
    leaves, the int8 cache's four, the latent's one, and a row whose kv heads
    take two programs. With no live row the call's one step passes its block
    through and every leaf comes back as it went in."""
    from prime_tpu.ops.pallas_attention import column_work_list, write_columns

    leaves, columns, capacity = _column_case(case, batch=4)
    slots = jnp.asarray([5, 130, capacity + 3, capacity - 1], dtype=jnp.int32)
    listed = write_columns(
        leaves, columns, jnp.int32(1), slots, work=column_work_list(jnp.asarray(live)),
        interpret=True,
    )
    every_row = write_columns(leaves, columns, jnp.int32(1), slots, interpret=True)
    for got, written, old in zip(listed, every_row, leaves):
        assert got.dtype == old.dtype
        for row, alive in enumerate(live):
            want = written if alive else old
            assert np.array_equal(np.asarray(got[:, row]), np.asarray(want[:, row])), (row, alive)
    if not any(live):
        assert all(np.array_equal(np.asarray(a), np.asarray(b)) for a, b in zip(listed, leaves))


@pytest.mark.parametrize(
    "shape, heads",
    [
        pytest.param((8, 128, 2), 8, id="qwen3-4b"),
        pytest.param((10, 128, 2), 10, id="phi4-mini-flash"),
        pytest.param((2, 256, 2), 2, id="qwen3-next-ep2"),
        pytest.param((8, 128, 2), 8, id="laguna-s-ep2"),
        pytest.param((1, 576, 2), 1, id="longcat-latent"),
        pytest.param((8, 128, 1), 8, id="int8-cache"),
        pytest.param((30, 128, 2), 15, id="olmo-hybrid-7b-pp2"),
        pytest.param((32, 128, 2), 16, id="32-mha-heads"),
        pytest.param((7, 1024, 2), 1, id="7-wide-heads"),
    ],
)
def test_column_heads_is_the_widest_divisor_that_fits(shape, heads):
    """A ``write_columns`` program carries as many of a row's kv heads as keep
    its widest 128-slot block under 512 KB, a divisor of the head count: the
    benchmark's shapes keep all of theirs but Olmo-Hybrid's 30 x 128, which go
    15 a program (all-or-one gave it 30 programs a row)."""
    from prime_tpu.ops.pallas_attention import column_heads

    kv_heads, width, itemsize = shape
    assert column_heads(kv_heads, width * itemsize, 128) == heads
    assert kv_heads % heads == 0 and heads * width * itemsize * 128 <= 512 * 1024


def test_forward_decode_writes_through_the_kernel(monkeypatch):
    """``attn_impl="pallas"`` sends decode's column through ``write_columns``
    and its attention through ``flash_decode``: the first layer's cache bytes
    (same inputs on both paths) are those of the XLA path, and the logits
    agree to the kernel's accumulation order."""
    from prime_tpu.ops import pallas_attention

    monkeypatch.setenv("PRIME_TPU_PALLAS_INTERPRET", "1")
    calls = []
    real = pallas_attention.write_columns
    monkeypatch.setattr(
        pallas_attention, "write_columns",
        lambda *args, **kw: calls.append(len(args[0])) or real(*args, **kw),
    )
    config = get_config("tiny-test")
    params = init_params(jax.random.PRNGKey(0), config, dtype=jnp.float32)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (3, 9), 1, config.vocab_size)
    for quantized in (False, True):
        cache = init_cache(config, 3, 128, dtype=jnp.float32, quantized=quantized)
        _, cache = forward(params, tokens[:, :8], config, cache=cache)
        cache = cache._replace(lengths=jnp.asarray([8, 3, 6], dtype=jnp.int32))
        step = dict(cache=cache, decode=True, positions=cache.lengths[:, None])
        kernel_logits, kernel_cache = forward(
            params, tokens[:, 8:], config, attn_impl="pallas", **step
        )
        xla_logits, xla_cache = forward(params, tokens[:, 8:], config, attn_impl="xla", **step)
        np.testing.assert_allclose(
            np.asarray(kernel_logits), np.asarray(xla_logits), atol=2e-5
        )
        for name in ("k", "v", "k_scale", "v_scale"):
            a, b = getattr(kernel_cache, name), getattr(xla_cache, name)
            assert (a is None) == (b is None)
            if a is not None:
                assert np.array_equal(np.asarray(a[0]), np.asarray(b[0])), name
        assert np.array_equal(np.asarray(kernel_cache.lengths), [9, 4, 7])
    assert calls == [2, 4]  # the scanned body is traced once a forward
