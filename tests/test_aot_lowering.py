"""The permanent TPU lowering gate: compile for a v5e WITHOUT a chip.

Interpret-mode tests exercise a kernel's arithmetic and nothing of its Mosaic
lowering — block shapes, dtype legalization, layouts. libtpu is installed in
the test environment, so ``topologies.get_topology_desc("v5e:2x2", "tpu")``
gives four ``TPU v5 lite`` devices to ``jit(f).lower(...).compile()`` while the
process itself stays on the CPU backend (no chip, no lock file). Every Pallas
kernel the TPU serve path can dispatch is compiled here at one served shape
(qwen3-4b: B 8, H 32, KH 8, D 128, C 2048), plus ``forward`` decode / chunked
prefill of a two-layer qwen3-4b and the ``tp=4`` shard_map decode. A kernel
that cannot lower fails tier-1 here, not on the chip a round later.

The compiled text is read too: the KV cache is the layer scan's carry and is
updated in place, so the engine-shaped decode may hold no cache-sized
temporary, no ``copy`` of the stacked cache and no one-layer slice of it.
"""

import dataclasses
import functools
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

B, H, KH, D, C = 8, 32, 8, 128, 2048


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc("v5e:2x2", "tpu")
    except Exception as e:  # noqa: BLE001 — no libtpu in this environment
        pytest.skip(f"no TPU compiler available for AOT lowering: {e}")


def _compile(topo, fn, *args, sharding=None, donate=(), **static):
    """AOT-compile ``fn`` for device 0 of the topology (or ``sharding``);
    ``args`` are (shape, dtype) pairs or pytrees of ShapeDtypeStructs, those
    at the positions in ``donate`` donated as the engine donates its cache."""
    sharding = sharding or SingleDeviceSharding(topo.devices[0])

    def place(x):
        if isinstance(x, tuple) and len(x) == 2 and isinstance(x[0], tuple):
            return jax.ShapeDtypeStruct(x[0], x[1], sharding=sharding)
        return jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding), x
        )

    jitted = jax.jit(functools.partial(fn, **static), donate_argnums=donate)
    return jitted.lower(*(place(a) for a in args)).compile()


# ---- kernels at the served shape ---------------------------------------------

_Q = ((B, H, 1, D), jnp.bfloat16)
_LENS = ((B,), jnp.int32)


def _kv(dtype, d=D):
    return ((B, KH, d, C), dtype)


_SCALE = ((B, KH, 1, C), jnp.float32)


@pytest.mark.parametrize(
    "variant",
    ["bf16", "window_sinks", "int8", "int4", "mha", "stacked", "stacked_int8"],
)
def test_flash_decode_lowers(topo, variant):
    from prime_tpu.ops.pallas_attention import flash_decode

    if variant.startswith("stacked"):
        # what the model's layer scan hands over: the whole (L, B, KH, D, C)
        # buffer and a traced layer for the index maps
        layers, scalar = 4, ((), jnp.int32)
        if variant == "stacked":
            kv = ((layers, B, KH, D, C), jnp.bfloat16)
            _compile(
                topo, lambda q, k, v, n, l: flash_decode(q, k, v, n, layer=l),
                _Q, kv, kv, _LENS, scalar,
            )
        else:
            kv = ((layers, B, KH, D, C), jnp.int8)
            scale = ((layers, B, KH, 1, C), jnp.float32)
            _compile(
                topo,
                lambda q, k, v, n, ks, vs, l: flash_decode(
                    q, k, v, n, k_scale=ks, v_scale=vs, layer=l
                ),
                _Q, kv, kv, _LENS, scale, scale, scalar,
            )
    elif variant == "bf16":
        _compile(topo, flash_decode, _Q, _kv(jnp.bfloat16), _kv(jnp.bfloat16), _LENS)
    elif variant == "window_sinks":
        _compile(
            topo,
            lambda q, k, v, n, s: flash_decode(q, k, v, n, window=1024, sinks=s),
            _Q, _kv(jnp.bfloat16), _kv(jnp.bfloat16), _LENS, ((H,), jnp.float32),
        )
    elif variant == "int8":
        _compile(
            topo,
            lambda q, k, v, n, ks, vs: flash_decode(q, k, v, n, k_scale=ks, v_scale=vs),
            _Q, _kv(jnp.int8), _kv(jnp.int8), _LENS, _SCALE, _SCALE,
        )
    elif variant == "int4":
        _compile(
            topo,
            lambda q, k, v, n, ks, vs: flash_decode(q, k, v, n, k_scale=ks, v_scale=vs),
            _Q, _kv(jnp.uint8, D // 2), _kv(jnp.uint8, D // 2), _LENS, _SCALE, _SCALE,
        )
    else:  # MHA: group of one query head per kv head
        kv = ((B, H, D, C), jnp.bfloat16)
        _compile(topo, flash_decode, _Q, kv, kv, _LENS)


@pytest.mark.parametrize("variant", ["bf16", "int8_with_scales", "mla_latent"])
def test_write_columns_lowers(topo, variant):
    """Decode's column write as one kernel, at the served shape: the stacked
    leaves in, the same buffers out."""
    from prime_tpu.ops.pallas_attention import write_columns

    layers, kv_heads, dims, dtypes = 4, KH, (D, D), (jnp.bfloat16,) * 2
    if variant == "int8_with_scales":
        dims, dtypes = (D, D, 1, 1), (jnp.int8, jnp.int8, jnp.float32, jnp.float32)
    elif variant == "mla_latent":  # DeepSeek-V2's one latent head of rank + rope
        kv_heads, dims, dtypes = 1, (576,), (jnp.bfloat16,)
    leaves = tuple(((layers, B, kv_heads, d, C), t) for d, t in zip(dims, dtypes))
    columns = tuple(((B, kv_heads, d, 1), t) for d, t in zip(dims, dtypes))
    count = len(leaves)
    compiled = _compile(
        topo,
        lambda *a: write_columns(a[:count], a[count : 2 * count], a[-2], a[-1]),
        *leaves, *columns, ((), jnp.int32), _LENS, donate=tuple(range(count)),
    )
    # donated and aliased through the kernel: no second cache is allocated
    assert compiled.memory_analysis().temp_size_in_bytes < B * kv_heads * max(dims) * C


@pytest.mark.parametrize("variant", ["plain", "window_sinks"])
def test_flash_prefill_lowers(topo, variant):
    from prime_tpu.ops.pallas_attention import flash_attention_causal

    q = ((1, H, 512, D), jnp.bfloat16)
    kv = ((1, KH, 512, D), jnp.bfloat16)
    if variant == "plain":
        _compile(topo, flash_attention_causal, q, kv, kv)
    else:
        _compile(
            topo,
            lambda q, k, v, s: flash_attention_causal(q, k, v, window=256, sinks=s),
            q, kv, kv, ((H,), jnp.float32),
        )


@pytest.mark.parametrize("seq", [1, 8])
def test_fused_lora_matmul_lowers(topo, seq):
    from prime_tpu.ops.pallas_lora import fused_lora_matmul

    d_in, d_out, r, bank = 2560, 4096, 16, 4
    _compile(
        topo, fused_lora_matmul,
        ((B, seq, d_in), jnp.bfloat16), ((d_in, d_out), jnp.bfloat16),
        ((bank, d_in, r), jnp.bfloat16), ((bank, r, d_out), jnp.bfloat16),
        ((B,), jnp.int32),
    )


def test_int4_matmul_lowers(topo):
    from prime_tpu.ops.pallas_quant import int4_matmul

    d_in, d_out = 2560, 9728
    _compile(
        topo, int4_matmul,
        ((B, d_in), jnp.bfloat16), ((d_in // 2, d_out), jnp.uint8),
        ((d_in // 128, d_out), jnp.float32),
    )


def test_prefix_page_gather_lowers(topo):
    """The prefix-hit seed path at qwen3-4b's pool geometry: the page gather
    and the donated page store both compile for the chip (the Pallas
    gather this replaced could not: 16-token blocks are not a legal TPU
    tile)."""
    from prime_tpu.serve.kv_pool import _store_pages, gather_pages

    r_dim, pt, pages = 36 * KH * D, 16, 96
    pool = ((pages, r_dim, pt), jnp.bfloat16)
    _compile(topo, gather_pages, pool, ((C // pt,), jnp.int32))
    _compile(
        topo, _store_pages, pool, ((36, 1, KH, D, 4 * pt), jnp.bfloat16),
        ((4,), jnp.int32),
    )


# ---- the model forward, two layers of qwen3-4b --------------------------------


def _qwen3_4b(n_layers):
    from prime_tpu.models.config import get_config

    return dataclasses.replace(get_config("qwen3-4b"), n_layers=n_layers)


def _abstract_model(config, batch, capacity):
    from prime_tpu.models.llama import init_cache, init_params

    params = jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), config, dtype=jnp.bfloat16)
    )
    cache = jax.eval_shape(lambda: init_cache(config, batch, capacity))
    return params, cache


@pytest.mark.parametrize("attn_impl", ["pallas", "xla"])
def test_forward_decode_lowers(topo, attn_impl):
    from prime_tpu.models.llama import forward

    config = _qwen3_4b(2)
    params, cache = _abstract_model(config, B, C)

    def step(params, cache, tokens):
        return forward(
            params, tokens, config, cache=cache, decode=True, attn_impl=attn_impl
        )

    compiled = _compile(topo, step, params, cache, ((B, 1), jnp.int32))
    assert compiled.memory_analysis().argument_size_in_bytes > 0


@pytest.mark.parametrize("attn_impl", ["pallas", "xla"])
def test_forward_chunked_prefill_lowers(topo, attn_impl):
    from prime_tpu.models.llama import forward

    config = _qwen3_4b(2)
    params, cache = _abstract_model(config, 1, C)

    def chunk(params, cache, tokens, offset):
        return forward(
            params, tokens, config, cache=cache, attn_impl=attn_impl,
            prefill_offset=offset, last_positions=jnp.zeros((1,), jnp.int32),
        )

    _compile(topo, chunk, params, cache, ((1, 512), jnp.int32), ((), jnp.int32))


def _results(compiled, opcode):
    """Result types of every ``opcode`` instruction in the compiled text."""
    pattern = re.compile(rf"^\s*(?:ROOT )?%\S+ = (\S+) {opcode}\(", re.M)
    return pattern.findall(compiled.as_text())


@pytest.mark.parametrize("n_layers", [2, 4])
def test_decode_keeps_the_cache_in_place(topo, n_layers):
    """The engine's decode program (a scan of steps over ``forward(decode=
    True)``, cache donated) at the served shape: the cache rides both scans as
    a carry and each layer writes its column in place, so the program's
    temporaries stay under ONE layer's K + V (with the cache as the layer
    scan's xs/ys they exceeded the whole cache), nothing copies the stacked
    cache, and the kernel reads its layer out of the stacked buffer, so no
    one-layer slice is made."""
    from prime_tpu.models.llama import forward

    config = _qwen3_4b(n_layers)
    params, cache = _abstract_model(config, B, C)

    def decode(params, cache, tok):
        def step(carry, _):
            cache, tok = carry
            logits, cache = forward(
                params, tok[:, None], config, positions=cache.lengths[:, None],
                cache=cache, decode=True, attn_impl="pallas",
            )
            tok = jnp.argmax(logits[:, 0], axis=-1).astype(jnp.int32)
            return (cache, tok), tok

        (cache, _), toks = jax.lax.scan(step, (cache, tok), None, length=2)
        return cache, toks

    compiled = _compile(topo, decode, params, cache, ((B,), jnp.int32), donate=(1,))
    one_layer_kv = 2 * B * KH * D * C * 2
    assert compiled.memory_analysis().temp_size_in_bytes < one_layer_kv
    stacked, one_layer = f"bf16[{n_layers},{B},{KH},{D},{C}]", f"bf16[{B},{KH},{D},{C}]"
    assert not [r for r in _results(compiled, "copy") if r.startswith(stacked)]
    assert not [r for r in _results(compiled, "fusion") if r.startswith(one_layer)]
    assert "tpu_custom_call" in compiled.as_text()  # the kernel is in the program


@pytest.mark.parametrize("n_layers", [2, 4])
def test_chunked_prefill_copies_no_staging_row(topo, n_layers):
    """The engine's chunk prefill (one staging row, donated): its attention
    is an XLA einsum that may keep a 4 MB one-row slice a layer, but nothing
    copies the stacked row (without the barrier in ``ops.attention._layer_of``
    XLA re-lays the whole V row for the einsum, a copy in and a copy out)."""
    from prime_tpu.models.llama import forward

    config = _qwen3_4b(n_layers)
    params, cache = _abstract_model(config, 1, C)

    def chunk(params, cache, tokens, offset):
        return forward(
            params, tokens, config, cache=cache, attn_impl="pallas",
            prefill_offset=offset, last_positions=jnp.zeros((1,), jnp.int32),
        )

    compiled = _compile(
        topo, chunk, params, cache, ((1, 512), jnp.int32), ((), jnp.int32), donate=(1,)
    )
    stacked = f"bf16[{n_layers},1,{KH},{D},{C}]"
    assert not [r for r in _results(compiled, "copy") if r.startswith(stacked)]


def test_sharded_decode_lowers_tp4(topo):
    """The mesh replica's decode (attn_impl="sharded"): flash-decode under
    shard_map over tp=4, each device streaming its two kv heads."""
    from prime_tpu.parallel.decode_sharded import flash_decode_sharded

    mesh = jax.sharding.Mesh(np.array(topo.devices).reshape(4), ("tp",))
    heads = NamedSharding(mesh, P(None, "tp", None, None))
    rep = NamedSharding(mesh, P())

    def sds(shape, dtype, sharding):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    fn = jax.jit(lambda q, k, v, n: flash_decode_sharded(q, k, v, n, mesh))
    fn.lower(
        sds((B, H, 1, D), jnp.bfloat16, heads),
        sds((B, KH, D, C), jnp.bfloat16, heads),
        sds((B, KH, D, C), jnp.bfloat16, heads),
        sds((B,), jnp.int32, rep),
    ).compile()


def test_forward_decode_lowers_tp4(topo, monkeypatch):
    """``forward`` decode as the tp=4 replica runs it (``attn_impl="sharded"``):
    the stacked cache, kv heads over tp, goes through the layer scan as a
    carry into the shard_mapped kernel with the layer index replicated; no
    device gathers or copies its cache shard."""
    from prime_tpu.models.llama import forward
    from prime_tpu.ops import attention
    from prime_tpu.parallel.sharding import param_shardings, serving_cache_spec

    # the process is on the CPU backend, where the kernel gate says no: steer
    # it here, as the engine on the chip would find it
    monkeypatch.setattr(attention, "_decode_pallas_eligible", lambda k_cache: True)
    config = _qwen3_4b(2)
    mesh = jax.sharding.Mesh(np.array(topo.devices).reshape(4), ("tp",))
    params, cache = _abstract_model(config, B, C)
    rep = NamedSharding(mesh, P())
    kv = NamedSharding(mesh, serving_cache_spec(config, mesh))

    def sds(s, sharding):
        return jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding)

    params = jax.tree.map(sds, params, param_shardings(mesh, config))
    cache = cache._replace(
        k=sds(cache.k, kv), v=sds(cache.v, kv), lengths=sds(cache.lengths, rep)
    )

    def step(params, cache, tokens):
        return forward(
            params, tokens, config, cache=cache, decode=True,
            attn_impl="sharded", mesh=mesh,
        )

    compiled = (
        jax.jit(step, donate_argnums=(1,))
        .lower(params, cache, jax.ShapeDtypeStruct((B, 1), jnp.int32, sharding=rep))
        .compile()
    )
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    shard = f"bf16[2,{B},{KH // 4},{D},{C}]"
    for opcode in ("copy", "all-gather"):
        assert not [r for r in _results(compiled, opcode) if r.startswith(shard)]
    assert compiled.memory_analysis().temp_size_in_bytes < 2 * B * KH * D * C * 2 // 4
