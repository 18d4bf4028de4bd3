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
"""

import dataclasses
import functools

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


def _compile(topo, fn, *args, sharding=None, **static):
    """AOT-compile ``fn`` for device 0 of the topology (or ``sharding``);
    ``args`` are (shape, dtype) pairs or pytrees of ShapeDtypeStructs."""
    sharding = sharding or SingleDeviceSharding(topo.devices[0])

    def place(x):
        if isinstance(x, tuple) and len(x) == 2 and isinstance(x[0], tuple):
            return jax.ShapeDtypeStruct(x[0], x[1], sharding=sharding)
        return jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding), x
        )

    jitted = jax.jit(functools.partial(fn, **static))
    return jitted.lower(*(place(a) for a in args)).compile()


# ---- kernels at the served shape ---------------------------------------------

_Q = ((B, H, 1, D), jnp.bfloat16)
_LENS = ((B,), jnp.int32)


def _kv(dtype, d=D):
    return ((B, KH, d, C), dtype)


_SCALE = ((B, KH, 1, C), jnp.float32)


@pytest.mark.parametrize(
    "variant",
    ["bf16", "window_sinks", "int8", "int4", "mha"],
)
def test_flash_decode_lowers(topo, variant):
    from prime_tpu.ops.pallas_attention import flash_decode

    if variant == "bf16":
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


def _two_layer_qwen3_4b():
    from prime_tpu.models.config import get_config

    return dataclasses.replace(get_config("qwen3-4b"), n_layers=2)


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

    config = _two_layer_qwen3_4b()
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

    config = _two_layer_qwen3_4b()
    params, cache = _abstract_model(config, 1, C)

    def chunk(params, cache, tokens, offset):
        return forward(
            params, tokens, config, cache=cache, attn_impl=attn_impl,
            prefill_offset=offset, last_positions=jnp.zeros((1,), jnp.int32),
        )

    _compile(topo, chunk, params, cache, ((1, 512), jnp.int32), ((), jnp.int32))


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
