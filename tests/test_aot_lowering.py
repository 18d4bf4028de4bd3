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


def _pallas_grids(fn, *args):
    """The grid of every ``pallas_call`` that ``fn`` makes on these (shape,
    dtype) operands, read off its jaxpr: what ``_compile`` hands to Mosaic. An
    axis whose bound is traced (flash-decode's work list) reads ``None``."""
    shapes = [jax.ShapeDtypeStruct(shape, dtype) for shape, dtype in args]
    grids = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                grids.append(tuple(
                    n if isinstance(n, int) else None for n in eqn.params["grid_mapping"].grid
                ))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jax.make_jaxpr(fn)(*shapes).jaxpr)
    return grids


def _compile_decode(topo, fn, *args):
    """Compile a flash-decode call for the v5e and hold its grid to the shape
    function's answer for the operands: the static axis is the row's head
    blocks, the other the work list under a DYNAMIC bound, whose most (every
    row full) is what this returns as (rows, head blocks, cache blocks). A
    later edit that falls back to a kv head and 128 slots a step (1024 steps at
    the served shapes), or to a static walk of the cache, fails here, on the
    CPU, before a chip run shows it."""
    from prime_tpu.ops.pallas_attention import decode_call_tiling

    _compile(topo, fn, *args)
    (q_shape, _), (k_shape, k_dtype) = args[0], args[1]
    cache = jax.ShapeDtypeStruct(k_shape, k_dtype)
    quantized = jnp.dtype(k_dtype).itemsize == 1
    heads, block_c = decode_call_tiling(q_shape[1], cache, quantized)
    batch, kv_heads, capacity = k_shape[-4], k_shape[-3], k_shape[-1]
    assert _pallas_grids(fn, *args) == [(kv_heads // heads, None)]
    return (batch, kv_heads // heads, capacity // block_c)


@pytest.mark.parametrize(
    "variant",
    ["bf16", "window_sinks", "int8", "int4", "mha", "stacked", "stacked_int8", "latent_64_heads"],
)
def test_flash_decode_lowers(topo, variant, monkeypatch):
    from prime_tpu.ops.pallas_attention import flash_decode

    # the tiling under test is the one from the shapes, whatever the
    # environment of the run says
    monkeypatch.delenv("PRIME_TPU_BLOCK_C", raising=False)
    served = {"bf16": (8, 1, 4), "stacked": (8, 1, 4), "latent_64_heads": (64, 1, 4)}
    if variant.startswith("stacked"):
        # what the model's layer scan hands over: the whole (L, B, KH, D, C)
        # buffer and a traced layer for the index maps
        layers, scalar = 4, ((), jnp.int32)
        if variant == "stacked":
            kv = ((layers, B, KH, D, C), jnp.bfloat16)
            grid = _compile_decode(
                topo, lambda q, k, v, n, l: flash_decode(q, k, v, n, layer=l),
                _Q, kv, kv, _LENS, scalar,
            )
        else:
            kv = ((layers, B, KH, D, C), jnp.int8)
            scale = ((layers, B, KH, 1, C), jnp.float32)
            grid = _compile_decode(
                topo,
                lambda q, k, v, n, ks, vs, l: flash_decode(
                    q, k, v, n, k_scale=ks, v_scale=vs, layer=l
                ),
                _Q, kv, kv, _LENS, scale, scale, scalar,
            )
    elif variant == "latent_64_heads":
        # LongCat-Flash's served shape: 64 slots, 64 query heads on ONE latent
        # head of rank + rope = 576, 8 attention rows, the latent as K and V
        kv = ((8, 64, 1, 576, C), jnp.bfloat16)
        grid = _compile_decode(
            topo, lambda q, k, n, l: flash_decode(q, k, k, n, layer=l),
            ((64, 64, 1, 576), jnp.bfloat16), kv, ((64,), jnp.int32), ((), jnp.int32),
        )
    elif variant == "bf16":
        grid = _compile_decode(topo, flash_decode, _Q, _kv(jnp.bfloat16), _kv(jnp.bfloat16), _LENS)
    elif variant == "window_sinks":
        grid = _compile_decode(
            topo,
            lambda q, k, v, n, s: flash_decode(q, k, v, n, window=1024, sinks=s),
            _Q, _kv(jnp.bfloat16), _kv(jnp.bfloat16), _LENS, ((H,), jnp.float32),
        )
    elif variant == "int8":
        grid = _compile_decode(
            topo,
            lambda q, k, v, n, ks, vs: flash_decode(q, k, v, n, k_scale=ks, v_scale=vs),
            _Q, _kv(jnp.int8), _kv(jnp.int8), _LENS, _SCALE, _SCALE,
        )
    elif variant == "int4":
        grid = _compile_decode(
            topo,
            lambda q, k, v, n, ks, vs: flash_decode(q, k, v, n, k_scale=ks, v_scale=vs),
            _Q, _kv(jnp.uint8, D // 2), _kv(jnp.uint8, D // 2), _LENS, _SCALE, _SCALE,
        )
    else:  # MHA: group of one query head per kv head
        kv = ((B, H, D, C), jnp.bfloat16)
        grid = _compile_decode(topo, flash_decode, _Q, kv, kv, _LENS)
    # the served shapes, spelled out: at most 32 steps a call for qwen3-4b
    # (every kv head of a row, 512 slots), 256 for the latent; both were 1024
    assert grid == served.get(variant, grid)
    assert (variant == "mha") == (grid[1] > 1)  # only 32 MHA heads overflow a step


@pytest.mark.parametrize("listed", [False, True], ids=["every_row", "live_rows_listed"])
@pytest.mark.parametrize("variant", ["bf16", "int8_with_scales", "mla_latent", "olmo_30_heads"])
def test_write_columns_lowers(topo, variant, listed):
    """Decode's column write as one kernel, at the served shape: the stacked
    leaves in, the same buffers out, a program a row's block of kv heads (all of
    them but for Olmo-Hybrid's 30, which go 15 a program) over the rows, or over
    the step's list of live rows under its traced bound."""
    from prime_tpu.ops.pallas_attention import column_heads, column_work_list, write_columns

    layers, kv_heads, dims, dtypes = 4, KH, (D, D), (jnp.bfloat16,) * 2
    if variant == "int8_with_scales":
        dims, dtypes = (D, D, 1, 1), (jnp.int8, jnp.int8, jnp.float32, jnp.float32)
    elif variant == "mla_latent":  # DeepSeek-V2's one latent head of rank + rope
        kv_heads, dims, dtypes = 1, (576,), (jnp.bfloat16,)
    elif variant == "olmo_30_heads":
        kv_heads = 30
    leaves = tuple(((layers, B, kv_heads, d, C), t) for d, t in zip(dims, dtypes))
    columns = tuple(((B, kv_heads, d, 1), t) for d, t in zip(dims, dtypes))
    count = len(leaves)

    def write(*a):
        work = column_work_list(a[-1]) if listed else None
        return write_columns(a[:count], a[count : 2 * count], a[-3], a[-2], work=work)

    args = (*leaves, *columns, ((), jnp.int32), _LENS, ((B,), jnp.bool_))
    compiled = _compile(topo, write, *args, donate=tuple(range(count)))
    heads = column_heads(kv_heads, max(d * jnp.dtype(t).itemsize for d, t in zip(dims, dtypes)), 128)
    assert heads == (15 if variant == "olmo_30_heads" else kv_heads)
    assert _pallas_grids(write, *args) == [(kv_heads // heads, None if listed else B)]
    # donated and aliased through the kernel: no second cache is allocated
    assert compiled.memory_analysis().temp_size_in_bytes < B * kv_heads * max(dims) * C


@pytest.mark.parametrize(
    "layers, slots, heads, d_k, d_v", [(3, 128, 32, 128, 128), (12, 16, 30, 96, 192)],
    ids=["qwen3-next-ep2", "olmo-hybrid-7b-pp2"],
)
def test_delta_decode_step_lowers(topo, layers, slots, heads, d_k, d_v):
    """The gated delta rule's state kernel at qwen3-next-ep2's shape (3 linear
    layers x 128 slots x 32 heads of (128, 128) float32) and at
    olmo-hybrid-7b-pp2's (12 x 16 x 30 heads of (96, 192): keys under a lane
    tile, values a tile and a half, blocks whose last two dimensions are the
    array's own): ONE kernel, the stacked leaf in and the same buffer out, a
    row's heads a grid step under the work list's traced bound."""
    from prime_tpu.ops.pallas_delta import delta_decode_step, delta_step_heads

    leaf = ((layers, slots, heads, d_k, d_v), jnp.float32)
    vector = lambda *shape: ((slots, heads, *shape), jnp.float32)  # noqa: E731
    args = (leaf, ((), jnp.int32), vector(d_k), vector(d_k), vector(d_v), vector(), vector(),
            ((slots,), jnp.bool_))
    compiled = _compile(topo, delta_decode_step, *args, donate=(0,))
    assert _pallas_grids(delta_decode_step, *args) == [
        (heads // delta_step_heads(heads, d_k, d_v), None)
    ] == [(1 if d_v % 128 == 0 else 2, None)]
    # donated and aliased through the kernel: no second state, not one layer's
    assert compiled.memory_analysis().temp_size_in_bytes < slots * heads * d_k * d_v * 4 // 16


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
    return _results_in(compiled.as_text(), opcode)


def _assert_listed_outside_the_layer_loop(compiled, steps, kernel="flash_decode", tables=2):
    """A kernel call's grid bound, per-row scalars and work list (its first
    four operands, the last ``tables`` of them the list's tables of ``steps`` +
    1 entries: flash-decode's rows and blocks, the column write's rows) reach
    the layer loop's body as loop-carried values, and nothing in that body
    makes a table of that size: the list is made once a step."""
    text = compiled.as_text()
    table = f"s32[{steps + 1}]"
    bodies = [c for c in re.split(r"\n\n(?=%|ENTRY)", text) if kernel in c and "tpu_custom_call" in c]
    assert bodies
    for body in bodies:
        calls = re.findall(rf"%{kernel}\S* = .+? custom-call\(([^)]*)\)", body)
        assert calls
        def carried(name):
            # the compiler may move a small operand between memory spaces on
            # its way (copy-start / copy-done): what it moves is what counts
            while moved := re.search(rf"{re.escape(name)} = .+? copy-(?:start|done)\((%[\w.-]+)\)", body):
                name = moved[1]
            return name

        for operands in calls:
            names = [carried(name.strip()) for name in operands.split(",")[:4]]
            assert all(name.startswith("%get-tuple-element") for name in names), names
            kinds = [re.search(rf"{re.escape(name)} = (\S+) get-tuple-element", body)[1] for name in names]
            kinds = [kind.split("{")[0] for kind in kinds]
            assert kinds[0] == "s32[]" and kinds[-tables:] == [table] * tables, kinds
        made = [r for r in _results_in(body, "fusion") + _results_in(body, "reduce") if r.startswith(table)]
        assert not made, made
    assert table in text


def _results_in(text, opcode):
    return re.compile(rf"^\s*(?:ROOT )?%\S+ = (\S+) {opcode}\(", re.M).findall(text)


@pytest.mark.parametrize("masked", [False, True], ids=["every_row_live", "retired_rows_masked"])
@pytest.mark.parametrize("n_layers", [2, 4])
def test_decode_keeps_the_cache_in_place(topo, n_layers, masked):
    """The engine's decode program (a scan of steps over ``forward(decode=
    True)``, cache donated) at the served shape: the cache rides both scans as
    a carry and each layer writes its column in place, so the program's
    temporaries stay under ONE layer's K + V (with the cache as the layer
    scan's xs/ys they exceeded the whole cache), nothing copies the stacked
    cache, and the kernel reads its layer out of the stacked buffer, so no
    one-layer slice is made. With the engine's mask of retired slots the same
    holds, and the kernels' work lists (the attention's and the column
    write's) are operands made outside the layer loop's body."""
    from prime_tpu.models.llama import forward

    config = _qwen3_4b(n_layers)
    params, cache = _abstract_model(config, B, C)

    def decode(params, cache, tok, active):
        def step(carry, _):
            cache, tok = carry
            logits, new_cache = forward(
                params, tok[:, None], config, positions=cache.lengths[:, None],
                cache=cache, decode=True, attn_impl="pallas",
                token_mask=active[:, None] if masked else None,
            )
            new_cache = new_cache._replace(
                lengths=jnp.where(active, new_cache.lengths, cache.lengths)
            )
            tok = jnp.argmax(logits[:, 0], axis=-1).astype(jnp.int32)
            return (new_cache, tok), tok

        (cache, _), toks = jax.lax.scan(step, (cache, tok), None, length=2)
        return cache, toks

    compiled = _compile(
        topo, decode, params, cache, ((B,), jnp.int32), ((B,), jnp.bool_), donate=(1,)
    )
    if masked:
        _assert_listed_outside_the_layer_loop(compiled, steps=B * 4)
        _assert_listed_outside_the_layer_loop(compiled, steps=B, kernel="write_columns", tables=1)
    one_layer_kv = 2 * B * KH * D * C * 2
    assert compiled.memory_analysis().temp_size_in_bytes < one_layer_kv
    stacked, one_layer = f"bf16[{n_layers},{B},{KH},{D},{C}]", f"bf16[{B},{KH},{D},{C}]"
    assert not [r for r in _results(compiled, "copy") if r.startswith(stacked)]
    assert not [r for r in _results(compiled, "fusion") if r.startswith(one_layer)]
    assert "tpu_custom_call" in compiled.as_text()  # the kernel is in the program


# (queries, stacked K/V leaf, one leaf for K and V, bytes of temporaries the parent's
# dense form held): the full layer's chunk prefill of the benchmark's configurations
_PREFILL_ATTENTIONS = {
    "laguna_48_over_8_heads_512_x_8192": ((1, 48, 512, 128), (2, 1, 8, 128, 8192), False, 1.61e9),
    "qwen3_4b_wave_of_8_512_x_2048": ((8, 32, 512, 128), (36, 8, 8, 128, 2048), False, 1.07e9),
    "qwen3_next_16_over_2_heads_2048_x_4096": ((1, 16, 2048, 256), (12, 1, 2, 256, 4096), False, 1.07e9),
    "longcat_64_over_one_latent_512_x_2048": ((4, 64, 512, 576), (56, 4, 1, 576, 2048), True, 2.15e9),
}


@pytest.mark.parametrize("shape", sorted(_PREFILL_ATTENTIONS))
def test_chunk_prefill_attention_walks_its_row_in_one_rolled_loop(topo, shape):
    """``cache_prefill_attention`` at a served shape, for a v5e: one ``while``
    whose body scores one column block (no Python loop over blocks: one body a
    program), no float32 value over chunk x row capacity anywhere in the text,
    no copy of the stacked leaf, and fewer temporaries than the dense form held
    (compiler, PR 43: 1.61 GB at Laguna's 48 heads over 512 x 8192, where the
    walk's tile stays on the chip)."""
    from prime_tpu.ops.attention import cache_prefill_attention, prefill_attention_block

    q, kv, shared, dense_temporaries = _PREFILL_ATTENTIONS[shape]
    seq, capacity = q[2], kv[-1]
    assert capacity % prefill_attention_block(capacity) == 0

    def attend(q, k, v, offset, layer):
        return cache_prefill_attention(q, k, k if shared else v, offset, 0.08, layer=layer)

    leaf = (kv, jnp.bfloat16)
    compiled = _compile(topo, attend, (q, jnp.bfloat16), leaf, leaf, ((), jnp.int32), ((), jnp.int32))
    text = compiled.as_text()
    assert len(re.findall(r" while\(", text)) == 1
    assert not re.findall(rf"f32\[[0-9,]*{seq},{capacity}\]", text)
    stacked = "bf16[" + ",".join(map(str, kv)) + "]"
    assert not [r for r in _results(compiled, "copy") if r.startswith(stacked)]
    assert compiled.memory_analysis().temp_size_in_bytes < 0.6 * dense_temporaries


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


# configuration: (slots of the served engine, tokens of its widest prompt chunk's group)
_EXPERT_LAYERS = {
    "longcat-flash-ep32": (64, 1024), "laguna-s-ep2": (32, 512), "qwen3-next-ep2": (128, 2048),
}


@pytest.mark.parametrize("preset", sorted(_EXPERT_LAYERS))
def test_grouped_matmul_lowers(topo, monkeypatch, preset):
    """The dropless expert layer's grouped matmul (megablox) at the three expert
    configurations' widths and the tile ``grouped_matmul_tiling`` gives them (the
    whole contraction, as wide as 12 MiB of VMEM double-buffer): a decode step's
    rows and a prompt chunk's (tile-aligned) against the whole (4, held, k, n)
    expert stack and a traced layer."""
    from prime_tpu.models import get_config
    from prime_tpu.ops import moe

    monkeypatch.setattr(moe, "_kernel_path", lambda: (True, False))  # the CPU backend would take ragged_dot
    config = get_config(preset)
    held, top_k, width = config.experts_held, config.experts_per_token, config.router_width
    budgets = [moe.row_budget(tokens, top_k, held, width) for tokens in _EXPERT_LAYERS[preset]]
    assert budgets[0] == -(-_EXPERT_LAYERS[preset][0] * min(top_k, held) // 32) * 32 < budgets[1]
    for k, n in ((config.d_model, config.d_ff), (config.d_ff, config.d_model)):
        assert moe.grouped_matmul_tiling(k, n, 2)[1] == k
        for rows in budgets:
            compiled = _compile(
                topo, moe.grouped_matmul, ((rows, k), jnp.bfloat16), ((4, held, k, n), jnp.bfloat16),
                ((held,), jnp.int32), ((), jnp.int32),
            )
            assert "tpu_custom_call" in compiled.as_text()
            # the stack goes to the kernel as it is: no copy of a layer's experts
            assert compiled.memory_analysis().temp_size_in_bytes < held * k * n


def test_shortcut_block_decode_reads_its_weights_in_place(topo, monkeypatch):
    """The engine-shaped decode of LongCat-Flash at the served size (64 slots x
    2048, two double layers): it compiles with the latent flash-decode, the
    column write and the three grouped matmuls in it, and no layer's experts
    or dense FFN matrices are copied out of their stacks before use (handed
    over as the scan's xs they were, 2.5 GB a layer; compiler, PR 26)."""
    from prime_tpu.models.config import get_config
    from prime_tpu.models.llama import forward
    from prime_tpu.ops import moe

    monkeypatch.setattr(moe, "_kernel_path", lambda: (True, False))
    config = dataclasses.replace(get_config("longcat-flash-ep32"), n_layers=2)
    params, cache = _abstract_model(config, 64, C)

    def decode(params, cache, tok, active):
        def step(carry, _):
            cache, tok, counts = carry
            logits, cache, step_counts = forward(
                params, tok[:, None], config, positions=cache.lengths[:, None], cache=cache,
                decode=True, attn_impl="pallas", token_mask=active[:, None],
                return_moe_stats=True,
            )
            tok = jnp.argmax(logits[:, 0], axis=-1).astype(jnp.int32)
            return (cache, tok, counts + step_counts), tok

        zero = jnp.zeros((len(moe.MOE_STATS),), jnp.int32)
        (cache, _, counts), toks = jax.lax.scan(step, (cache, tok, zero), None, length=2)
        return cache, toks, counts

    compiled = _compile(
        topo, decode, params, cache, ((64,), jnp.int32), ((64,), jnp.bool_), donate=(1,)
    )
    text = compiled.as_text()
    assert len(re.findall("tpu_custom_call", text)) >= 5
    top_level = lambda shape: [  # materialised results, not operand fusions inside a matmul
        r for op in ("fusion", "copy") for r in _results(compiled, op) if r.startswith(shape)
    ]
    assert not top_level("bf16[16,6144,2048]") and not top_level("bf16[16,2048,6144]")
    assert not top_level("bf16[2,6144,12288]") and not top_level("bf16[2,12288,6144]")
    assert compiled.memory_analysis().temp_size_in_bytes < 1.0e9
    _assert_listed_outside_the_layer_loop(compiled, steps=64 * 4)  # the latent: at most 64 x 1 x 4 steps
    _assert_listed_outside_the_layer_loop(compiled, steps=64, kernel="write_columns", tables=1)


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


@pytest.mark.parametrize("masked", [False, True], ids=["every_row_live", "retired_rows_masked"])
def test_forward_decode_lowers_tp4(topo, monkeypatch, masked):
    """``forward`` decode as the tp=4 replica runs it (``attn_impl="sharded"``):
    the stacked cache, kv heads over tp, goes through the layer scan as a
    carry into the shard_mapped kernel with the layer index replicated; no
    device gathers or copies its cache shard. With retired rows masked the
    kernel's work list is made once a step, outside the layer loop, and
    handed to every device (no axis shards the rows here)."""
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

    def step(params, cache, tokens, active):
        return forward(
            params, tokens, config, cache=cache, decode=True,
            attn_impl="sharded", mesh=mesh,
            token_mask=active[:, None] if masked else None,
        )

    compiled = (
        jax.jit(step, donate_argnums=(1,))
        .lower(
            params, cache, jax.ShapeDtypeStruct((B, 1), jnp.int32, sharding=rep),
            jax.ShapeDtypeStruct((B,), jnp.bool_, sharding=rep),
        )
        .compile()
    )
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    shard = f"bf16[2,{B},{KH // 4},{D},{C}]"
    for opcode in ("copy", "all-gather"):
        assert not [r for r in _results(compiled, opcode) if r.startswith(shard)]
    assert compiled.memory_analysis().temp_size_in_bytes < 2 * B * KH * D * C * 2 // 4
    if masked:
        _assert_listed_outside_the_layer_loop(compiled, steps=B * 4)


# ---- the sambay block at the served size: phi4-mini-flash, 64 slots x 4096 -----


def _phi4_flash_engine_programs():
    """The engine's decode and chunk_prefill bodies (serve/engine.py), with the
    sampler left out: a scan of decode steps over the donated cache, and a
    chunk of a staging row at an offset with the padding masked."""
    from prime_tpu.models.config import get_config
    from prime_tpu.models.llama import forward

    config = get_config("phi4-mini-flash")

    def decode(params, cache, tok, active):
        def step(carry, _):
            cache, tok = carry
            logits, new_cache = forward(
                params, tok[:, None], config, positions=cache.lengths[:, None], cache=cache,
                decode=True, attn_impl="auto", token_mask=active[:, None],
            )
            new_cache = new_cache._replace(
                lengths=jnp.where(active, new_cache.lengths, cache.lengths)
            )
            tok = jnp.argmax(logits[:, 0], axis=-1).astype(jnp.int32)
            return (new_cache, tok), tok

        (cache, _), toks = jax.lax.scan(step, (cache, tok), None, length=8)
        return cache, toks

    def chunk_prefill(params, row, tokens, offset, last_in_chunk):
        live = jnp.arange(tokens.shape[1])[None, :] <= last_in_chunk[:, None]
        logits, row = forward(
            params, tokens, config, cache=row, attn_impl="auto", prefill_offset=offset,
            last_positions=last_in_chunk, token_mask=live,
        )
        return row, logits

    return config, decode, chunk_prefill


def test_sambay_decode_fits_the_chip_with_ring_state_and_row_in_place(topo, monkeypatch):
    """phi4-mini-flash's engine-shaped decode at 64 slots x 4096: all 32 layers
    at the published widths beside the whole cache, under 15 GB on a v5e; the
    full row and the rings go through the flash-decode kernel and the column
    write, and the step holds no copy of a ring, of the state or of the row."""
    from prime_tpu.ops import attention

    # the chip's choice: a TPU backend sends a 4096-column row to the kernel
    monkeypatch.setattr(attention, "_decode_pallas_eligible", lambda k: k.shape[-1] >= 2048)
    config, decode, _ = _phi4_flash_engine_programs()
    params, cache = _abstract_model(config, 64, 4096)
    compiled = _compile(
        topo, decode, params, cache, ((64,), jnp.int32), ((64,), jnp.bool_), donate=(1,)
    )
    memory = compiled.memory_analysis()
    weights_and_cache = memory.argument_size_in_bytes
    assert 10.0e9 < weights_and_cache < 11.5e9  # 7.7 GB of weights, 2.9 GB of slots
    assert weights_and_cache + memory.temp_size_in_bytes < 15.0e9
    # no cache-sized temporary: the largest leaf is a ring of 8 x 64 x 10 x 128 x 512 bf16
    assert memory.temp_size_in_bytes < 0.6e9
    text = compiled.as_text()
    assert len(re.findall("tpu_custom_call", text)) >= 4  # ring + row: write and attend
    for leaf in ("bf16[8,64,10,128,512]", "bf16[1,64,10,128,4096]", "f32[9,64,16,5120]"):
        assert not [r for r in _results(compiled, "copy") if r.startswith(leaf)], leaf


@pytest.mark.parametrize("wave", [1, 2])
def test_sambay_chunk_prefill_fits_beside_the_engines_cache(topo, wave):
    """A 512-token chunk of a 4096-column staging row, a wave of one and of two
    (the widest the engine forms at these shapes): weights, row and temporaries
    under the 15 GB less the 2.9 GB the engine's own slots hold."""
    config, _, chunk_prefill = _phi4_flash_engine_programs()
    params, row = _abstract_model(config, wave, 4096)
    compiled = _compile(
        topo, chunk_prefill, params, row, ((wave, 512), jnp.int32), ((), jnp.int32),
        ((wave,), jnp.int32), donate=(1,),
    )
    memory = compiled.memory_analysis()
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes < 15.0e9 - 2.9e9


# ---- the gated_delta block at the served size: qwen3-next-ep2, 128 slots x 4096 -----


def _qwen3_next_engine_programs(preset="qwen3-next-ep2"):
    """The engine's decode and chunk_prefill bodies (serve/engine.py) for a
    gated_delta preset, the sampler left out, the expert layer's counts carried
    (zeros where the feed-forward is dense)."""
    from prime_tpu.models.config import get_config
    from prime_tpu.models.llama import forward
    from prime_tpu.ops.moe import MOE_STATS

    config = get_config(preset)

    def decode(params, cache, tok, active):
        def step(carry, _):
            cache, tok, counts = carry
            logits, new_cache, step_counts = forward(
                params, tok[:, None], config, positions=cache.lengths[:, None], cache=cache,
                decode=True, attn_impl="auto", token_mask=active[:, None], return_moe_stats=True,
            )
            new_cache = new_cache._replace(
                lengths=jnp.where(active, new_cache.lengths, cache.lengths)
            )
            tok = jnp.argmax(logits[:, 0], axis=-1).astype(jnp.int32)
            return (new_cache, tok, counts + step_counts), tok

        zero = jnp.zeros((len(MOE_STATS),), jnp.int32)
        (cache, _, counts), toks = jax.lax.scan(step, (cache, tok, zero), None, length=8)
        return cache, toks, counts

    def chunk_prefill(params, row, tokens, offset, last_in_chunk):
        live = jnp.arange(tokens.shape[1])[None, :] <= last_in_chunk[:, None]
        logits, row, counts = forward(
            params, tokens, config, cache=row, attn_impl="auto", prefill_offset=offset,
            last_positions=last_in_chunk, token_mask=live, return_moe_stats=True,
        )
        return row, logits, counts

    return config, decode, chunk_prefill


def _chip_takes_the_kernels(monkeypatch):
    """What a TPU backend chooses: the flash-decode kernel for a 4096-column
    row, megablox for the grouped products (the CPU backend takes neither)."""
    from prime_tpu.ops import attention, moe

    monkeypatch.setattr(attention, "_decode_pallas_eligible", lambda k: k.shape[-1] >= 2048)
    monkeypatch.setattr(moe, "_kernel_path", lambda: (True, False))


def test_gated_delta_decode_fits_the_chip_with_state_and_row_in_place(topo, monkeypatch):
    """qwen3-next-ep2's engine-shaped decode at 128 slots x 4096: one period at
    the published widths, 256 experts a layer, beside the whole cache. K/V rows
    exist for the one full layer (2 heads of 256 through flash-decode and the
    column write), the 12 grouped products read the expert stacks in place, and
    the step holds no copy of the stacked state, tail or row. Nor one layer's
    slice of the state (0.27 GB, which the XLA step materialised for its two
    readers): three custom calls more, the state kernel's (ops/pallas_delta.py),
    step the live rows' blocks where they lie."""
    _chip_takes_the_kernels(monkeypatch)
    config, decode, _ = _qwen3_next_engine_programs()
    params, cache = _abstract_model(config, 128, 4096)
    compiled = _compile(
        topo, decode, params, cache, ((128,), jnp.int32), ((128,), jnp.bool_), donate=(1,)
    )
    memory = compiled.memory_analysis()
    assert 9.1e9 < memory.argument_size_in_bytes < 9.4e9  # 7.36 GB of weights, 1.90 GB of slots
    assert memory.temp_size_in_bytes < 0.4e9
    text = compiled.as_text()
    # 12 grouped products, write and attend, and the three linear layers' state kernel
    assert len(re.findall("tpu_custom_call", text)) >= 17
    for leaf in ("f32[3,128,32,128,128]", "bf16[3,128,3,8192]", "bf16[1,128,2,256,4096]",
                 "bf16[4,256,2048,512]", "bf16[4,256,512,2048]"):
        assert not [r for r in _results(compiled, "copy") if r.startswith(leaf)], leaf
    # no operation makes one layer's state: it is never sliced out of the stack
    assert not re.findall(r"= f32\[128,32,128,128\]", text)


@pytest.mark.parametrize("tokens, temporaries", [(512, 0.6e9), (2048, 1.3e9)])
def test_gated_delta_chunk_prefill_fits_beside_the_engines_cache(topo, monkeypatch, tokens, temporaries):
    """A chunk of a 4096-column staging row, a wave of one (the cell's
    ``max_wave``), at 512 tokens and at the 2048 the rule from shapes gives
    this engine (``prefill_shapes``): weights, row and temporaries (the chunked
    delta rule's float32 blocks, the experts' row buffers, and at 2048 tokens
    half a gibibyte of scores) under the 15 GB less the 1.9 GB the engine's
    own slots hold. The longer chunk is one token group: every grouped product
    appears once, not once a group inside a loop."""
    from prime_tpu.serve.engine import prefill_shapes

    _chip_takes_the_kernels(monkeypatch)
    config, _, chunk_prefill = _qwen3_next_engine_programs()
    assert prefill_shapes(config, 4096, max_wave=1, dropless_experts=True) == (2048, 1)
    params, row = _abstract_model(config, 1, 4096)
    compiled = _compile(
        topo, chunk_prefill, params, row, ((1, tokens), jnp.int32), ((), jnp.int32),
        ((1,), jnp.int32), donate=(1,),
    )
    memory = compiled.memory_analysis()
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes < 15.0e9 - 1.9e9
    assert memory.temp_size_in_bytes < temporaries
    assert f"bf16[{tokens * 10},2048]" in compiled.as_text()  # all of the chunk's rows, sorted once


# ---- the same block, dense and post-norm: olmo-hybrid-7b-pp2, 16 slots x 2048 -----


def test_olmo_hybrid_decode_fits_the_chip_with_state_and_rows_in_place(topo, monkeypatch):
    """olmo-hybrid-7b-pp2's engine-shaped decode at 16 slots x 2048: four periods
    at the published widths beside the whole cache (8.2 GB of weights, 2.0 GB of
    K/V rows for the four full layers, 0.44 GB of float32 state and conv tails).
    The step holds no copy of the stacked state, tail or rows, and never slices
    one layer's state out: the state kernel steps the live rows' (30, 96, 192)
    blocks where they lie."""
    _chip_takes_the_kernels(monkeypatch)
    config, decode, _ = _qwen3_next_engine_programs("olmo-hybrid-7b-pp2")
    params, cache = _abstract_model(config, 16, 2048)
    compiled = _compile(
        topo, decode, params, cache, ((16,), jnp.int32), ((16,), jnp.bool_), donate=(1,)
    )
    memory = compiled.memory_analysis()
    assert 10.5e9 < memory.argument_size_in_bytes < 10.8e9
    assert memory.temp_size_in_bytes < 0.4e9
    text = compiled.as_text()
    # write and attend a period, and the state kernel a linear layer of it
    assert len(re.findall("tpu_custom_call", text)) >= 5
    for leaf in ("f32[12,16,30,96,192]", "bf16[12,16,3,11520]", "bf16[4,16,30,128,2048]"):
        assert not [r for r in _results(compiled, "copy") if r.startswith(leaf)], leaf
    assert not re.findall(r"= f32\[16,30,96,192\]", text)


@pytest.mark.parametrize("wave, temporaries", [(1, 0.25e9), (8, 1.9e9)])
def test_olmo_hybrid_chunk_prefill_fits_beside_the_engines_cache(topo, monkeypatch, wave, temporaries):
    """A 512-token chunk of a 2048-column staging row, a wave of one (a prefix
    hit's suffix from a restored state; the cell's ``max_wave``) and the widest
    wave the rule from shapes gives 30 heads at 512 x 2048 (8): weights, rows
    and temporaries under the 15 GB less the 2.45 GB the engine's own slots
    hold. What a wave of 8 takes (1.2 GB of rows, 1.7 GB of temporaries) is
    what the cell's prefix cache has instead."""
    from prime_tpu.serve.engine import prefill_shapes

    _chip_takes_the_kernels(monkeypatch)
    config, _, chunk_prefill = _qwen3_next_engine_programs("olmo-hybrid-7b-pp2")
    assert prefill_shapes(config, 2048) == (512, 8)
    params, row = _abstract_model(config, wave, 2048)
    compiled = _compile(
        topo, chunk_prefill, params, row, ((wave, 512), jnp.int32), ((), jnp.int32),
        ((wave,), jnp.int32), donate=(1,),
    )
    memory = compiled.memory_analysis()
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes < 15.0e9 - 2.45e9
    assert memory.temp_size_in_bytes < temporaries


def test_gated_delta_finalize_splices_state_and_row_in_place(topo):
    """The engine's own finalize program (a staged row of 4096 columns, a wave
    of one, into 128 slots, its first token sampled from the 75968 logits): the
    walk over the cache's leaves splices K/V by columns and state and tail
    whole, and keeps no second copy of a leaf."""
    import types

    from prime_tpu.models.config import get_config
    from prime_tpu.serve.engine import ContinuousBatchingEngine

    config = get_config("qwen3-next-ep2")
    finalize = ContinuousBatchingEngine._make_finalize_batch(
        types.SimpleNamespace(_cache_constraint=lambda: None)
    )
    _, cache = _abstract_model(config, 128, 4096)
    _, rows = _abstract_model(config, 1, 4096)
    place = lambda tree: jax.tree.map(  # noqa: E731
        lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=SingleDeviceSharding(topo.devices[0])
        ), tree,
    )
    slot = lambda dtype, n=128: jax.ShapeDtypeStruct((n,), dtype)  # noqa: E731
    compiled = finalize.lower(*place((
        cache, slot(jnp.int32), slot(jnp.float32), slot(jnp.float32), slot(jnp.int32), rows,
        jax.ShapeDtypeStruct((1, 1, config.vocab_size), jnp.float32),
        slot(jnp.int32, 1), slot(jnp.int32, 1), slot(jnp.float32, 1), slot(jnp.float32, 1),
        slot(jnp.int32, 1), slot(jnp.uint32, 2),
    ))).compile()
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < 0.1e9
    for leaf in ("f32[3,128,32,128,128]", "bf16[1,128,2,256,4096]"):
        assert not [r for r in _results(compiled, "copy") if r.startswith(leaf)], leaf


# ---- layers of more than one kind on the standard block: laguna-s-ep2, 32 slots x 8192 ----


@pytest.mark.parametrize("heads, cache", [(48, (2, 32, 8, 128, 8192)), (72, (3, 32, 8, 128, 512))],
                         ids=["group_6_row_of_8192", "group_9_ring_of_512"])
def test_flash_decode_and_write_columns_lower_at_query_groups_6_and_9(topo, monkeypatch, heads, cache):
    """laguna-s-ep2's two attentions in one decode step: 48 query heads over 8
    K/V heads of 128 on a row of 8192 (a group of 6), 72 on a ring of 512 (a
    group of 9; the cells before it ran groups of 1, 2, 4 and 8), each under its
    own work list, and the column write beside each."""
    from prime_tpu.ops.pallas_attention import (
        column_work_list, decode_call_tiling, decode_work_list, flash_decode, write_columns,
    )

    monkeypatch.delenv("PRIME_TPU_BLOCK_C", raising=False)
    layers, batch, kv_heads, d, columns = cache
    kv = (cache, jnp.bfloat16)
    tile_heads, block_c = decode_call_tiling(heads, jax.ShapeDtypeStruct(cache[1:], jnp.bfloat16), False)
    assert (tile_heads, block_c) == (8, min(columns, 512))  # every K/V head of a row a step

    def attend(q, k, v, n, layer):
        work = decode_work_list(n, block_c, columns // block_c)
        return flash_decode(q, k, v, n, layer=layer, work=work)

    args = (((batch, heads, 1, d), jnp.bfloat16), kv, kv, ((batch,), jnp.int32), ((), jnp.int32))
    _compile(topo, attend, *args)
    assert _pallas_grids(attend, *args) == [(1, None)]

    def write(k, v, k_col, v_col, layer, at, live):
        return write_columns((k, v), (k_col, v_col), layer, at, work=column_work_list(live))

    column = ((batch, kv_heads, d, 1), jnp.bfloat16)
    args = (kv, kv, column, column, ((), jnp.int32), ((batch,), jnp.int32), ((batch,), jnp.bool_))
    compiled = _compile(topo, write, *args, donate=(0, 1))
    assert _pallas_grids(write, *args) == [(1, None)]
    assert compiled.memory_analysis().temp_size_in_bytes < batch * kv_heads * d * columns


def test_laguna_decode_fits_the_chip_with_rows_and_rings_in_place(topo, monkeypatch):
    """laguna-s-ep2's engine-shaped decode at 32 slots x 8192: the dense layer 0
    and one period at the published widths, 128 experts a layer, beside the whole
    cache (11.14 GB of weights, 2.15 GB of rows for the two full layers, 0.20 GB
    of rings for the three sliding ones). Five column writes and five flash-decode
    calls at two head counts, the 12 grouped products read the expert stacks in
    place, and the step holds no copy of a stacked row, ring or expert matrix."""
    _chip_takes_the_kernels(monkeypatch)
    config, decode, _ = _qwen3_next_engine_programs("laguna-s-ep2")
    params, cache = _abstract_model(config, 32, 8192)
    compiled = _compile(
        topo, decode, params, cache, ((32,), jnp.int32), ((32,), jnp.bool_), donate=(1,)
    )
    memory = compiled.memory_analysis()
    assert 13.4e9 < memory.argument_size_in_bytes < 13.6e9
    assert memory.temp_size_in_bytes < 0.4e9
    text = compiled.as_text()
    # layer 0 and layer 4 unrolled, the three sliding layers one scanned body: a column
    # write and a flash-decode each, and three grouped products an expert layer
    assert len(re.findall("tpu_custom_call", text)) >= 12
    for leaf in ("bf16[2,32,8,128,8192]", "bf16[3,32,8,128,512]", "bf16[4,128,3072,1024]",
                 "bf16[4,128,1024,3072]"):
        assert not [r for r in _results(compiled, "copy") if r.startswith(leaf)], leaf


def test_laguna_chunk_prefill_fits_beside_the_engines_cache(topo, monkeypatch):
    """The widest chunk prefill the rule from shapes gives (512 tokens, a wave of
    one) over a staging row of 8192 columns and its three rings: weights, row and
    temporaries (the experts' row buffers; a full layer's scores are a column
    block at a time and the 0.8 GB tensor over the whole row is gone: 1.67 GB of
    temporaries before the walk, 0.21 after; compiler, PR 43) under the 15.75 GB
    the chip gives less the 2.35 GB the engine's own slots hold."""
    from prime_tpu.ops import moe
    from prime_tpu.serve.engine import prefill_shapes

    _chip_takes_the_kernels(monkeypatch)
    config, _, chunk_prefill = _qwen3_next_engine_programs("laguna-s-ep2")
    assert prefill_shapes(config, 8192, dropless_experts=True) == (512, 1)
    params, row = _abstract_model(config, 1, 8192)
    compiled = _compile(
        topo, chunk_prefill, params, row, ((1, 512), jnp.int32), ((), jnp.int32),
        ((1,), jnp.int32), donate=(1,),
    )
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < 0.4e9
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes < 15.75e9 - 2.35e9
    text = compiled.as_text()
    # all of the chunk's rows, sorted once, every group from a row tile: the 5120
    # the pairs may take and a tile less a row for each of the 128 held experts
    assert moe.row_budget(512, 10, 128, 256) == 512 * 10 + 128 * 31
    assert f"bf16[{512 * 10 + 128 * 31},3072]" in text
    # the grouped products read the expert stacks in place: no second copy
    for leaf in ("bf16[4,128,3072,1024]", "bf16[4,128,1024,3072]"):
        assert not [r for r in _results(compiled, "copy") if r.startswith(leaf)], leaf
    assert not re.findall(r"f32\[[0-9,]*512,8192\]", text)  # no scores over the whole row
