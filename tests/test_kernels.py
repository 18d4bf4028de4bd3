"""Kernel-campaign tests (CPU; pallas kernels run in interpret mode).

The pins, per docs/kernels.md "Kernel campaign & autotune":

- **Config registry**: resolution order env > tuned artifact > default; a
  tuned artifact round-trips through ``save_artifact``/``load_tuned`` and
  flips ``source()``; malformed/mismatched artifacts degrade to defaults.
- **Page pool**: the pool's store/gather/split/free lifecycle round-trips
  exactly (the gather is plain XLA; tests/test_aot_lowering.py compiles it
  for the chip).
- **Fused gathered-LoRA**: the one-pass kernel is BIT-IDENTICAL to the
  base + gather + einsum chain it replaces (rounding contract in
  ops/pallas_lora.py), across mixed adapter ids.
- **int4 KV decode**: the kernel matches the widen-in-graph XLA reference
  to fp32 accumulation-order noise (~3e-7 observed; 5e-6 pinned), and the
  int4 quantization itself sits within the documented rounding tolerance
  of the fp cache (~0.09 observed on unit-normal KV; 0.2 pinned — 4-bit
  symmetric rounding error, NOT a kernel property).
- **Autotune**: a dry-run sweep persists an artifact the resolution path
  demonstrably loads.
"""

import json
import warnings

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from prime_tpu.ops import kernel_configs


@pytest.fixture(autouse=True)
def _clean_registry(monkeypatch, tmp_path):
    """Isolate every test from ambient env overrides and any committed
    artifact for this host's device kind; clear the jitted kernels whose
    traces baked in a prior test's resolution."""
    for knob in ("PRIME_TPU_BLOCK_Q", "PRIME_TPU_BLOCK_K", "PRIME_TPU_BLOCK_C"):
        monkeypatch.delenv(knob, raising=False)
    monkeypatch.setenv("PRIME_TPU_KERNEL_CONFIG_DIR", str(tmp_path / "cfg"))
    kernel_configs.invalidate_cache()
    yield
    kernel_configs.invalidate_cache()
    from prime_tpu.ops.pallas_lora import fused_lora_matmul

    fused_lora_matmul.clear_cache()


# ---- config registry ---------------------------------------------------------


def test_resolve_order_default_tuned_env(monkeypatch, tmp_path):
    assert kernel_configs.resolve("flash_prefill", "block_q") == 128
    assert kernel_configs.source() == "default"

    out = tmp_path / "cfg"
    path = kernel_configs.save_artifact(
        {"flash_prefill": {"block_q": 256, "us": 12.5}}, directory=str(out)
    )
    assert json.loads(open(path).read())["schema"] == kernel_configs.SCHEMA_VERSION
    assert kernel_configs.resolve("flash_prefill", "block_q") == 256
    # params the artifact doesn't cover keep their defaults
    assert kernel_configs.resolve("flash_prefill", "block_k") == 128
    assert kernel_configs.source() == "tuned"

    monkeypatch.setenv("PRIME_TPU_BLOCK_Q", "64")
    assert kernel_configs.resolve("flash_prefill", "block_q") == 64
    assert kernel_configs.source() == "env"


def test_resolve_unknown_pair_raises():
    with pytest.raises(KeyError):
        kernel_configs.resolve("flash_prefill", "nope")
    with pytest.raises(KeyError):
        kernel_configs.resolve("not_a_kernel", "block_q")


def test_malformed_artifact_degrades_to_defaults(tmp_path):
    out = tmp_path / "cfg"
    out.mkdir()
    kind = kernel_configs.device_kind()
    (out / f"{kind}.json").write_text('{"schema": 999, "kernels": {}}')
    kernel_configs.invalidate_cache()
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        assert kernel_configs.load_tuned() is None
    assert any("ignoring kernel config artifact" in str(w.message) for w in caught)
    # flash-decode's default is "no preference": the block comes from the
    # cache's shape (pallas_attention.decode_tiling)
    assert kernel_configs.resolve("flash_decode", "block_c") == 0
    assert kernel_configs.source() == "default"


def test_wrong_device_kind_artifact_ignored(tmp_path):
    path = kernel_configs.save_artifact(
        {"flash_decode": {"block_c": 512}}, kind="tpu-v999"
    )
    assert path.endswith("tpu-v999.json")
    # this host's kind is not tpu-v999: the artifact must not feed it
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        assert kernel_configs.load_tuned() is None
    assert kernel_configs.resolve("flash_decode", "block_c") == 0


# ---- page pool ---------------------------------------------------------------


def test_kv_pool_store_gather_split_free():
    from prime_tpu.serve.kv_pool import PagedKVPool, PagedSegment

    rng = np.random.default_rng(1)
    leaves = lambda t: {
        "k": jnp.asarray(rng.normal(size=(2, 1, 3, 8, t)).astype(np.float32)),
        "v": jnp.asarray(rng.normal(size=(2, 1, 3, 8, t)).astype(np.float32)),
    }
    page_nbytes = 2 * (2 * 3 * 8 * 16 * 4)
    pool = PagedKVPool(budget_bytes=page_nbytes * 4, page_tokens=16)

    seg = leaves(48)
    pages = pool.store(seg)
    assert pages is not None and len(pages) == 3 and pool.free_pages == 1

    # materialize round-trips the exact bytes
    back = pool.materialize(pages, 48)
    for name in seg:
        assert np.array_equal(np.asarray(back[name]), np.asarray(seg[name]))

    # over-budget store falls back (returns None, frees nothing)
    assert pool.store(leaves(32)) is None and pool.free_pages == 1

    # unaligned store falls back
    assert pool.store(leaves(10)) is None

    # gather_row lays pages contiguously, zeros past the table
    table = np.full(4, -1, dtype=np.int32)
    table[:3] = pages
    row = pool.gather_row(table)
    got = np.asarray(row["k"])
    assert got.shape == (2, 1, 3, 8, 64)
    assert np.array_equal(got[..., :48], np.asarray(seg["k"]))
    assert np.all(got[..., 48:] == 0)

    # split is a zero-copy page repartition; close frees exactly once
    ps = PagedSegment(pool, pages, 48)
    upper, lower = ps.split(16)
    assert upper.pages == pages[:1] and lower.pages == pages[1:]
    assert upper.nbytes + lower.nbytes == len(pages) * pool.page_nbytes
    upper.close()
    lower.close()
    lower.close()  # double close is a no-op
    assert pool.free_pages == 4
    with pytest.raises(ValueError):
        PagedSegment(pool, [0, 1], 32).split(8)  # not page-aligned


def test_kv_pool_budget_too_small_disables():
    from prime_tpu.serve.kv_pool import PagedKVPool

    pool = PagedKVPool(budget_bytes=16, page_tokens=16)
    seg = {"k": jnp.ones((2, 1, 3, 8, 16), dtype=jnp.float32)}
    assert pool.store(seg) is None
    assert pool.store(seg) is None  # stays disabled, no crash


# ---- fused gathered-LoRA -----------------------------------------------------


def _lora_reference(x, w, a, b, ids):
    """The einsum chain from models/llama._lora_mm, verbatim rounding."""
    y = x @ w
    a_rows = a[ids].astype(jnp.float32)
    b_rows = b[ids].astype(jnp.float32)
    h = jnp.einsum("bsd,bdr->bsr", x.astype(jnp.float32), a_rows)
    delta = jnp.einsum("bsr,bro->bso", h, b_rows)
    return y + delta.astype(y.dtype)


@pytest.mark.parametrize("seq", [1, 6])
def test_fused_lora_bit_identical_to_einsum_chain(seq):
    from prime_tpu.ops.pallas_lora import fused_lora_matmul

    rng = np.random.default_rng(2)
    batch, d_in, rank, d_out, bank = 4, 24, 4, 40, 3
    x = jnp.asarray(rng.normal(size=(batch, seq, d_in)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(d_in, d_out)).astype(np.float32))
    a = jnp.asarray(rng.normal(size=(bank, d_in, rank)).astype(np.float32))
    b = jnp.asarray(rng.normal(size=(bank, rank, d_out)).astype(np.float32))
    ids = jnp.asarray([0, 2, 1, 2], dtype=jnp.int32)  # mixed wave, incl. base
    out = fused_lora_matmul(x, w, a, b, ids, interpret=True)
    ref = _lora_reference(x, w, a, b, ids)
    assert np.array_equal(np.asarray(out), np.asarray(ref))


def test_lora_mm_dispatches_kernel_under_interpret(monkeypatch):
    """models/llama._lora_mm routes through the fused kernel when interpret
    mode marks it eligible, and the result still matches the chain."""
    from prime_tpu.models.llama import _lora_kernel_eligible, _lora_mm

    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(2, 3, 16)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(16, 24)).astype(np.float32))
    a = jnp.asarray(rng.normal(size=(2, 16, 4)).astype(np.float32))
    b = jnp.asarray(rng.normal(size=(2, 4, 24)).astype(np.float32))
    ids = jnp.asarray([1, 0], dtype=jnp.int32)
    lp = {"wq": w, "lora:wq:a": a, "lora:wq:b": b}

    monkeypatch.delenv("PRIME_TPU_PALLAS_INTERPRET", raising=False)
    assert not _lora_kernel_eligible(w, x, b)  # CPU, no interpret: einsum path
    ref = _lora_mm(x, lp, "wq", ids)

    monkeypatch.setenv("PRIME_TPU_PALLAS_INTERPRET", "1")
    assert _lora_kernel_eligible(w, x, b)
    out = _lora_mm(x, lp, "wq", ids)
    assert np.array_equal(np.asarray(out), np.asarray(ref))
    # quantized base weights keep the chain (the kernel only fuses plain 2-D)
    assert not _lora_kernel_eligible((w, jnp.ones((1, 24))), x, b)


# ---- int4 KV decode ----------------------------------------------------------


def _int4_cache(seed=4, batch=2, kv_heads=1, dim=16, capacity=64):
    from prime_tpu.models.quantize import quantize_kv_int4

    rng = np.random.default_rng(seed)
    k = jnp.asarray(rng.normal(size=(batch, kv_heads, dim, capacity)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(batch, kv_heads, dim, capacity)).astype(np.float32))
    kq, ks = quantize_kv_int4(k)
    vq, vs = quantize_kv_int4(v)
    return k, v, kq, ks, vq, vs


def test_quantize_kv_int4_round_trip():
    from prime_tpu.models.quantize import quantize_kv_int4, unpack_kv_int4

    k, _, kq, ks, _, _ = _int4_cache()
    assert kq.dtype == jnp.uint8 and kq.shape == (2, 1, 8, 64)  # packed halves
    assert ks.shape == (2, 1, 1, 64)
    recon = np.asarray(unpack_kv_int4(kq) * ks)
    # 4-bit symmetric: |err| <= scale/2 per element
    assert np.all(np.abs(recon - np.asarray(k)) <= np.asarray(ks) / 2 + 1e-7)
    with pytest.raises(ValueError):
        quantize_kv_int4(jnp.ones((1, 1, 3, 16)))  # odd feature dim


def test_int4_decode_kernel_matches_xla_reference(monkeypatch):
    """flash_decode's int4 variant (interpret) vs the widen-in-graph XLA
    path: accumulation-order noise only."""
    from prime_tpu.ops.attention import decode_attention

    k, v, kq, ks, vq, vs = _int4_cache()
    rng = np.random.default_rng(5)
    q = jnp.asarray(rng.normal(size=(2, 2, 1, 16)).astype(np.float32))
    lengths = jnp.asarray([64, 37], dtype=jnp.int32)
    sm_scale = 16 ** -0.5

    ref = decode_attention(
        q, kq, vq, lengths, sm_scale, impl="xla", k_scale=ks, v_scale=vs
    )
    monkeypatch.setenv("PRIME_TPU_PALLAS_INTERPRET", "1")
    out = decode_attention(
        q, kq, vq, lengths, sm_scale, impl="pallas", k_scale=ks, v_scale=vs
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=5e-6)

    # documented int4 rounding tolerance vs the fp cache (unit-normal KV:
    # ~0.09 observed; this is the quantizer's error, not the kernel's)
    fp = decode_attention(q, k, v, lengths, sm_scale, impl="xla")
    assert float(np.max(np.abs(np.asarray(out) - np.asarray(fp)))) < 0.2


def test_int4_decode_dispatch_detects_uint8():
    """decode_attention intercepts uint8 caches before the impl switch —
    auto on CPU (no interpret) must take the XLA widen path, not crash."""
    from prime_tpu.ops.attention import decode_attention

    _, _, kq, ks, vq, vs = _int4_cache()
    q = jnp.ones((2, 2, 1, 16), dtype=jnp.float32)
    lengths = jnp.asarray([64, 64], dtype=jnp.int32)
    out = decode_attention(
        q, kq, vq, lengths, 0.25, impl="auto", k_scale=ks, v_scale=vs
    )
    assert out.shape == (2, 2, 1, 16) and out.dtype == jnp.float32


# ---- flash-decode tiling -------------------------------------------------------

# name: (batch, q heads, kv heads, head_dim, capacity, block_c asked for through
# the env knob, kv heads a step the VMEM budget is cut to, cache dtype, q dtype,
# extra)
_TILING_CASES = {
    "gqa_all_heads_512": (5, 8, 2, 32, 1024, 512, 2, "float32", "float32", None),
    "gqa_half_the_heads_256": (5, 8, 4, 32, 1024, 256, 2, "float32", "float32", None),
    "gqa_bf16_to_the_mxu_as_stored_128": (5, 8, 2, 32, 1024, 128, 2, "bfloat16", "bfloat16", None),
    "gqa_f32_q_on_a_bf16_cache_512": (5, 8, 2, 32, 1024, 512, 1, "bfloat16", "float32", None),
    "mha_all_heads_256": (5, 4, 4, 32, 1024, 256, 4, "float32", "float32", None),
    "mha_one_head_512": (5, 4, 4, 32, 1024, 512, 1, "float32", "float32", None),
    "latent_one_head_of_576_512": (5, 8, 1, 576, 1024, 512, 1, "bfloat16", "bfloat16", "latent"),
    "window_sinks_256": (5, 8, 4, 32, 1024, 256, 2, "float32", "float32", "window_sinks"),
    "softcap_from_the_shapes": (5, 8, 2, 32, 1024, 0, 2, "float32", "float32", "softcap"),
    "int8_512": (5, 8, 2, 32, 1024, 512, 2, "int8", "float32", None),
    "int8_one_head_128": (5, 8, 2, 32, 1024, 128, 1, "int8", "float32", None),
    "int4_half_the_heads_256": (5, 8, 4, 32, 1024, 256, 2, "int4", "float32", None),
}


def _decode_operands(
    seed, batch, h, kh, d, c, cache, q_dtype, extra, *, heads, block_c, monkeypatch
):
    """(q, k, v, keywords) of a flash-decode call over a random cache of the
    given kind (float dtype | "int8" | "int4"; ``extra``: "latent" reads K as V,
    "window_sinks", "softcap"), with the kernel's tiling pinned to ``heads`` kv
    heads a step (the VMEM budget cut to what they need) and ``block_c`` slots
    (asked for the way an operator asks, PRIME_TPU_BLOCK_C; 0 = from the shapes)."""
    from prime_tpu.models.llama import quantize_kv
    from prime_tpu.models.quantize import quantize_kv_int4
    from prime_tpu.ops import pallas_attention as pa

    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(batch, h, 1, d)).astype(np.float32)).astype(q_dtype)
    k = jnp.asarray(rng.normal(size=(batch, kh, d, c)).astype(np.float32))
    v = k if extra == "latent" else jnp.asarray(
        rng.normal(size=(batch, kh, d, c)).astype(np.float32)
    )
    kw = {}
    if cache == "int8":
        (k, ks), (v, vs) = quantize_kv(k), quantize_kv(v)
        kw = dict(k_scale=ks, v_scale=vs)
    elif cache == "int4":
        (k, ks), (v, vs) = quantize_kv_int4(k), quantize_kv_int4(v)
        kw = dict(k_scale=ks, v_scale=vs)
    else:
        k, v = k.astype(cache), v.astype(cache)
    if extra == "window_sinks":
        kw |= dict(
            window=300, sliding=jnp.asarray(True),
            sinks=jnp.asarray(rng.normal(size=(h,)).astype(np.float32)),
        )
    elif extra == "softcap":
        kw |= dict(softcap=30.0)

    quantized = cache in ("int8", "int4")
    want_c = block_c or 512
    monkeypatch.setattr(
        pa, "DECODE_VMEM_BUDGET",
        pa.decode_step_vmem_bytes(heads, k.shape[2], want_c, k.dtype.itemsize, h // kh, d, quantized),
    )
    if block_c:
        monkeypatch.setenv("PRIME_TPU_BLOCK_C", str(block_c))
    assert pa.decode_call_tiling(h, k, quantized) == (heads, want_c)
    return q, k, v, kw


@pytest.mark.parametrize("case", sorted(_TILING_CASES))
def test_flash_decode_tiling_matches_xla(case, monkeypatch):
    """The decode kernel at every tiling its shape function can hand out
    (every kv head of a row or fewer, blocks of 128 / 256 / 512) against the
    XLA reference, over ragged lengths: 1, a block boundary, boundary + 1, a
    row inside its second block and full capacity. The block is asked for
    the way an operator asks (PRIME_TPU_BLOCK_C); the heads a step are cut by
    shrinking the VMEM budget to what that many heads need."""
    from prime_tpu.ops import pallas_attention as pa
    from prime_tpu.ops.attention import decode_attention

    batch, h, kh, d, c, block_c, heads, cache, q_dtype, extra = _TILING_CASES[case]
    q, k, v, kw = _decode_operands(
        len(case), batch, h, kh, d, c, cache, q_dtype, extra,
        heads=heads, block_c=block_c, monkeypatch=monkeypatch,
    )
    lengths = jnp.asarray([1, 512, 513, 700, c], dtype=jnp.int32)

    pa.flash_decode.clear_cache()
    try:
        out = pa.flash_decode(q, k, v, lengths, sm_scale=d**-0.5, interpret=True, **kw)
    finally:
        pa.flash_decode.clear_cache()  # this trace baked the patched budget in
    ref = decode_attention(q, k, v, lengths, d**-0.5, impl="xla", **kw)
    tol = 2e-2 if "bfloat16" in (cache, q_dtype) else 2e-3
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), rtol=tol, atol=tol
    )


# ---- rows of length 0: a slot that belongs to nobody -------------------------------

# name: (q heads, kv heads, head_dim, kv heads a step, cache dtype, extra)
_DEAD_ROW_VARIANTS = {
    "bf16": (8, 2, 32, 2, "bfloat16", None),
    "int8": (8, 2, 32, 2, "int8", None),
    "int4": (8, 4, 32, 4, "int4", None),
    "latent_576": (8, 1, 576, 1, "bfloat16", "latent"),
    "window_sinks": (8, 4, 32, 4, "float32", "window_sinks"),
    "two_head_blocks": (4, 4, 32, 2, "float32", None),  # hb > 1: the middle grid axis is 2
}
_DEAD_ROWS = {"first": [0], "last": [4], "two_in_a_row": [1, 2], "all_but_one": [0, 1, 3, 4]}


@pytest.mark.parametrize("dead", sorted(_DEAD_ROWS))
@pytest.mark.parametrize("variant", sorted(_DEAD_ROW_VARIANTS))
def test_flash_decode_dead_rows(variant, dead, monkeypatch):
    """Rows at length 0 (the engine's retired slots) beside live ones: the
    live rows equal the XLA reference and are BIT-equal to the same call with
    the dead rows left at their stale lengths (same blocks, same order, same
    float32 state: a dead row's steps are simply not in the list), and the
    dead rows come back finite and zero from the kernel and from the reference
    alike."""
    from prime_tpu.ops import pallas_attention as pa
    from prime_tpu.ops.attention import decode_attention

    h, kh, d, heads, cache, extra = _DEAD_ROW_VARIANTS[variant]
    batch, c = 5, 1024
    q, k, v, kw = _decode_operands(
        len(variant), batch, h, kh, d, c, cache,
        "bfloat16" if cache == "bfloat16" else "float32", extra,
        heads=heads, block_c=256, monkeypatch=monkeypatch,
    )

    stale = np.asarray([1, 256, 257, 700, c], dtype=np.int32)  # where the rows' requests ended
    lengths = stale.copy()
    lengths[_DEAD_ROWS[dead]] = 0
    live = lengths > 0
    pa.flash_decode.clear_cache()
    try:
        out = pa.flash_decode(q, k, v, jnp.asarray(lengths), sm_scale=d**-0.5, interpret=True, **kw)
        unmasked = pa.flash_decode(q, k, v, jnp.asarray(stale), sm_scale=d**-0.5, interpret=True, **kw)
    finally:
        pa.flash_decode.clear_cache()  # these traces baked the patched budget in
    ref = decode_attention(q, k, v, jnp.asarray(lengths), d**-0.5, impl="xla", **kw)
    out, unmasked, ref = (np.asarray(a, np.float32) for a in (out, unmasked, ref))
    tol = 2e-2 if cache == "bfloat16" else 2e-3
    np.testing.assert_allclose(out[live], ref[live], rtol=tol, atol=tol)
    np.testing.assert_array_equal(out[live], unmasked[live])
    assert np.all(out[~live] == 0) and np.all(ref[~live] == 0)


def _walk_work_list(lengths, blocks, block_c, head_blocks=1, window=0):
    """(row, kv-head block, cache block) of every grid step a flash-decode call
    makes for these lengths, in the grid's order (head block, then the work
    list), after holding the list's tables to their form: one entry more than
    the static grid has steps, the tail repeating the last pair (the pipeline
    reads the index maps a step ahead, and an equal index copies nothing)."""
    from prime_tpu.ops import pallas_attention as pa

    rows, cache_blocks, n = (
        np.asarray(a) for a in pa.decode_work_list(
            jnp.asarray(lengths, jnp.int32), block_c, blocks, window
        )
    )
    n = int(n)
    assert rows.shape == cache_blocks.shape == (len(lengths) * blocks + 1,)
    assert 1 <= n <= len(lengths) * blocks
    assert np.all(rows[n:] == rows[n - 1]) and np.all(cache_blocks[n:] == cache_blocks[n - 1])
    return np.asarray(
        [(r, hb, c) for hb in range(head_blocks) for r, c in zip(rows[:n], cache_blocks[:n])]
    )


def _live_blocks(lengths, block_c, window=0):
    """[(row, cache block)] that hold a column a row attends over, rows in
    order and a row's blocks front to back: what the work list has to be."""
    pairs = []
    for row, length in enumerate(lengths):
        first = max(length - window, 0) // block_c if window else 0
        pairs += [(row, block) for block in range(first, -(-length // block_c))]
    return pairs


@pytest.mark.parametrize(
    "lengths",
    [
        "chat_no_row_dead", "chat_six_of_eight_dead", "leading_and_trailing_dead",
        "dead_between_live", "only_the_last_live", "every_row_dead",
    ],
)
@pytest.mark.parametrize("grid", ["qwen3_4b_8x1x4", "latent_64x1x4"])
def test_the_work_list_is_the_live_rows_live_blocks(grid, lengths):
    """The steps a call makes are its work list, and the list is the live rows'
    live blocks and nothing else: every step names a block of its own row that
    holds a live column, each such block has its one step, so every step is a
    copy and the copies are the steps (one step when every row is dead, the
    warm-up's call, whose result the caller zeroes). A dead row is in no step."""
    batch = 8 if grid.startswith("qwen3") else 64
    blocks, block_c = 4, 512
    rng = np.random.default_rng(batch)
    stale = rng.integers(1, 2049, size=batch)
    stale[:4] = [1, 512, 513, 2048]  # the block boundaries
    dead = {
        "chat_no_row_dead": [],
        "chat_six_of_eight_dead": [b for b in range(batch) if b % 4 not in (1, 2)][: batch * 3 // 4],
        "leading_and_trailing_dead": [0, 1, batch - 2, batch - 1],
        "dead_between_live": [2, 3, 4],
        "only_the_last_live": list(range(batch - 1)),
        "every_row_dead": list(range(batch)),
    }[lengths]
    lens = stale.copy()
    lens[dead] = 0
    named = _walk_work_list(lens, blocks, block_c)

    live_blocks = int(np.sum(-(-lens // block_c)))
    assert len(named) == max(live_blocks, 1)
    if live_blocks:
        assert [(r, c) for r, _, c in named] == _live_blocks(lens, block_c)
        assert not set(named[:, 0]) & set(dead)
        # no two consecutive steps name one block: none of them is for nothing
        assert np.all(np.any(named[1:] != named[:-1], axis=1))
    else:
        assert lens[named[0, 0]] == 0 and named[0, 2] == 0


def test_the_work_list_with_head_blocks_and_a_window():
    """The same count where a row's kv heads take two grid steps' worth of
    blocks (MHA past the VMEM budget: the list is walked once a head block) and
    a sliding window front-skips blocks: a live row's steps are head blocks x
    (last - first + 1), and they are its blocks [first, last]."""
    from prime_tpu.ops import pallas_attention as pa

    lens = np.asarray([0, 1800, 0, 0, 300, 2048, 0], dtype=np.int32)
    blocks, block_c, head_blocks, window = 4, 512, 2, 700
    named = _walk_work_list(lens, blocks, block_c, head_blocks, window)
    first = np.maximum(lens - window, 0) // block_c
    last = -(-lens // block_c) - 1
    assert len(named) == int(np.sum(np.where(lens > 0, head_blocks * (last - first + 1), 0)))
    want = _live_blocks(lens, block_c, window)
    assert [(r, hb, c) for r, hb, c in named] == [(r, hb, c) for hb in range(head_blocks) for r, c in want]
    # a traced window (the layer scan's sliding flag) lists the same steps
    traced = jax.jit(
        lambda w: pa.decode_work_list(jnp.asarray(lens), block_c, blocks, w)
    )(jnp.asarray([window], jnp.int32))
    assert int(traced[2]) == len(want) and [
        (int(r), int(c)) for r, c in zip(traced[0][: len(want)], traced[1][: len(want)])
    ] == want


@pytest.mark.parametrize(
    "rows, blocks", [(8, 4), (64, 4), (64, 8), (64, 1)],
    ids=["qwen3_4b_8x4", "latent_64x4", "phi4_row_64x8", "phi4_ring_64x1"],
)
def test_the_work_list_of_full_rows_is_the_static_grid(rows, blocks):
    """Every row full, the list is the whole static grid, row by row and block
    by block: a call over such a batch makes the steps it made before the list
    existed, so there is nothing for a second path to be faster at."""
    named = _walk_work_list(np.full(rows, blocks * 512), blocks, 512)
    grid = [(r, 0, c) for r in range(rows) for c in range(blocks)]
    assert [tuple(step) for step in named] == grid


# the served calls: (rows, q heads, kv heads, head_dim, capacity, cache), the row
# count of phi4's 64 cut for the CPU's interpreter
_SERVED_CALLS = {
    "qwen3_4b": (8, 32, 8, 128, 2048, "bfloat16"),
    "qwen3_4b_int8": (8, 32, 8, 128, 2048, "int8"),
    "qwen3_4b_int4": (8, 32, 8, 128, 2048, "int4"),
    "latent": (64, 64, 1, 576, 2048, "bfloat16"),
    "phi4_row": (12, 40, 10, 128, 4096, "bfloat16"),
    # laguna-s-ep2's two attentions of one step: query groups of 6 on a row of
    # 8192 and of 9 on a ring of 512 (the calls above run groups of 4 and 64)
    "laguna_row_group_6": (7, 48, 8, 128, 8192, "bfloat16"),
    "laguna_ring_group_9": (10, 72, 8, 128, 512, "bfloat16"),
}


@pytest.mark.parametrize("call", sorted(_SERVED_CALLS))
def test_flash_decode_at_the_served_shapes_matches_xla(call, monkeypatch):
    """The three served calls at their own widths and tilings (and the int8 and
    int4 carriers no cell serves) over ragged lengths with dead rows among them,
    first, last and between: the work list's steps against the XLA reference,
    the dead rows zero."""
    from prime_tpu.ops import pallas_attention as pa
    from prime_tpu.ops.attention import decode_attention

    batch, h, kh, d, c, cache = _SERVED_CALLS[call]
    extra = "latent" if kh == 1 else None
    q, k, v, kw = _decode_operands(
        len(call), batch, h, kh, d, c, cache, "bfloat16" if cache == "bfloat16" else "float32",
        extra, heads=kh, block_c=0, monkeypatch=monkeypatch,
    )
    rng = np.random.default_rng(len(call))
    lengths = rng.integers(1, c + 1, size=batch)
    lengths[:6] = [0, 1, 512, min(513, c), c, 0]
    lengths[-1] = 0
    lengths[rng.permutation(np.arange(6, batch - 1))[: (batch - 7) // 3]] = 0
    lengths = jnp.asarray(lengths, jnp.int32)

    pa.flash_decode.clear_cache()
    try:
        out = pa.flash_decode(q, k, v, lengths, sm_scale=d**-0.5, interpret=True, **kw)
    finally:
        pa.flash_decode.clear_cache()  # this trace baked the patched budget in
    ref = decode_attention(q, k, v, lengths, d**-0.5, impl="xla", **kw)
    out, ref = np.asarray(out, np.float32), np.asarray(ref, np.float32)
    dead = np.asarray(lengths) == 0
    tol = 2e-2 if cache == "bfloat16" else 2e-3
    np.testing.assert_allclose(out[~dead], ref[~dead], rtol=tol, atol=tol)
    assert dead.sum() >= 3 and np.all(out[dead] == 0) and np.all(ref[dead] == 0)


_SERVED = dict(itemsize=2, capacity=2048)


@pytest.mark.parametrize(
    "name, shape, batch, tiling, steps",
    [
        # qwen3-4b: 8 rows x 8 kv heads x 128 x 2048 bf16 (1024 steps a call
        # until PR 27)
        ("qwen3_4b", dict(kv_heads=8, kv_dim=128, group=4, head_dim=128, **_SERVED), 8, (8, 512), 32),
        # LongCat-Flash's latent: 64 rows, one head of rank + rope = 576
        ("latent", dict(kv_heads=1, kv_dim=576, group=64, head_dim=576, **_SERVED), 64, (1, 512), 256),
        # a 32-head MHA cache: every head of a row at 512 slots would be 16 MiB
        # of blocks alone
        ("mha_32_heads", dict(kv_heads=32, kv_dim=128, group=1, head_dim=128, **_SERVED), 8, (16, 512), 64),
        ("int8_with_scales", dict(kv_heads=8, kv_dim=128, group=4, head_dim=128, capacity=2048, itemsize=1, quantized=True), 8, (8, 512), 32),
        ("int4_packed", dict(kv_heads=8, kv_dim=64, group=4, head_dim=128, capacity=2048, itemsize=1, quantized=True), 8, (8, 512), 32),
        # an explicit block (PRIME_TPU_BLOCK_C, a tuned artifact) is tried first
        ("prefer_128", dict(kv_heads=8, kv_dim=128, group=4, head_dim=128, prefer=128, **_SERVED), 8, (8, 128), 128),
        ("prefer_256", dict(kv_heads=8, kv_dim=128, group=4, head_dim=128, prefer=256, **_SERVED), 8, (8, 256), 64),
        # ... and is harmless where it does not divide the capacity
        ("prefer_that_does_not_fit", dict(kv_heads=8, kv_dim=128, group=4, head_dim=128, prefer=384, **_SERVED), 8, (8, 512), 32),
        ("capacity_1536", dict(kv_heads=2, kv_dim=64, group=4, head_dim=64, capacity=1536, itemsize=4), 4, (2, 512), 12),
        ("capacity_640", dict(kv_heads=2, kv_dim=64, group=4, head_dim=64, capacity=640, itemsize=2), 4, (2, 128), 20),
        ("capacity_no_width_divides", dict(kv_heads=2, kv_dim=64, group=4, head_dim=64, capacity=320, itemsize=2), 4, (2, 320), 4),
        # laguna-s-ep2: 32 rows, groups of 6 on a row of 8192 and of 9 on a ring of
        # 512, no multiple of 8 either: every K/V head of a row a step as the others
        ("laguna_row_group_6", dict(kv_heads=8, kv_dim=128, group=6, head_dim=128, capacity=8192, itemsize=2), 32, (8, 512), 512),
        ("laguna_ring_group_9", dict(kv_heads=8, kv_dim=128, group=9, head_dim=128, capacity=512, itemsize=2), 32, (8, 512), 32),
        # tp=4 hands the kernel a device's 2 local kv heads
        ("two_local_heads", dict(kv_heads=2, kv_dim=128, group=4, head_dim=128, **_SERVED), 4, (2, 512), 16),
    ],
)
def test_decode_tiling_from_the_shapes(name, shape, batch, tiling, steps):
    from prime_tpu.ops import pallas_attention as pa

    heads, block_c = pa.decode_tiling(**shape)
    assert (heads, block_c) == tiling
    assert shape["kv_heads"] % heads == 0 and shape["capacity"] % block_c == 0
    assert batch * (shape["kv_heads"] // heads) * (shape["capacity"] // block_c) == steps
    need = pa.decode_step_vmem_bytes(
        heads, shape["kv_dim"], block_c, shape["itemsize"], shape["group"],
        shape["head_dim"], shape.get("quantized", False),
    )
    # inside the stated budget, which itself leaves room under a v5e's 16 MiB
    # scoped default for q, the output, the scratch and spills
    assert need <= pa.DECODE_VMEM_BUDGET <= 12 * 1024 * 1024
    # the next wider choice (one more divisor of heads) would not have fit,
    # unless every head is already in
    if heads < shape["kv_heads"]:
        more = min(h for h in range(heads + 1, shape["kv_heads"] + 1) if shape["kv_heads"] % h == 0)
        assert pa.decode_step_vmem_bytes(
            more, shape["kv_dim"], block_c, shape["itemsize"], shape["group"],
            shape["head_dim"], shape.get("quantized", False),
        ) > pa.DECODE_VMEM_BUDGET


def test_decode_kernel_tiling_follows_the_dispatch(monkeypatch):
    """What the engine publishes as serve_decode_attn_*: the tiling of the
    call decode_attention makes, zeros where it takes an XLA path."""
    from prime_tpu.ops.attention import decode_kernel_tiling

    cache = jax.ShapeDtypeStruct((36, 8, 8, 128, 2048), jnp.bfloat16)
    assert decode_kernel_tiling("xla", 32, cache, False) == (0, 0, 0)
    assert decode_kernel_tiling("auto", 32, cache, False) == (0, 0, 0)  # CPU backend
    assert decode_kernel_tiling("pallas", 32, cache, False) == (32, 512, 8)
    monkeypatch.setenv("PRIME_TPU_BLOCK_C", "128")
    assert decode_kernel_tiling("pallas", 32, cache, False) == (128, 128, 8)
    monkeypatch.delenv("PRIME_TPU_BLOCK_C")
    latent = jax.ShapeDtypeStruct((8, 64, 1, 576, 2048), jnp.bfloat16)
    assert decode_kernel_tiling("pallas", 64, latent, False) == (256, 512, 1)
    int4 = jax.ShapeDtypeStruct((36, 8, 8, 64, 2048), jnp.uint8)
    assert decode_kernel_tiling("pallas", 32, int4, True) == (32, 512, 8)
    assert decode_kernel_tiling("sharded", 32, cache, False, mesh=None) == (0, 0, 0)


@pytest.mark.parametrize(
    "attn_impl, capacity, want", [("xla", 256, (0, 0, 0)), ("pallas", 256, None), ("pallas", 1024, None)]
)
def test_engine_publishes_the_decode_kernels_tiling(attn_impl, capacity, want):
    """serve_decode_attn_{grid_steps,block_c,heads_per_step}: set once at
    build from the served cache, 0 when decode takes the XLA path."""
    from prime_tpu.models import get_config
    from prime_tpu.models.llama import init_params
    from prime_tpu.ops import pallas_attention as pa
    from prime_tpu.serve.engine import ContinuousBatchingEngine

    config = get_config("tiny-test")
    params = init_params(jax.random.PRNGKey(0), config, dtype=jnp.float32)
    engine = ContinuousBatchingEngine(
        params, config, max_slots=2, capacity=capacity, chunk=4, prefix_cache_mb=0,
        attn_impl=attn_impl,
    )
    try:
        got = tuple(
            int(engine.registry.get(f"serve_decode_attn_{name}").value())
            for name in ("grid_steps", "block_c", "heads_per_step")
        )
    finally:
        engine.shutdown()
    if want is None:
        cache = engine._cache.k
        heads, block_c = pa.decode_call_tiling(config.n_heads, cache, False)
        kv_heads = cache.shape[2]
        assert (heads, block_c) == (kv_heads, min(capacity, 512))
        want = (2 * (kv_heads // heads) * (capacity // block_c), block_c, heads)
    assert got == want


# ---- autotune ----------------------------------------------------------------


def test_autotune_dry_run_round_trips_artifact(tmp_path):
    from prime_tpu.ops.autotune import run_autotune

    winners = run_autotune(
        kernels=["flash_decode", "lora_mm"], dry_run=True
    )
    assert set(winners) == {"flash_decode", "lora_mm"}
    assert winners["flash_decode"]["block_c"] > 0
    assert "us" in winners["lora_mm"]

    out = tmp_path / "tuned"
    kernel_configs.save_artifact(winners, directory=str(out))
    # resolution must read the persisted winners (us key ignored)
    import os

    os.environ["PRIME_TPU_KERNEL_CONFIG_DIR"] = str(out)
    kernel_configs.invalidate_cache()
    try:
        assert kernel_configs.source() == "tuned"
        assert (
            kernel_configs.resolve("flash_decode", "block_c")
            == winners["flash_decode"]["block_c"]
        )
        assert (
            kernel_configs.resolve("lora_mm", "block_out")
            == winners["lora_mm"]["block_out"]
        )
        # kernels not in the artifact keep defaults
        assert kernel_configs.resolve("flash_prefill", "block_q") == 128
    finally:
        kernel_configs.invalidate_cache()


def test_autotune_unknown_kernel_raises():
    from prime_tpu.ops.autotune import run_autotune

    with pytest.raises(ValueError, match="unknown kernel"):
        run_autotune(kernels=["nope"], dry_run=True)
