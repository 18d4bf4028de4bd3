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
    assert kernel_configs.resolve("flash_decode", "block_c") == 128
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
    assert kernel_configs.resolve("flash_decode", "block_c") == 128


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
