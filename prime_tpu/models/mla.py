"""Multi-head latent attention (DeepSeek-V2/V3-style), TPU-first.

MLA stores ONE shared latent per token instead of per-head K/V: the cache
column is ``[c_kv (kv_lora_rank); roped k_pe (qk_rope_head_dim)]`` — for
DeepSeek-V2 dims that is 512+64 floats vs 2*H*hd (e.g. 2*32*128 = 8192), a
~14x smaller decode cache, which on TPU means ~14x less KV HBM traffic per
step and 14x longer context per chip.

This implementation uses the ABSORBED formulation everywhere (prefill and
decode): the per-head no-position query is projected into latent space
through W_kv_b's key half, so attention itself is plain GQA with ONE kv
head of width rank+rope —

    q_joint = [q_nope @ W_kc ; rope(q_pe)]          (B, H, S, rank+rope)
    k_joint = [rmsnorm(c_kv) ; rope(k_pe)]          (B, 1, S, rank+rope)
    scores  = q_joint . k_joint                      (== DeepSeek's two-part dot)
    ctx     = probs @ k_joint, keep first `rank`     (== probs @ c_kv exactly)
    out     = (ctx @ W_vc per head) @ wo

so every existing attention path (XLA grouped einsum, flash-decode pallas
kernel, chunked prefill, the continuous engine's slot cache) serves MLA
unchanged — the value tensor IS the key tensor and the rope tail is simply
dropped after the weighted sum. The softmax scale is (nope+rope)^-0.5, the
full query head width, matching DeepSeek.

Weights per layer (dense query unless ``q_lora_rank``):
    wq                 (d, H*(nope+rope))        [or wq_a/q_a_norm/wq_b]
    wkv_a              (d, rank+rope)
    kv_a_norm          (rank,)
    wkv_b              (rank, H*(nope+v))
    wo                 (H*v, d)

LongCat-Flash scales the two low-rank norms' outputs by sqrt(d / rank)
(``mla_scale_q_lora`` / ``mla_scale_kv_lora``); the scaled c_kv is what the
cache stores. ``init_mla_attn_params(lead=(L, 2))`` stacks the weights for
the shortcut block's two attentions a layer.

`naive_mla_attention` recomputes full per-head K/V from the latent (the
paper's textbook form) and exists as the parity oracle for tests.
"""

from __future__ import annotations

import jax.numpy as jnp

from prime_tpu.models.config import ModelConfig
from prime_tpu.ops.attention import (
    cache_prefill_attention,
    decode_attention,
    multi_head_attention,
    write_cache,
)
from prime_tpu.ops.rope import apply_rope_rows


def init_mla_attn_params(keys, config: ModelConfig, dtype, dense, lead=None) -> dict:
    """The MLA attention weight dict for init_params, stacked over ``lead``:
    (n_layers,), or (n_layers, 2) for the shortcut block's two attentions."""
    d = config.d_model
    lead = (config.n_layers,) if lead is None else tuple(lead)
    h = config.n_heads
    rank, rope = config.kv_lora_rank, config.qk_rope_head_dim
    nope, v = config.qk_nope_head_dim, config.v_head_dim
    weights = {
        "wkv_a": dense(keys[2], (*lead, d, rank + rope), d),
        "kv_a_norm": jnp.ones((*lead, rank), dtype=dtype),
        "wkv_b": dense(keys[3], (*lead, rank, h * (nope + v)), rank),
        "wo": dense(keys[4], (*lead, h * v, d), h * v),
    }
    if config.q_lora_rank is not None:
        qr = config.q_lora_rank
        weights |= {
            "wq_a": dense(keys[1], (*lead, d, qr), d),
            "q_a_norm": jnp.ones((*lead, qr), dtype=dtype),
            # keys[13]: every lower index belongs to a llama.init_params
            # weight (5/6/7 are the MLP stack) — sharing one would correlate
            # the two matrices at from-scratch init
            "wq_b": dense(keys[13], (*lead, qr, h * (nope + rope)), qr),
        }
    else:
        weights["wq"] = dense(keys[1], (*lead, d, h * (nope + rope)), d)
    return weights


def _rms(x: jnp.ndarray, weight: jnp.ndarray, config: ModelConfig) -> jnp.ndarray:
    # the shared rms_norm honors norm_plus_one; the latent/query low-rank
    # norms (kv_a_norm/q_a_norm) are init'd to ones, so plain scaling there
    # is the DeepSeek convention either way
    from prime_tpu.ops.norms import rms_norm

    return rms_norm(x, weight, config.rms_eps, plus_one=config.norm_plus_one)


# MLA reuses the shared attention ops through the absorbed joint-latent
# form, which cannot express these per-head attention features — reject
# them loudly instead of silently running different numerics
_UNSUPPORTED_WITH_MLA = (
    ("sliding_window", 0),
    ("attn_softcap", 0.0),
    ("attn_sinks", False),
    ("qk_norm", False),
    ("qk_norm_full", False),
    ("attn_bias", False),
    ("query_scale", None),
    ("partial_rotary", 1.0),
)


def _q_latent(x, lp, config: ModelConfig):
    """The low-rank query latent, with LongCat's sqrt(d_model / rank) scale."""
    from prime_tpu.models.quantize import matmul as _mm

    c_q = _rms(_mm(x, lp["wq_a"]), lp["q_a_norm"], config)
    if config.mla_scale_q_lora:
        c_q = c_q * jnp.asarray((config.d_model / config.q_lora_rank) ** 0.5, c_q.dtype)
    return c_q


def _kv_latent(kv, lp, config: ModelConfig):
    """The normed KV latent c_kv out of wkv_a's output, with LongCat's
    sqrt(d_model / rank) scale."""
    c_kv = _rms(kv[..., : config.kv_lora_rank], lp["kv_a_norm"], config)
    if config.mla_scale_kv_lora:
        c_kv = c_kv * jnp.asarray((config.d_model / config.kv_lora_rank) ** 0.5, c_kv.dtype)
    return c_kv


def validate_mla_config(config: ModelConfig) -> None:
    bad = [
        name for name, default in _UNSUPPORTED_WITH_MLA
        if getattr(config, name) != default
    ]
    if bad:
        raise ValueError(
            f"MLA (kv_lora_rank set) does not support {', '.join(bad)}: the "
            "absorbed latent attention has no per-head K to apply them to"
        )


def _split_wkv_b(lp, config: ModelConfig):
    """(w_kc, s_kc, w_vc, s_vc): the absorb/value halves of wkv_b with their
    int8 per-output-channel scales split alongside (None scales when fp).
    The scales fold exactly: the absorb einsum contracts the nope axis, so
    s_kc multiplies q_nope (the other contracted operand); the value einsum
    emits the v axis, so s_vc scales the output."""
    rank = config.kv_lora_rank
    h, nope, v = config.n_heads, config.qk_nope_head_dim, config.v_head_dim
    w = lp["wkv_b"]
    if isinstance(w, tuple):
        q8, s8 = w  # (rank, h*(nope+v)) int8, (1, h*(nope+v)) fp32
        wr = q8.reshape(rank, h, nope + v)
        sr = s8.reshape(h, nope + v)
        return wr[..., :nope], sr[..., :nope], wr[..., nope:], sr[..., nope:]
    wr = w.reshape(rank, h, nope + v)
    return wr[..., :nope], None, wr[..., nope:], None


def _queries_and_latent(x, lp, config: ModelConfig, cos_rows, sin_rows):
    """Shared front half: joint queries (B,H,S,rank+rope) and the per-token
    joint latent column (B,S,rank+rope) ready for the cache."""
    from prime_tpu.models.quantize import matmul as _mm

    batch, seq, _ = x.shape
    h = config.n_heads
    rank, rope = config.kv_lora_rank, config.qk_rope_head_dim
    nope = config.qk_nope_head_dim

    if "wq_a" in lp:
        q = _mm(_q_latent(x, lp, config), lp["wq_b"])
    else:
        q = _mm(x, lp["wq"])
    q = q.reshape(batch, seq, h, nope + rope)
    q_nope, q_pe = q[..., :nope], q[..., nope:]
    q_pe = apply_rope_rows(q_pe, cos_rows, sin_rows)

    kv = _mm(x, lp["wkv_a"])  # (B, S, rank+rope)
    c_kv = _kv_latent(kv, lp, config)
    k_pe = apply_rope_rows(kv[..., None, rank:], cos_rows, sin_rows)[:, :, 0, :]

    # absorb W_kv_b's key half into the query: q_nope -> latent space
    w_kc, s_kc, _, _ = _split_wkv_b(lp, config)
    if s_kc is not None:  # int8: fold the scales into the contracted operand
        q_nope = q_nope * s_kc[None, None].astype(q_nope.dtype)
    q_lat = jnp.einsum("bshn,rhn->bshr", q_nope, w_kc.astype(q_nope.dtype))
    q_joint = jnp.concatenate([q_lat, q_pe], axis=-1)  # (B, S, H, rank+rope)
    latent = jnp.concatenate([c_kv, k_pe], axis=-1)    # (B, S, rank+rope)
    return q_joint.transpose(0, 2, 1, 3), latent


def _project_out(ctx_latent, lp, config: ModelConfig):
    """(B, H, S, rank) latent context -> per-head values -> d_model."""
    from prime_tpu.models.quantize import matmul as _mm

    batch, h, seq, rank = ctx_latent.shape
    v = config.v_head_dim
    _, _, w_vc, s_vc = _split_wkv_b(lp, config)
    out = jnp.einsum("bhsr,rhv->bshv", ctx_latent, w_vc.astype(ctx_latent.dtype))
    if s_vc is not None:  # int8: v is the output axis, scales fold there
        out = out * s_vc[None, None].astype(out.dtype)
    return _mm(out.reshape(batch, seq, h * v), lp["wo"])


def mla_attention_block(
    x, lp, positions, rope_tables, config: ModelConfig,
    kv, layer, cache_lengths, decode: bool, attn_impl: str,
    attend_lengths=None, work=None, column_work=None, prefill_offset=None,
):
    """Drop-in replacement for llama._attention_block on MLA configs.

    Cache contract: the joint latent rides the standard KVCache ``k`` array
    with KH=1 and head width rank+rope; ``v`` is a 1-wide dummy that passes
    through untouched (llama.init_cache allocates it). ``kv`` is the stacked
    (k, v) pair the layer scan carries (() without a cache) and ``layer``
    this block's layer in it; the latent is written in place
    (ops.attention.write_cache). The attention ops receive the SAME latent
    array as both K and V and the rope tail of the weighted sum is discarded
    — probs @ [c_kv;k_pe] restricted to the first `rank` columns equals
    probs @ c_kv exactly.
    """
    batch, seq, _ = x.shape
    rank = config.kv_lora_rank
    # attn_scale_mult: DeepSeek-yarn mscale^2 rides the softmax scale
    sm_scale = (
        (config.qk_nope_head_dim + config.qk_rope_head_dim) ** -0.5
        * config.attn_scale_mult
    )
    cos, sin = rope_tables
    cos_rows, sin_rows = cos[positions], sin[positions]

    normed = _rms(x, lp["attn_norm"], config) if "attn_norm" in lp else x
    q_joint, latent = _queries_and_latent(normed, lp, config, cos_rows, sin_rows)

    def write(start, impl="xla", work=None):  # the latent block (B, 1, rank+rope, S) at slots [start, start+S)
        (k_cache,) = write_cache(
            kv[:1], (latent.transpose(0, 2, 1)[:, None],), layer, start, impl=impl, work=work
        )
        return k_cache, kv[1]

    if decode:
        assert kv and cache_lengths is not None
        kv = write(cache_lengths, attn_impl, column_work)
        ctx = decode_attention(
            q_joint, kv[0], kv[0], cache_lengths + 1 if attend_lengths is None else attend_lengths,
            sm_scale, impl=attn_impl, layer=layer, work=work,
        )
    elif prefill_offset is not None:
        kv = write(prefill_offset)
        ctx = cache_prefill_attention(
            q_joint, kv[0], kv[0], prefill_offset.astype(jnp.int32), sm_scale,
            layer=layer,
        )
    else:
        kj = latent[:, None]  # (B, 1, S, rank+rope): one shared kv head
        ctx = multi_head_attention(q_joint, kj, kj, sm_scale, impl=attn_impl)
        if kv:
            kv = write(jnp.zeros((), jnp.int32))

    out = _project_out(ctx[..., :rank], lp, config)
    if "attn_post_norm" in lp:
        out = _rms(out, lp["attn_post_norm"], config)
    return x + out, kv


def naive_mla_attention(x, lp, positions, rope_tables, config: ModelConfig):
    """Textbook (non-absorbed) MLA for one no-cache block: full per-head K/V
    recomputed from the latent, standard attention. Parity oracle only."""
    batch, seq, _ = x.shape
    h = config.n_heads
    rank, rope = config.kv_lora_rank, config.qk_rope_head_dim
    nope, vd = config.qk_nope_head_dim, config.v_head_dim
    from prime_tpu.models.quantize import matmul as _mm

    cos, sin = rope_tables
    cos_rows, sin_rows = cos[positions], sin[positions]
    normed = _rms(x, lp["attn_norm"], config) if "attn_norm" in lp else x

    if "wq_a" in lp:
        q = _mm(_q_latent(normed, lp, config), lp["wq_b"])
    else:
        q = _mm(normed, lp["wq"])
    q = q.reshape(batch, seq, h, nope + rope)
    q_nope, q_pe = q[..., :nope], apply_rope_rows(q[..., nope:], cos_rows, sin_rows)

    kv = _mm(normed, lp["wkv_a"])
    c_kv = _kv_latent(kv, lp, config)
    k_pe = apply_rope_rows(kv[..., None, rank:], cos_rows, sin_rows)  # (B,S,1,rope)

    w_kc, s_kc, w_vc, s_vc = _split_wkv_b(lp, config)
    k_nope = jnp.einsum("bsr,rhn->bshn", c_kv, w_kc.astype(c_kv.dtype))
    if s_kc is not None:
        k_nope = k_nope * s_kc[None, None].astype(k_nope.dtype)
    v = jnp.einsum("bsr,rhv->bshv", c_kv, w_vc.astype(c_kv.dtype))
    if s_vc is not None:
        v = v * s_vc[None, None].astype(v.dtype)

    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_pe, (batch, seq, h, rope))], -1)
    qf = jnp.concatenate([q_nope, q_pe], -1)
    sm_scale = (nope + rope) ** -0.5 * config.attn_scale_mult
    ctx = multi_head_attention(
        qf.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3),
        sm_scale, impl="xla",
    )
    out = _mm(ctx.transpose(0, 2, 1, 3).reshape(batch, seq, h * vd), lp["wo"])
    if "attn_post_norm" in lp:
        out = _rms(out, lp["attn_post_norm"], config)
    return x + out
