"""The "sambay" block kind (Phi-4-mini-flash): state-space layers, window
attention over a ring, ONE full-attention layer whose K/V seven cross layers
share, gated memory units, differential attention, no positional encoding.

Layer ``l`` of ``n`` (``config.window_pairs`` = n/4, ``config.cross_pairs``
= n/4 - 1), every layer ``x += mixer(LN1(x)); x += FFN(LN2(x))``:

    0, 2, .., n/2-2   state-space                        params["layers"]["ssm"]
    1, 3, .., n/2-1   differential attention, window     params["layers"]["attn"]
    n/2               state-space, hands on its scan     params["mid_layers"]["ssm"]
                      output M (before the gate)
    n/2+1             differential attention, full: the  params["mid_layers"]["attn"]
                      only full-length K/V there is
    n/2+2, ..         gated memory unit over M           params["cross_layers"]["gmu"]
    n/2+3, ..         differential CROSS attention: own  params["cross_layers"]["attn"]
                      queries, layer n/2+1's K/V

Two scans and an unrolled pair; M and the full layer's K/V go from the middle
to the last scan.

**Differential attention through the ordinary attention ops.** Query pair
``j`` is heads ``2j, 2j+1``, K/V pair ``g = j // 2`` heads ``2g, 2g+1``, and
``O_t = softmax(q_{2j+t} . k_{2g+t} / sqrt(hd)) [v_2g; v_2g+1]``. Store a K/V
pair as ONE head of width 2 hd, ``[k_2g; k_2g+1]``, and pad query ``2j+t`` with
zeros in the other half: the dot product is ``q . k_{2g+t}`` exactly, the value
is the pair, and what is left is grouped-query attention with n_kv_heads/2
heads of width 2 hd and four queries a head. Every attention op and kernel the
other blocks use (``decode_attention`` / ``flash_decode``, ``write_cache`` /
``write_columns``, ``cache_prefill_attention``) serves it unchanged; the zero
half doubles the QK^T operations, which decode (bound by bytes) does not see.

**The ring.** A window layer keeps ``sliding_window`` columns a slot, written
at ``position mod window``. With no positional encoding a softmax does not
care which column a key sits in, so at decode the ring is a plain cache of
length ``min(length + 1, window)``; a prompt's chunk attends the ring as it
stood beside its own columns (``ops.attention.attention_at_positions``) and
then writes its newest columns over it (``write_ring``).

**The cross half at one position.** The cross layers keep no state and only
the head reads their output, so with ``last_positions`` they run at that
position alone: a prompt's other tokens cost the first n/2 + 2 layers only.
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp

from prime_tpu.models.config import ModelConfig
from prime_tpu.models.quantize import matmul as _mm
from prime_tpu.models.ssm import gated_memory_unit, ssm_mixer
from prime_tpu.ops.attention import (
    _decode_uses_kernel,
    attention_at_positions,
    cache_prefill_attention,
    column_write_work,
    decode_attention,
    decode_work,
    ring_positions,
    write_cache,
    write_ring,
)
from prime_tpu.ops.norms import layer_norm, rms_norm

Params = dict[str, Any]

# the random recipe's keys (split 32 ways from the seed's key); the reference
# (benchmark/reference/phi4_flash.py) restates the table
KEY_OF = {
    "embed": 0,
    "ssm.w_in": 1, "ssm.conv_w": 2, "ssm.conv_b": 3, "ssm.w_x": 4, "ssm.w_dt": 5,
    "ssm.b_dt": 6, "ssm.w_out": 7,
    "attn.wqkv": 8, "attn.bqkv": 9, "attn.wo": 10, "attn.bo": 11, "attn.lambdas": 12,
    "gmu.w_in": 13, "gmu.w_out": 14,
    "w_gate": 15, "w_up": 16, "w_down": 17,
}
# each group of pairs folds its own number into a key, so that no two groups
# draw the same matrix
GROUPS = {"layers": 0, "mid_layers": 1, "cross_layers": 2}


def lambda_init(layer: jnp.ndarray) -> jnp.ndarray:
    """``0.8 - 0.6 exp(-0.3 l)`` for the layer's index in the whole model."""
    return 0.8 - 0.6 * jnp.exp(-0.3 * layer.astype(jnp.float32))


def init_params(rng: jax.Array, config: ModelConfig, dtype=jnp.bfloat16) -> Params:
    """Random init of the sambay block. A matrix is ``normal * fan_in**-0.5``
    rounded to ``dtype``, a linear bias ``normal * 0.02``, the four lambda
    vectors ``normal * 0.1``, norm weights one and biases zero,
    ``A_log[n, c] = log(n + 1)``, ``D`` one, and ``b_dt`` such that
    ``softplus(b_dt)`` is log-uniform in [1e-3, 1e-1] (``A_log``, ``D``,
    ``b_dt`` float32). Each array is one jitted draw."""
    keys = jax.random.split(rng, 32)
    d, ff, di = config.d_model, config.d_ff, config.d_inner
    n, k, r = config.ssm_d_state, config.ssm_d_conv, config.dt_rank
    hd = config.head_dim
    qw, kvw = config.n_heads * hd, config.n_kv_heads * hd

    def draw(key, shape, scale, out=dtype):
        make = jax.jit(
            lambda key: (jax.random.normal(key, shape, dtype=jnp.float32) * scale).astype(out)
        )
        return make(key)

    def ffn(key_of, lead):
        return {
            "norm1": jnp.ones((*lead, d), dtype), "norm1_b": jnp.zeros((*lead, d), dtype),
            "norm2": jnp.ones((*lead, d), dtype), "norm2_b": jnp.zeros((*lead, d), dtype),
            "w_gate": draw(key_of("w_gate"), (*lead, d, ff), d**-0.5),
            "w_up": draw(key_of("w_up"), (*lead, d, ff), d**-0.5),
            "w_down": draw(key_of("w_down"), (*lead, ff, d), ff**-0.5),
        }

    def group(name, pairs, first, second):
        gid = GROUPS[name]

        def sub(half):
            def key_of(leaf):
                return jax.random.fold_in(keys[KEY_OF[leaf]], 2 * gid + half)
            return key_of

        lead = (pairs,)
        return {first: _MIXERS[first](sub(0), lead), second: _MIXERS[second](sub(1), lead)}

    def ssm(key_of, lead):
        uniform = jax.jit(lambda key: jax.random.uniform(key, (*lead, di), dtype=jnp.float32))
        dt = jnp.exp(uniform(key_of("ssm.b_dt")) * (math.log(1e-1) - math.log(1e-3)) + math.log(1e-3))
        return {
            **ffn(key_of, lead),
            "w_in": draw(key_of("ssm.w_in"), (*lead, d, 2 * di), d**-0.5),
            "conv_w": draw(key_of("ssm.conv_w"), (*lead, k, di), k**-0.5),
            "conv_b": draw(key_of("ssm.conv_b"), (*lead, di), 0.02),
            "w_x": draw(key_of("ssm.w_x"), (*lead, di, r + 2 * n), di**-0.5),
            "w_dt": draw(key_of("ssm.w_dt"), (*lead, r, di), r**-0.5),
            # the inverse of softplus at dt
            "b_dt": dt + jnp.log(-jnp.expm1(-dt)),
            "A_log": jnp.broadcast_to(
                jnp.log(jnp.arange(1, n + 1, dtype=jnp.float32))[:, None], (*lead, n, di)
            ),
            "D": jnp.ones((*lead, di), jnp.float32),
            "w_out": draw(key_of("ssm.w_out"), (*lead, di, d), di**-0.5),
        }

    def attention(cross):
        def make(key_of, lead):
            width = qw if cross else qw + 2 * kvw
            return {
                **ffn(key_of, lead),
                "wqkv": draw(key_of("attn.wqkv"), (*lead, d, width), d**-0.5),
                "bqkv": draw(key_of("attn.bqkv"), (*lead, width), 0.02),
                "wo": draw(key_of("attn.wo"), (*lead, qw, d), qw**-0.5),
                "bo": draw(key_of("attn.bo"), (*lead, d), 0.02),
                # lambda_q1, lambda_k1, lambda_q2, lambda_k2
                "lambdas": draw(key_of("attn.lambdas"), (*lead, 4, hd), 0.1, out=jnp.float32),
                "subln": jnp.ones((*lead, 2 * hd), dtype),
            }
        return make

    def gmu(key_of, lead):
        return {
            **ffn(key_of, lead),
            "w_in": draw(key_of("gmu.w_in"), (*lead, d, di), d**-0.5),
            "w_out": draw(key_of("gmu.w_out"), (*lead, di, d), di**-0.5),
        }

    _MIXERS = {"ssm": ssm, "attn": attention(False), "gmu": gmu, "cross": attention(True)}
    cross = group("cross_layers", config.cross_pairs, "gmu", "cross")
    return {
        "embed": draw(keys[KEY_OF["embed"]], (config.vocab_size, d), d**-0.5),
        "layers": group("layers", config.window_pairs, "ssm", "attn"),
        "mid_layers": group("mid_layers", 1, "ssm", "attn"),
        "cross_layers": {"gmu": cross["gmu"], "attn": cross["cross"]},
        "final_norm": jnp.ones((d,), dtype),
        "final_norm_bias": jnp.zeros((d,), dtype),
    }


def _ffn(x: jnp.ndarray, lp: Params, config: ModelConfig) -> jnp.ndarray:
    h = layer_norm(x, lp["norm2"], lp["norm2_b"], config.rms_eps)
    return x + _mm(jax.nn.silu(_mm(h, lp["w_gate"])) * _mm(h, lp["w_up"]), lp["w_down"])


def _pad_queries(q: jnp.ndarray, config: ModelConfig) -> jnp.ndarray:
    """(B, S, H * hd) -> (B, H, S, 2 hd): head ``2j + t`` in half ``t`` of its
    width, zeros in the other (module docstring)."""
    batch, seq, _ = q.shape
    h, hd = config.n_heads, config.head_dim
    q = q.reshape(batch, seq, h // 2, 2, 1, hd)
    halves = jnp.eye(2, dtype=q.dtype)[:, :, None]  # (t, half, 1)
    return (q * halves).reshape(batch, seq, h, 2 * hd).transpose(0, 2, 1, 3)


def _staged(x: jnp.ndarray, config: ModelConfig) -> jnp.ndarray:
    """(B, S, KH * hd) keys or values -> (B, KH / 2, 2 hd, S): pairs of heads
    as one head, feature-major as the cache stores them."""
    batch, seq, _ = x.shape
    return x.reshape(batch, seq, config.n_kv_heads // 2, 2 * config.head_dim).transpose(0, 2, 3, 1)


def _project_qkv(h, lp, config):
    """A self-attention layer's padded queries and its staged keys and values."""
    qw, kvw = config.n_heads * config.head_dim, config.n_kv_heads * config.head_dim
    qkv = _mm(h, lp["wqkv"]) + lp["bqkv"]
    return (
        _pad_queries(qkv[..., :qw], config),
        _staged(qkv[..., qw : qw + kvw], config),
        _staged(qkv[..., qw + kvw :], config),
    )


def _differential_out(attn, lp, layer, config):
    """(B, H, S, 2 hd) the padded heads' attention -> the layer's output:
    ``RMSNorm(O_0 - lambda O_1) (1 - lambda_init)`` a query pair, then W_o."""
    batch, h, seq, width = attn.shape
    lam = lp["lambdas"].astype(jnp.float32)
    init = lambda_init(layer)
    lam_full = jnp.exp(jnp.sum(lam[0] * lam[1])) - jnp.exp(jnp.sum(lam[2] * lam[3])) + init
    pairs = attn.transpose(0, 2, 1, 3).reshape(batch, seq, h // 2, 2, width).astype(jnp.float32)
    diff = pairs[:, :, :, 0] - lam_full * pairs[:, :, :, 1]
    out = rms_norm(diff, lp["subln"], config.rms_eps) * (1.0 - init)
    out = out.astype(attn.dtype).reshape(batch, seq, h // 2 * width)
    return _mm(out, lp["wo"]) + lp["bo"]


def sambay_layers(
    params: Params,
    x: jnp.ndarray,                   # (B, S, d) the embedded tokens
    config: ModelConfig,
    cache,                            # models.llama.KVCache or None
    decode: bool,
    attn_impl: str,
    prefill_offset: jnp.ndarray | None,
    token_mask: jnp.ndarray | None,   # (B, S): decode, rows that belong to nobody
    #                                   are False; a prompt's chunk, the padding
    #                                   past a row's last token is
    last_positions: jnp.ndarray | None,
):
    """Returns (x, cache leaves, attention steps, column writes). ``x`` is
    (B, S, d), or (B, 1, d) at ``last_positions`` where they were given. The
    leaves are ``(k, v, ring_k, ring_v, ssm_state, conv_tail)`` updated, or ()
    without a cache. The steps are ``forward``'s ``return_attn_steps`` vector: a
    decode step's flash-decode grid steps, made and at most, rings and row
    summed; the writes its ``return_column_writes`` vector, the rows the step's
    column-write calls wrote and were offered, the rings' and the row's."""
    batch, seq, _ = x.shape
    hd = config.head_dim
    sm_scale = hd**-0.5
    window = config.sliding_window
    n_window, n_self = config.window_pairs, 2 * config.window_pairs
    cached = cache is not None
    if decode and (not cached or seq != 1):
        raise ValueError("a decode step takes a cache and one token a row")

    live = None  # a chunk's real tokens (padding leaves state and ring alone)
    offset = jnp.zeros((), jnp.int32)
    if cached and not decode:
        if prefill_offset is not None:
            offset = prefill_offset.astype(jnp.int32)
            if offset.ndim:
                raise ValueError(
                    "the sambay block takes one chunk offset for all rows: a per-row "
                    "window (speculative verify) would have to rewind recurrent state"
                )
        live = token_mask
    counts = (
        jnp.sum(live.astype(jnp.int32), axis=1) if live is not None
        else jnp.full((batch,), seq, jnp.int32)
    )

    lengths = attend = ring_attend = work = ring_work = ring_impl = column_work = None
    attn_steps = column_writes = jnp.zeros((2,), jnp.int32)
    if decode:
        lengths = cache.lengths
        attend = lengths + 1
        rows_live = None if token_mask is None else token_mask[:, 0]
        if rows_live is not None:
            attend = jnp.where(rows_live, attend, 0)
        ring_attend = jnp.minimum(attend, window)
        # the ring takes the path the slot's full row takes: beside the
        # flash-decode kernel, per-row dynamic_update_slice writes of a wide
        # batch would be a launch a row a layer
        ring_impl = "pallas" if _decode_uses_kernel(attn_impl, cache.k) else "xla"
        # the kernel's grid steps for this step's lengths, once for the eight
        # ring calls (a block a live row) and once for the row's eight
        ring_work, ring_steps = decode_work(
            ring_impl, config.n_heads, cache.ring_k, False, ring_attend
        )
        work, steps = decode_work(attn_impl, config.n_heads, cache.k, False, attend)
        attn_steps = ring_steps * n_window + steps * (1 + config.cross_pairs)
        # the live rows, listed once for the rings' column writes and the row's
        # (one gate: ``ring_impl`` follows the row's)
        column_work, column_writes = column_write_work(attn_impl, cache.k, rows_live)
        column_writes = column_writes * (n_window + 1)

    q_pos = offset + jnp.arange(seq, dtype=jnp.int32)[None, :]  # (1, S)

    def window_attention(h, lp, row, ring):
        q, k_t, v_t = _project_qkv(h, lp, config)
        if decode:
            ring = write_cache(
                ring, (k_t, v_t), row, lengths % window, impl=ring_impl, work=column_work
            )
            attn = decode_attention(
                q, ring[0], ring[1], ring_attend, sm_scale, impl=ring_impl, layer=row, work=ring_work,
            )
        elif cached:
            # the ring as it stood, then the chunk's own columns
            old = tuple(
                jax.lax.dynamic_index_in_dim(leaf, row, 0, keepdims=False) for leaf in ring
            )
            k_pos = jnp.concatenate(
                [ring_positions(offset, window), offset + jnp.arange(seq, dtype=jnp.int32)]
            )[None, :]
            attn = attention_at_positions(
                q, jnp.concatenate([old[0], k_t.astype(old[0].dtype)], axis=-1),
                jnp.concatenate([old[1], v_t.astype(old[1].dtype)], axis=-1),
                q_pos, k_pos, sm_scale, window=window,
            )
            ring = write_ring(ring, (k_t, v_t), row, offset, counts)
        else:
            attn = attention_at_positions(q, k_t, v_t, q_pos, q_pos, sm_scale, window=window)
        return attn, ring

    def window_pair(carry, scanned):
        x, ring, state, tail = carry
        row, lp = scanned
        ssm, attn = lp["ssm"], lp["attn"]
        h = layer_norm(x, ssm["norm1"], ssm["norm1_b"], config.rms_eps)
        out, _, new_state, new_tail = ssm_mixer(
            h, ssm, state[row] if cached else None, tail[row] if cached else None, live
        )
        if cached:
            state = jax.lax.dynamic_update_index_in_dim(state, new_state, row, 0)
            tail = jax.lax.dynamic_update_index_in_dim(tail, new_tail, row, 0)
        x = _ffn(x + out, ssm, config)
        h = layer_norm(x, attn["norm1"], attn["norm1_b"], config.rms_eps)
        a, ring = window_attention(h, attn, row, ring)
        x = _ffn(x + _differential_out(a, attn, 2 * row + 1, config), attn, config)
        return (x, ring, state, tail), None

    ring = (cache.ring_k, cache.ring_v) if cached else ()
    state, tail = (cache.ssm_state, cache.conv_tail) if cached else (None, None)
    rows = jnp.arange(n_window, dtype=jnp.int32)
    (x, ring, state, tail), _ = jax.lax.scan(
        window_pair, (x, ring, state, tail), (rows, params["layers"])
    )

    # the middle pair: the state-space layer whose scan output is the memory,
    # and the one full attention, whose K/V are the row the cross layers read
    mid = jax.tree_util.tree_map(lambda w: w[0], params["mid_layers"])
    ssm, attn = mid["ssm"], mid["attn"]
    h = layer_norm(x, ssm["norm1"], ssm["norm1_b"], config.rms_eps)
    out, memory, new_state, new_tail = ssm_mixer(
        h, ssm, state[n_window] if cached else None, tail[n_window] if cached else None, live
    )
    if cached:
        state = state.at[n_window].set(new_state)
        tail = tail.at[n_window].set(new_tail)
    x = _ffn(x + out, ssm, config)
    h = layer_norm(x, attn["norm1"], attn["norm1_b"], config.rms_eps)
    q, k_t, v_t = _project_qkv(h, attn, config)
    zero = jnp.zeros((), jnp.int32)
    full = (cache.k, cache.v) if cached else ()
    if decode:
        full = write_cache(full, (k_t, v_t), zero, lengths, impl=attn_impl, work=column_work)
        a = decode_attention(q, full[0], full[1], attend, sm_scale, impl=attn_impl, layer=zero, work=work)
    elif cached:
        full = write_cache(full, (k_t, v_t), zero, offset)
        a = cache_prefill_attention(q, full[0], full[1], offset, sm_scale, layer=zero)
    else:
        a = attention_at_positions(q, k_t, v_t, q_pos, q_pos, sm_scale)
    x = _ffn(x + _differential_out(a, attn, jnp.asarray(n_self + 1), config), attn, config)

    # the cross half keeps no state and only the head reads it: where the
    # caller wants one position a row, it runs there alone
    if last_positions is not None:
        at = last_positions.astype(jnp.int32)
        x = jnp.take_along_axis(x, at[:, None, None], axis=1)
        memory = jnp.take_along_axis(memory, at[:, None, None], axis=1)
        q_pos = offset + at[:, None]  # (B, 1)

    def cross_attention(h, lp):
        q = _pad_queries(_mm(h, lp["wqkv"]) + lp["bqkv"], config)
        if decode:
            return decode_attention(
                q, full[0], full[1], attend, sm_scale, impl=attn_impl, layer=zero, work=work
            )
        if not cached:
            return attention_at_positions(
                q, k_t, v_t, q_pos, jnp.arange(seq, dtype=jnp.int32)[None, :], sm_scale
            )
        # queries at q_pos over the row the middle layer has written
        start = q_pos[:, 0] if last_positions is not None else offset
        return cache_prefill_attention(q, full[0], full[1], start, sm_scale, layer=zero)

    def cross_pair(x, scanned):
        row, lp = scanned
        gmu, attn = lp["gmu"], lp["attn"]
        h = layer_norm(x, gmu["norm1"], gmu["norm1_b"], config.rms_eps)
        x = _ffn(x + gated_memory_unit(h, gmu, memory), gmu, config)
        h = layer_norm(x, attn["norm1"], attn["norm1_b"], config.rms_eps)
        a = cross_attention(h, attn)
        x = _ffn(x + _differential_out(a, attn, n_self + 3 + 2 * row, config), attn, config)
        return x, None

    x, _ = jax.lax.scan(
        cross_pair, x, (jnp.arange(config.cross_pairs, dtype=jnp.int32), params["cross_layers"])
    )
    return x, ((*full, *ring, state, tail) if cached else ()), attn_steps, column_writes
