"""State-space mixer (Mamba-1) and gated memory unit of the "sambay" block.

The state-space layer, for a normed input ``h`` (B, S, d):

    [xs; z] = W_in h
    u       = silu(causal depthwise conv1d(xs, k) + b_conv)
    [dt; B; C] = W_x u                      (dt_rank + d_state + d_state)
    delta   = softplus(W_dt dt + b_dt)
    s_t     = exp(delta_t A) s_{t-1} + delta_t B_t u_t      A = -exp(A_log)
    y_t     = C_t . s_t + D u_t
    out     = W_out (y * silu(z))

What it carries from one call to the next is ``s`` and the last ``k - 1``
columns of the pre-conv ``xs`` (the conv tail). A call takes both and returns
both, so a prompt's chunks hand them on and a decode step is the same
recurrence at S = 1. ``y`` before the gate is the "memory" that the block's
gated memory units read at the same position.

Layout: the state is (B, d_state, d_inner) float32, channels minor. d_inner
(5120) fills the TPU's 128 lanes and d_state (16) two sublane tiles; with the
state minor, 16 values would be padded to 128 lanes, eight times the bytes
and the work. ``A_log`` is stored the same way, (d_state, d_inner).

Everything here is plain XLA: the recurrence over a chunk's positions is a
``lax.scan`` whose carry is the state (PERF.md section 7 queues the kernel).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from prime_tpu.models.quantize import matmul as _mm

# positions the prefill scan's body holds, unrolled: a step is a few dozen
# vector operations on a state that fits the chip's fast memory, so the loop's
# own overhead is most of a step; unrolling amortises it
SCAN_UNROLL = 8


def ssm_mixer(
    h: jnp.ndarray,                  # (B, S, d) the normed input
    lp: dict,                        # one layer's state-space params
    state: jnp.ndarray | None,       # (B, d_state, d_inner) float32; None: zeros
    tail: jnp.ndarray | None,        # (B, d_conv - 1, d_inner); None: zeros
    live: jnp.ndarray | None = None,  # (B, S) bool: False positions are padding
    #                                   past a row's last token; they leave the
    #                                   state and the tail as they were
):
    """Returns (out (B, S, d), memory y (B, S, d_inner), state, tail)."""
    batch, seq, _ = h.shape
    d_state, d_inner = lp["A_log"].shape
    k = lp["conv_w"].shape[0]
    dt_rank = lp["w_dt"].shape[0]

    xz = _mm(h, lp["w_in"])
    xs, z = xz[..., :d_inner], xz[..., d_inner:]
    if tail is None:
        tail = jnp.zeros((batch, k - 1, d_inner), dtype=xs.dtype)
    ext = jnp.concatenate([tail.astype(xs.dtype), xs], axis=1)  # (B, S + k - 1, d_inner)
    conv = sum(
        ext[:, j : j + seq].astype(jnp.float32) * lp["conv_w"][j].astype(jnp.float32)
        for j in range(k)
    ) + lp["conv_b"].astype(jnp.float32)
    u = jax.nn.silu(conv).astype(xs.dtype)

    dbc = _mm(u, lp["w_x"])
    dt, b_t, c_t = jnp.split(dbc, [dt_rank, dt_rank + d_state], axis=-1)
    delta = jax.nn.softplus(_mm(dt, lp["w_dt"]).astype(jnp.float32) + lp["b_dt"])
    if live is not None:
        # delta 0: exp(0 * A) = 1 and 0 * B * u = 0, the state passes through
        delta = jnp.where(live[..., None], delta, 0.0)
    a = -jnp.exp(lp["A_log"])  # (d_state, d_inner) float32
    u32 = u.astype(jnp.float32)
    if state is None:
        state = jnp.zeros((batch, d_state, d_inner), dtype=jnp.float32)

    def step(s, inputs):
        delta_t, du_t, b, c = inputs  # (B, d_inner) x 2, (B, d_state) x 2
        s = jnp.exp(delta_t[:, None, :] * a) * s + du_t[:, None, :] * b[:, :, None]
        return s, jnp.sum(c[:, :, None] * s, axis=1)

    du = delta * u32
    b32, c32 = b_t.astype(jnp.float32), c_t.astype(jnp.float32)
    if seq == 1:
        state, y = step(state, (delta[:, 0], du[:, 0], b32[:, 0], c32[:, 0]))
        y = y[:, None]
    else:
        time_major = tuple(jnp.swapaxes(t, 0, 1) for t in (delta, du, b32, c32))
        state, y = jax.lax.scan(step, state, time_major, unroll=min(SCAN_UNROLL, seq))
        y = jnp.swapaxes(y, 0, 1)
    y = (y + lp["D"] * u32).astype(h.dtype)  # the memory: before the gate
    out = _mm(y * jax.nn.silu(z), lp["w_out"])

    # the last k - 1 pre-conv columns of each row's live positions (a row with
    # none keeps its tail): ext[r : r + k - 1] for r live positions
    if live is None:
        new_tail = ext[:, seq:]
    else:
        count = jnp.sum(live.astype(jnp.int32), axis=1)  # (B,)
        rows = count[:, None] + jnp.arange(k - 1)[None, :]
        new_tail = jnp.take_along_axis(ext, rows[:, :, None], axis=1)
    return out, y, state, new_tail.astype(tail.dtype)


def gated_memory_unit(h: jnp.ndarray, lp: dict, memory: jnp.ndarray) -> jnp.ndarray:
    """``W_out (silu(W_in h) * memory)``: the memory is another layer's scan
    output at the same position; the unit keeps no state of its own."""
    return _mm(jax.nn.silu(_mm(h, lp["w_in"])) * memory, lp["w_out"])
