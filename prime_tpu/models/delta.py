"""Linear attention by the gated delta rule (Gated DeltaNet), the mixer of the
"gated_delta" block's linear layers.

For a normed input ``h`` (B, S, d), ``n_k`` key heads of ``d_k`` and ``n_v``
value heads of ``d_v`` (``n_v / n_k`` value heads a key head):

    [q; k; v; z] = W_qkvz h      laid out a KEY head: q d_k, k d_k, then its value
    [b; a]       = W_ba h        heads' v and z (d_v each); b and a one a value head
           (``grouped``: all heads' q, then all k, all v, all z, and all b, then all a:
           separate projections side by side, the order the conv takes. A
           projection a head whose widths are no multiples of a lane tile (96,
           96, 192, 192) is then split at four places and not thirty: split a
           head, XLA re-lays the whole matrix out at every decode call)
    [q; k; v]    = silu(causal depthwise conv1d([q; k; v], taps))      no bias
    beta = sigmoid(b)            g = -exp(A_log) softplus(a + dt_bias)   float32
           (2 sigmoid(b) under ``neg_eigval``: the transition I - beta k k^T then
           has eigenvalues in (-1, 1], Olmo-Hybrid's linear_allow_neg_eigval)
    q, k: repeated to the value heads (key head j serves 2j, 2j + 1), each
          x / sqrt(sum x^2 + 1e-6) over d_k; q times d_k^-1/2

    a value head, state S (d_k, d_v) float32, position t:
        S  <- exp(g_t) S
        d   = beta_t (v_t - k_t^T S)
        S  <- S + k_t (x) d
        o_t = q_t^T S

    y   = w * o / sqrt(mean o^2 + 1e-6) * silu(z)    over d_v, a value head
    out = W_out y

What it carries from one call to the next is ``S`` and the last ``taps - 1``
pre-conv columns of ``[q; k; v]`` (the conv tail). A call takes both and
returns both, so a prompt's chunks hand them on.

**Decode** (S = 1) is the recurrence, one read of the state for both products
it needs (``k^T S`` and ``q^T S``: ``o = exp(g) q^T S + (q . k) d``) and one
pass that rewrites it: ``_step``, the definition and the CPU's path. Where the
decode attention takes its kernel (a chip, or ``attn_impl="pallas"``) the model
hands the mixer the STACKED leaf of every linear layer's state and the stack
row (``in_place``), and ``ops/pallas_delta.py`` runs the same step in place
over a work list of the live rows: a live row's state moves into VMEM once and
back once, a row that belongs to nobody is not touched, and no layer's state is
sliced out of the stack.

**Prefill** is the chunked form over blocks of ``CHUNK`` positions. Inside a
block, with ``G`` the running sum of ``g`` and ``S_0`` the state it starts from:

    (I + A) D = beta (V - exp(G) K S_0)      A_ts = beta_t exp(G_t - G_s) k_t.k_s, s < t
    O   = exp(G) Q S_0 + M D                 M_ts = exp(G_t - G_s) q_t.k_s,       s <= t
    S_C = exp(G_C) S_0 + (exp(G_C - G) K)^T D

so with ``T = (I + A)^-1``, ``U = T beta V`` and ``W = T beta exp(G) K`` made
for every block at once, the scan over blocks is four matrix products a step
(``D = U - W S``): a 512-token chunk is 8 steps of products, not 512 of vector
work. ``T`` is built quadrant by quadrant (the inverse of a block-triangular
matrix from its diagonal blocks' inverses), which is as stable as substitution;
a Neumann series is not (identical keys, as a run of one token gives, make its
terms grow to 1e8 before they cancel). Every product that touches the state is
float32 at ``highest`` precision: the state is stated float32.

``live`` False positions (padding past a row's last token, a decode row that
belongs to nobody) leave the state and the tail as they were: ``beta`` 0 and
``g`` 0 there.

Layout: the state is (B, n_v, d_k, d_v), values minor: d_v fills the lanes.
Everything here but that decode kernel is plain XLA (ROADMAP R7 queues the
chunked prefill's products).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from prime_tpu.models.quantize import matmul as _mm

CHUNK = 64  # positions a block of the chunked form holds
_PRECISION = jax.lax.Precision.HIGHEST


def _product(spec: str, a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    return jnp.einsum(spec, a, b, precision=_PRECISION)


def _unit_lower_inverse(a: jnp.ndarray) -> jnp.ndarray:
    """``(I + a)^-1`` for strictly lower-triangular ``a`` (..., C, C), C a power
    of two. Level by level: once the diagonal blocks of width s are inverted
    (``t``, block-diagonal), the blocks of width 2s are
    ``[[T11, 0], [-T22 L21 T11, T22]] = t - t X t`` with ``X`` their lower-left
    quadrants of ``a``."""
    c = a.shape[-1]
    index = jnp.arange(c)
    t = jnp.broadcast_to(jnp.eye(c, dtype=a.dtype), a.shape)
    s = 1
    while s < c:
        row, col = index[:, None], index[None, :]
        quadrant = (row // (2 * s) == col // (2 * s)) & (row % (2 * s) >= s) & (col % (2 * s) < s)
        x = jnp.where(quadrant, a, 0.0)
        t = t - _product("...ij,...jk->...ik", _product("...ij,...jk->...ik", t, x), t)
        s *= 2
    return t


def delta_rule_recurrent(q, k, v, beta, g, state):
    """The recurrence, a position at a time: q, k (B, S, H, d_k), v (B, S, H,
    d_v), beta, g (B, S, H), state (B, H, d_k, d_v), all float32. Returns
    (o (B, S, H, d_v), state). The definition the chunked form is held to
    (tests/test_qwen3_next.py); decode runs ``_step`` once."""

    def step(s, inputs):
        return _step(s, *inputs)

    time_major = tuple(jnp.swapaxes(t, 0, 1) for t in (q, k, v, beta, g))
    state, o = jax.lax.scan(step, state, time_major)
    return jnp.swapaxes(o, 0, 1), state


def _step(state, q, k, v, beta, g):
    """One position: q, k (B, H, d_k), v (B, H, d_v), beta, g (B, H)."""
    decay = jnp.exp(g)[..., None]
    # k^T S and q^T S in ONE pass over the state
    kq = jnp.stack([k, q], axis=2)                                    # (B, H, 2, d_k)
    both = jnp.sum(kq[..., None] * state[:, :, None], axis=3) * decay[..., None]
    d = beta[..., None] * (v - both[:, :, 0])
    state = decay[..., None] * state + k[..., None] * d[:, :, None, :]
    o = both[:, :, 1] + jnp.sum(q * k, axis=-1, keepdims=True) * d
    return state, o


def delta_rule_chunked(q, k, v, beta, g, state):
    """The same function as ``delta_rule_recurrent`` by the chunked form
    (module docstring). Any S: the sequence is padded to whole blocks with
    positions that change nothing (beta 0, g 0)."""
    batch, seq, heads, d_k = q.shape
    block = CHUNK
    while block // 2 >= seq:  # a short call: the least power of two that holds it
        block //= 2
    pad = -seq % block
    if pad:
        q, k, v, beta, g = (
            jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2)) for t in (q, k, v, beta, g)
        )
    n = (seq + pad) // block

    def blocks(t):  # (B, S, H, *) -> (B, H, n, C, *)
        t = t.reshape(batch, n, block, heads, *t.shape[3:])
        return jnp.moveaxis(t, 3, 1)

    q, k, v, beta, g = (blocks(t) for t in (q, k, v, beta, g))
    total = jnp.cumsum(g, axis=-1)                                    # G (B, H, n, C)
    index = jnp.arange(block)
    lower = index[:, None] >= index[None, :]
    # exp(G_t - G_s) for s <= t, 0 above the diagonal (masked before the exp:
    # above it the difference is positive and may overflow)
    decay = jnp.where(lower, jnp.exp(jnp.where(lower, total[..., :, None] - total[..., None, :], 0.0)), 0.0)
    kk = _product("bhntd,bhnsd->bhnts", k, k)
    a = jnp.where(index[:, None] > index[None, :], beta[..., None] * decay * kk, 0.0)
    t_inv = _unit_lower_inverse(a)
    grow = jnp.exp(total)
    u = _product("bhnts,bhnsd->bhntd", t_inv, beta[..., None] * v)
    w = _product("bhnts,bhnsd->bhntd", t_inv, (beta * grow)[..., None] * k)
    m = decay * _product("bhntd,bhnsd->bhnts", q, k)
    q_in = grow[..., None] * q
    k_out = jnp.exp(total[..., -1:] - total)[..., None] * k
    end = grow[..., -1]                                               # exp(G_C) (B, H, n)

    def step(s, inputs):
        u, w, m, q_in, k_out, end = inputs
        d = u - _product("bhtk,bhkv->bhtv", w, s)
        o = _product("bhtk,bhkv->bhtv", q_in, s) + _product("bhts,bhsv->bhtv", m, d)
        s = end[..., None, None] * s + _product("bhtk,bhtv->bhkv", k_out, d)
        return s, o

    block_major = tuple(jnp.moveaxis(t, 2, 0) for t in (u, w, m, q_in, k_out, end))
    state, o = jax.lax.scan(step, state, block_major)                 # o (n, B, H, C, d_v)
    o = jnp.moveaxis(o, (0, 3), (1, 2)).reshape(batch, seq + pad, heads, -1)
    return o[:, :seq], state


def delta_mixer(
    h: jnp.ndarray,                  # (B, S, d) the normed input
    lp: dict,                        # one linear layer's params
    state: jnp.ndarray | None,       # (B, n_v, d_k, d_v) float32; None: zeros
    tail: jnp.ndarray | None,        # (B, taps - 1, conv channels); None: zeros
    live: jnp.ndarray | None = None,  # (B, S) bool: False positions change nothing
    *,
    key_heads: int,
    in_place: tuple | None = None,   # (stack row, work list): ``state`` is the STACKED
    #                                  (R, B, n_v, d_k, d_v) leaf, S is 1, and the kernel
    #                                  steps that row of it where it lies
    neg_eigval: bool = False,        # beta = 2 sigmoid(b)
    grouped: bool = False,           # W_qkvz is [all q | all k | all v | all z], W_ba [b | a]
):
    """Returns (out (B, S, d), state, tail); with ``in_place`` the state
    returned is the stacked leaf."""
    batch, seq, _ = h.shape
    n_v, d_v = lp["A_log"].shape[0], lp["gate_norm"].shape[0]
    taps, channels = lp["conv_w"].shape
    per = n_v // key_heads
    d_k = (channels - n_v * d_v) // (2 * key_heads)
    key_width = key_heads * d_k

    if grouped:
        # the conv's channel order is the projection's own: nothing is regrouped
        qkvz = _mm(h, lp["w_qkvz"])
        mixed, z = qkvz[..., :channels], qkvz[..., channels:]
        ba = _mm(h, lp["w_ba"]).astype(jnp.float32)
        b, a = ba[..., :n_v], ba[..., n_v:]
    else:
        qkvz = _mm(h, lp["w_qkvz"]).reshape(batch, seq, key_heads, 2 * d_k + 2 * per * d_v)
        q, k, v, z = jnp.split(qkvz, [d_k, 2 * d_k, 2 * d_k + per * d_v], axis=-1)
        ba = _mm(h, lp["w_ba"]).reshape(batch, seq, key_heads, 2 * per).astype(jnp.float32)
        b, a = (t.reshape(batch, seq, n_v) for t in (ba[..., :per], ba[..., per:]))
        mixed = jnp.concatenate([t.reshape(batch, seq, -1) for t in (q, k, v)], axis=-1)

    if tail is None:
        tail = jnp.zeros((batch, taps - 1, channels), dtype=mixed.dtype)
    ext = jnp.concatenate([tail.astype(mixed.dtype), mixed], axis=1)  # (B, S + taps - 1, channels)
    conv = sum(
        ext[:, j : j + seq].astype(jnp.float32) * lp["conv_w"][j].astype(jnp.float32)
        for j in range(taps)
    )
    mixed = jax.nn.silu(conv).astype(h.dtype).astype(jnp.float32)

    def heads(t, n):
        return t.reshape(batch, seq, n, -1)

    def unit(t):  # a key head's vector at unit length, for its value heads
        t = t * jax.lax.rsqrt(jnp.sum(t * t, axis=-1, keepdims=True) + 1e-6)
        return jnp.repeat(t, per, axis=2)

    q = unit(heads(mixed[..., :key_width], key_heads)) * d_k**-0.5
    k = unit(heads(mixed[..., key_width : 2 * key_width], key_heads))
    v = heads(mixed[..., 2 * key_width :], n_v)
    beta = jax.nn.sigmoid(b)
    if neg_eigval:
        beta = 2.0 * beta
    g = -jnp.exp(lp["A_log"]) * jax.nn.softplus(a + lp["dt_bias"])
    if live is not None:
        beta = jnp.where(live[..., None], beta, 0.0)
        g = jnp.where(live[..., None], g, 0.0)
    if state is None:
        state = jnp.zeros((batch, n_v, d_k, d_v), dtype=jnp.float32)

    with jax.named_scope("delta_rule"):
        if in_place is not None:
            from prime_tpu.ops.attention import _pallas_interpret
            from prime_tpu.ops.pallas_delta import delta_decode_step

            row, work = in_place
            alive = jnp.ones((batch,), bool) if live is None else live[:, 0]
            state, o = delta_decode_step(
                state, row, q[:, 0], k[:, 0], v[:, 0], beta[:, 0], g[:, 0], alive, work,
                interpret=_pallas_interpret(),
            )
            o = o[:, None]
        elif seq == 1:
            state, o = _step(state, q[:, 0], k[:, 0], v[:, 0], beta[:, 0], g[:, 0])
            o = o[:, None]
        else:
            o, state = delta_rule_chunked(q, k, v, beta, g, state)

    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + 1e-6)
    gate = jax.nn.silu(z.reshape(batch, seq, n_v, d_v).astype(jnp.float32))
    y = (o * lp["gate_norm"].astype(jnp.float32) * gate).astype(h.dtype)
    out = _mm(y.reshape(batch, seq, n_v * d_v), lp["w_out"])

    # the last taps - 1 pre-conv columns of each row's live positions (a row
    # with none keeps its tail), as models/ssm.py keeps its own
    if live is None:
        new_tail = ext[:, seq:]
    else:
        count = jnp.sum(live.astype(jnp.int32), axis=1)
        rows = count[:, None] + jnp.arange(taps - 1)[None, :]
        new_tail = jnp.take_along_axis(ext, rows[:, :, None], axis=1)
    return out, state, new_tail.astype(tail.dtype)
