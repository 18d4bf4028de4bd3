"""The gated delta rule's decode step (models/delta.py ``_step``) as ONE pallas
program a linear layer, in place on the stacked ``delta_state`` leaf.

The leaf is (R, B, H, d_k, d_v) float32: R linear layers' matrix state for
every slot, the buffer the model's layer scan carries. As XLA the step takes
``leaf[row]`` out (a copy of every slot's state, live or not), reads it for
``k^T S`` and ``q^T S``, rewrites it and puts it back: five passes over all
the slots' state a layer (PERF.md, PR 34). Here a grid step brings ONE live
row's (heads, d_k, d_v) block into VMEM, computes

    both = (k, q)^T S * exp(g)        d  = beta (v - both_k)
    S'   = exp(g) S + k (x) d         o  = both_q + (q . k) d

on the VPU in float32 (elementwise products, sums over d_k's sublanes; no
matrix unit, so nothing is rounded to bfloat16) and writes ``S'`` back to the
block it came from (``input_output_aliases``: the scan still carries one
buffer). The stack row is a prefetched scalar in the state's index map, as
``flash_decode`` and ``write_columns`` take their ``layer``.

The steps are a work list of the live rows (``delta_work_list``) under a
dynamic grid bound, the form ``flash_decode`` has: a row that belongs to nobody
has no step, its state is not touched and its ``o`` comes back zero. The list is
a function of the step's ``live`` mask alone, so a model makes it once a decode
step and hands it to every linear layer's call.

Layout: d_v fills the lanes and d_k the sublanes, so ``k`` and ``q`` are needed
as COLUMNS (a value a sublane, broadcast along the lanes). The caller's
(B, H, d_k) vectors are turned to (B, head blocks, d_k, 2 x heads) outside
(64 KB a row; the state is 2 MB), and a head's column is a static lane of the
block. ``v``, ``beta`` and ``exp(g)`` ride as (1, d_v) rows of one
(B, head blocks, 3, heads, d_v) operand. Every block's last two dimensions are
its array's own, so any number of heads a step lowers, and so does a state
tile that is no multiple of (8, 128): Mosaic pads (96, 192) to whole tiles in
VMEM, the slots hold the leaf's own bytes (olmo-hybrid-7b-pp2; the sums run
over d_k's sublanes, which padding lanes do not enter).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from prime_tpu.ops.pallas_attention import DECODE_VMEM_BUDGET, column_work_list


def delta_step_heads(heads: int, d_k: int, d_v: int) -> int:
    """Value heads a grid step holds: the most (a divisor of ``heads``) whose
    state block, in and out and each double-buffered by the pipeline, stays
    inside the decode kernels' VMEM budget. A step costs 0.2-0.35 us beside
    what its block costs, so fewer and larger steps win until the blocks no
    longer fit: 32 heads of (128, 128) float32 are 2 MB a block, 8 MB held,
    one step a row. A head's (d_k, d_v) is counted as VMEM holds it, in whole
    (8, 128) tiles: 30 heads of (96, 192) are 2.2 MB in the slots and 2.9 MB
    in VMEM (192 lanes take two tiles' 256), so 15 a step, two steps a row."""
    tile = -(-d_k // 8) * 8 * -(-d_v // 128) * 128 * 4
    for block in range(heads, 0, -1):
        if heads % block == 0 and 4 * block * tile <= DECODE_VMEM_BUDGET:
            return block
    return 1


def delta_work_list(live: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(rows, n): the live rows of a (B,) bool mask in order, as the first
    ``n`` entries of a (B + 1,) table whose tail repeats the last (the
    pipeline reads the index maps a step ahead): the list the column write
    walks (``pallas_attention.column_work_list``). No live row: ``n`` is 1 and
    the one step finds its row dead (``delta_decode_step`` copies it through)."""
    _, rows, n = column_work_list(live)
    return rows, n


def _delta_kernel(
    live_ref,   # (B,) scalar-prefetch: 1 where the row belongs to somebody
    rows_ref,   # (B + 1,) scalar-prefetch: the work list's row of each grid step
    layer_ref,  # (1,) scalar-prefetch: read by the state's index map only
    kq_ref,     # (1, 1, d_k, 2 * HEADS): k columns, then q columns
    vbd_ref,    # (1, 1, 3, HEADS, d_v): v, beta and exp(g), each along the lanes
    s_ref,      # (1, HEADS, d_k, d_v) the row's state block
    o_ref,      # (1, 1, HEADS, d_v)
    out_ref,    # (1, HEADS, d_k, d_v) the same block of the same buffer
):
    heads = s_ref.shape[1]
    alive = live_ref[rows_ref[pl.program_id(1)]] > 0

    @pl.when(alive)
    def _step():
        # a static loop: one head's (d_k, d_v) block is live in registers at a time
        for h in range(heads):
            state = s_ref[0, h]                                   # (d_k, d_v)
            k = kq_ref[0, 0, :, h : h + 1]                        # (d_k, 1)
            q = kq_ref[0, 0, :, heads + h : heads + h + 1]
            v, beta, decay = (vbd_ref[0, 0, i, h : h + 1, :] for i in range(3))  # (1, d_v)
            k_s = jnp.sum(k * state, axis=0, keepdims=True) * decay
            q_s = jnp.sum(q * state, axis=0, keepdims=True) * decay
            d = beta * (v - k_s)
            out_ref[0, h] = decay * state + k * d
            o_ref[0, 0, h : h + 1, :] = q_s + jnp.sum(q * k, axis=0, keepdims=True) * d

    # the one step of a call with no live row: the block goes back as it came
    @pl.when(jnp.logical_not(alive))
    def _keep():
        out_ref[...] = s_ref[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def delta_decode_step(
    leaf: jnp.ndarray,    # (R, B, H, d_k, d_v) float32 the stacked state
    layer: jnp.ndarray | int,  # () the stack row to step
    q: jnp.ndarray,       # (B, H, d_k) float32, as ``_step`` takes them
    k: jnp.ndarray,
    v: jnp.ndarray,       # (B, H, d_v)
    beta: jnp.ndarray,    # (B, H)
    g: jnp.ndarray,       # (B, H)
    live: jnp.ndarray,    # (B,) bool: the rows that belong to somebody
    work: tuple | None = None,  # delta_work_list(live), where the caller made it
    #                             once for all its layers
    interpret: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(leaf, o (B, H, d_v)): ``models.delta._step`` on stack row ``layer`` of
    the leaf for the live rows, the leaf updated in place. A row that is not
    live keeps its state bit for bit and has ``o`` zero; so has every other
    stack row."""
    _, batch, heads, d_k, d_v = leaf.shape
    block = delta_step_heads(heads, d_k, d_v)
    blocks = heads // block
    rows, n = delta_work_list(live) if work is None else work

    def columns(t):  # (B, H, d_k) -> (B, head blocks, d_k, heads a block)
        return jnp.swapaxes(t.reshape(batch, blocks, block, d_k), 2, 3)

    def along(t):  # (B, H) or (B, H, d_v) -> (B, head blocks, heads a block, d_v)
        t = t if t.ndim == 3 else t[..., None]
        return jnp.broadcast_to(t, (batch, heads, d_v)).reshape(batch, blocks, block, d_v)

    kq = jnp.concatenate([columns(k), columns(q)], axis=-1).astype(jnp.float32)
    vbd = jnp.stack([along(v), along(beta), along(jnp.exp(g))], axis=2).astype(jnp.float32)

    def row_map(hb, step, live, rows, lay):
        return (rows[step], hb, 0, 0)

    def vbd_map(hb, step, live, rows, lay):
        return (rows[step], hb, 0, 0, 0)

    def state_map(hb, step, live, rows, lay):
        return (lay[0], rows[step], hb, 0, 0)

    state_spec = pl.BlockSpec((None, 1, block, d_k, d_v), state_map)
    o, leaf = pl.pallas_call(
        _delta_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            # a step is one live row with `block` of its heads (all of them
            # where they fit, so the first axis is 1); the second axis is the
            # work list, its bound traced: at most `batch` steps
            grid=(blocks, n),
            in_specs=[
                pl.BlockSpec((1, 1, d_k, 2 * block), row_map),
                pl.BlockSpec((1, 1, 3, block, d_v), vbd_map),
                state_spec,
            ],
            out_specs=[pl.BlockSpec((1, 1, block, d_v), row_map), state_spec],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((batch, blocks, block, d_v), jnp.float32),
            jax.ShapeDtypeStruct(leaf.shape, leaf.dtype),
        ],
        # operands count the three prefetched scalars, kq and vbd first
        input_output_aliases={5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        cost_estimate=pl.CostEstimate(
            # every row live: the ceiling, as flash_decode charges its compute.
            # Bytes are one stack row's state in and out; the live mask is
            # traced (unknown at estimate time) and the leaf's other stack rows
            # are never touched (docs/kernels.md "Cost estimates")
            flops=8 * batch * heads * d_k * d_v,
            bytes_accessed=2 * batch * heads * d_k * d_v * 4,
            transcendentals=0,
        ),
        interpret=interpret,
    )(
        live.astype(jnp.int32), rows, jnp.asarray(layer, jnp.int32).reshape(1),
        kq, vbd, leaf,
    )
    # no step visits a dead row: what the call left there is not a result
    return leaf, jnp.where(live[:, None, None], o.reshape(batch, heads, d_v), 0)
