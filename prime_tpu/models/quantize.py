"""int8 / int4 weight quantization for serving (W8A16 / W4A16).

At small decode batches the weight matrices — not the KV cache — dominate
HBM traffic (every step reads every layer's weights once), so quantized
weights are the other half of the decode-bandwidth story next to the int8
KV cache.

int8 scheme: per-output-channel symmetric. A quantized matrix is the pytree
tuple ``(q int8 (..., in, out), scale fp32 (..., 1, out))`` and the matmul
dequantizes by scaling the OUTPUT columns — ``x @ (q * s) == (x @ q) * s``
exactly, so XLA reads int8 from HBM and fuses the convert + scale into the
matmul epilogue; the fp weights are never materialized.

int4 scheme: group-wise symmetric along the REDUCTION axis (AWQ/GPTQ-style,
group=128 input channels), because 4 bits with one scale per whole column
loses too much signal. Storage is NIBBLE-PACKED uint8 — two 4-bit values
per byte, low nibble = first half of the group, high nibble = second half —
NOT the jnp.int4 dtype: int4 arrays cannot cross a jit boundary on every
backend, and a packed uint8 carrier moves the same 4 bits/weight while
staying a first-class dtype everywhere. The tuple is ``(packed uint8 (..., in/2,
out), scale fp32 (..., groups, 1, out))``; the matmul sign-extends the
nibbles in-graph and splits the reduction into per-group partials —
``sum_g (x_lo @ lo_g + x_hi @ hi_g) * s_g`` — so HBM streams half the
int8 bytes and the MXU still sees batched bf16 matmuls.

Norms, embeddings, the router, and the LM head stay in their original dtype
(gathers and the final fp32 logits matmul have different numerics); the
seven big per-layer matrices are what move the bytes.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

QUANTIZED_LAYER_KEYS = (
    "wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down",
    # MLA projections (models/mla.py): wkv_a/wq_a/wq_b flow through matmul();
    # wkv_b's absorb/value einsums fold the per-output-channel int8 scales
    # themselves (_split_wkv_b)
    "wkv_a", "wq_a", "wq_b", "wkv_b",
    # DeepSeekMoE always-on shared expert (dense path through matmul())
    "w_shared_gate", "w_shared_up", "w_shared_down",
    # the sambay block (models/sambay.py): fused qkv, state-space and memory
    # unit in/out projections
    "wqkv", "w_in", "w_out",
)
# the layer stacks a params tree may hold (dense_layers: the DeepSeek prefix;
# mid_layers, cross_layers: the sambay block, whose stacks nest a dict a mixer)
LAYER_SUBTREES = ("layers", "dense_layers", "mid_layers", "cross_layers")


def _refuse_delta_stack(params: dict) -> None:
    """The gated_delta block's stack (its linear layers under ``delta``) is
    served in the precision it was made in: its float32 matrix state and decay
    terms sit beside matrices no quantized path has been held to a reference
    for."""
    if "delta" in params.get("layers", {}):
        raise ValueError("the gated_delta block does not serve int8 or int4 weights: ROADMAP.md R7")
    if "window" in params.get("layers", {}):  # stacks a kind of layer (models/kinds.py)
        raise ValueError("a layer_types model does not serve int8 or int4 weights: ROADMAP.md R7")


def quantize_weight(w: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Per-output-channel (last axis) symmetric int8."""
    absmax = jnp.max(jnp.abs(w.astype(jnp.float32)), axis=-2, keepdims=True)
    scale = absmax / 127.0
    q = jnp.round(w.astype(jnp.float32) / jnp.maximum(scale, 1e-12)).astype(jnp.int8)
    return q, scale


def quantize_params_int8(params: dict) -> dict:
    """Return a params tree whose big layer matrices are (int8, scale) tuples.

    MoE expert stacks quantize the same way (the per-output-channel axis is
    still the last one). The rest of the tree is shared by reference.
    """
    _refuse_delta_stack(params)

    def walk(layers: dict) -> dict:
        out = {}
        for key, value in layers.items():
            if isinstance(value, dict):
                value = walk(value)
            elif key in QUANTIZED_LAYER_KEYS and not isinstance(value, tuple):
                value = quantize_weight(value)
            out[key] = value
        return out

    out = dict(params)
    for subtree in LAYER_SUBTREES:
        if subtree in params:
            out[subtree] = walk(params[subtree])
    return out


INT4_GROUP = 128


def quantize_weight_int4(
    w: jnp.ndarray, group: int = INT4_GROUP
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Group-wise (reduction axis) symmetric int4, nibble-packed into uint8:
    one fp32 scale per ``group`` input channels per output channel. Falls
    back to a single group when the reduction dim doesn't divide; an odd
    reduction dim (can't pack pairs) keeps an unpacked int8 carrier, which
    ``_matmul_int4`` detects by shape."""
    *lead, d_in, d_out = w.shape
    g = group if d_in % group == 0 else d_in
    groups = d_in // g
    wg = w.astype(jnp.float32).reshape(*lead, groups, g, d_out)
    absmax = jnp.max(jnp.abs(wg), axis=-2, keepdims=True)  # (..., groups, 1, out)
    scale = absmax / 7.0
    q = jnp.clip(jnp.round(wg / jnp.maximum(scale, 1e-12)), -8, 7).astype(jnp.int8)
    if g % 2:  # odd group: no pair packing; int8 carrier, same scale layout
        return q.reshape(*lead, d_in, d_out), scale
    # low nibble = first half-group, high nibble = second half-group
    lo = q[..., : g // 2, :].astype(jnp.uint8) & 0xF
    hi = q[..., g // 2 :, :].astype(jnp.uint8) & 0xF
    packed = lo | (hi << 4)
    return packed.reshape(*lead, d_in // 2, d_out), scale


def quantize_params_int4(params: dict, group: int = INT4_GROUP) -> dict:
    """Params tree with the big DENSE layer matrices as (int4, scale) tuples.

    MoE expert stacks are left untouched (the grouped-reduction einsum isn't
    wired through the expert dispatch path) — quantize those with
    :func:`quantize_params_int8` first if needed; int8 tuples and int4
    tuples coexist in one tree, ``matmul`` dispatches on dtype."""
    _refuse_delta_stack(params)
    out = dict(params)
    for subtree in ("layers", "dense_layers"):  # dense_layers: DeepSeek prefix
        if subtree not in params:
            continue
        layers = dict(params[subtree])
        for key in QUANTIZED_LAYER_KEYS:
            if key == "wkv_b":
                # the MLA absorb einsum CONTRACTS wkv_b's reduction axis,
                # where int4's group scales live — only int8's output-channel
                # scheme folds there; a later int8 pass picks this key up
                continue
            w = layers.get(key)
            if w is not None and not isinstance(w, tuple) and w.ndim == 3:
                layers[key] = quantize_weight_int4(w, group=group)
        out[subtree] = layers
    return out


def quantize_kv_int4(x: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Per-slot symmetric int4 for the KV cache, nibble-packed along
    head_dim: x is (..., head_dim, S) feature-major; returns (packed uint8
    (..., head_dim/2, S), scale fp32 (..., 1, S)). Same scales layout as the
    int8 ``quantize_kv`` — the flash-decode kernel's scale plumbing is
    shared; only the carrier (and the VMEM widening) differs. Low nibble =
    features [0, D/2), high nibble = [D/2, D), matching the weight packing
    convention so one unpack rule serves both."""
    d = x.shape[-2]
    if d % 2:
        raise ValueError(f"head_dim {d} must be even to nibble-pack the KV cache")
    absmax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-2, keepdims=True)
    scale = absmax / 7.0
    q = jnp.clip(
        jnp.round(x.astype(jnp.float32) / jnp.maximum(scale, 1e-12)), -8, 7
    ).astype(jnp.int8)
    lo = q[..., : d // 2, :].astype(jnp.uint8) & 0xF
    hi = q[..., d // 2 :, :].astype(jnp.uint8) & 0xF
    return lo | (hi << 4), scale


def unpack_kv_int4(packed: jnp.ndarray) -> jnp.ndarray:
    """Widen a nibble-packed KV array (..., head_dim/2, S) back to fp32
    (..., head_dim, S) — the XLA reference path's dequant (scales applied by
    the caller) and the ground truth the pallas int4 decode is tested
    against."""
    lo, hi = _unpack_nibbles(packed)
    return jnp.concatenate([lo, hi], axis=-2).astype(jnp.float32)


def _unpack_nibbles(packed: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Sign-extend the two 4-bit values in each uint8 to int8 in [-8, 7]."""
    lo = ((packed & 0xF).astype(jnp.int8) ^ 8) - 8
    hi = ((packed >> 4).astype(jnp.int8) ^ 8) - 8
    return lo, hi


def _mesh_context_active() -> bool:
    """True inside any mesh context (``with mesh:`` or ``jax.set_mesh``) with
    more than one device — the SPMD regime where a bare ``pl.pallas_call``
    cannot partition under jit (same rule the attention ops document: mesh
    callers must take the XLA path). ``with mesh:`` sets only the legacy
    physical-mesh thread resource and ``jax.set_mesh`` only the abstract
    mesh, so both are read."""
    from jax._src import mesh as mesh_lib

    physical = mesh_lib.thread_resources.env.physical_mesh
    if not physical.empty and physical.size > 1:
        return True
    abstract = jax.sharding.get_abstract_mesh()
    return not abstract.empty and abstract.size > 1


def _int4_pallas_eligible(x: jnp.ndarray, q: jnp.ndarray, interpret: bool) -> bool:
    """Gate the fused pallas int4 kernel to the regime it exists for: the
    SINGLE-DEVICE decode/gemv path on TPU (few activation rows, per-layer
    2-D packed weights, lane-aligned output). Prefill and training keep the
    XLA path — they are MXU-bound, not weight-bandwidth-bound — as do
    stacked (pre-scan-slice) weights and CPU runs (unless interpret mode is
    forced for tests). Under an active multi-device mesh context the XLA
    unpack chain runs instead: a bare pallas_call cannot partition under
    SPMD jit (ADVICE r5). A multi-chip host WITHOUT a mesh stays eligible —
    unsharded jit commits to one device, where the kernel is exactly the
    weight-bandwidth win it was built for."""
    import numpy as np

    if q.ndim != 2 or q.dtype != jnp.uint8:
        return False
    if q.shape[-1] % 128:
        return False
    rows = int(np.prod(x.shape[:-1]))
    if rows > 32:
        return False
    if _mesh_context_active():
        return False
    return interpret or jax.default_backend() == "tpu"


def _matmul_int4(x: jnp.ndarray, q: jnp.ndarray, scale: jnp.ndarray) -> jnp.ndarray:
    """Per-group partial matmuls, scaled then summed over groups: exact
    w.r.t. ``x @ dequant(q, scale)`` up to fp accumulation order. ``q`` is
    nibble-packed uint8 (rows = d_in/2) or, for an odd reduction dim, an
    unpacked int8 carrier (rows = d_in).

    On TPU in the decode regime the packed case dispatches to the fused
    pallas kernel (ops/pallas_quant.py): XLA materializes the unpack chain's
    intermediates to HBM, forfeiting the nibble packing's bandwidth halving;
    the kernel unpacks in VMEM so HBM streams exactly the packed bytes."""
    d_in = x.shape[-1]
    d_out = q.shape[-1]
    groups = scale.shape[-3]
    g = d_in // groups
    # the kernel's in-loop activation slice is on the LANE dim: group
    # boundaries must be 128-aligned (always true for INT4_GROUP=128; a
    # single whole-dim group is the full lane dim, also fine)
    lane_aligned = g % 128 == 0 or groups == 1
    from prime_tpu.ops.attention import _pallas_interpret

    interpret = _pallas_interpret()
    if (
        q.shape[-2] * 2 == d_in
        and lane_aligned
        and _int4_pallas_eligible(x, q, interpret)
    ):
        from prime_tpu.ops.pallas_quant import int4_matmul

        y = int4_matmul(
            x.reshape(-1, d_in), q, scale[..., 0, :].astype(jnp.float32),
            interpret=interpret,
        )
        return y.reshape(*x.shape[:-1], d_out)
    xg = x.reshape(*x.shape[:-1], groups, g)
    s = scale[..., 0, :]  # (..., groups, out)
    if q.shape[-2] == d_in:  # odd-group int8 carrier
        qg = q.reshape(*q.shape[:-2], groups, g, d_out)
        y = jnp.einsum("...gi,gio->...go", xg, qg.astype(x.dtype))
        return jnp.sum(y * s.astype(y.dtype), axis=-2)
    pg = q.reshape(*q.shape[:-2], groups, g // 2, d_out)
    lo, hi = _unpack_nibbles(pg)
    y = jnp.einsum("...gi,gio->...go", xg[..., : g // 2], lo.astype(x.dtype))
    y = y + jnp.einsum("...gi,gio->...go", xg[..., g // 2 :], hi.astype(x.dtype))
    return jnp.sum(y * s.astype(y.dtype), axis=-2)


def matmul(x: jnp.ndarray, w) -> jnp.ndarray:
    """``x @ w`` where w may be an int8 or int4 quantized (q, scale) tuple."""
    if isinstance(w, tuple):
        q, scale = w
        # grouped (int4) scheme carries a per-group scale axis the
        # per-output-channel int8 scheme doesn't have
        if scale.ndim == q.ndim + 1:
            return _matmul_int4(x, q, scale)
        # int8 read from HBM; convert fuses into the matmul, scale into its
        # epilogue (output columns), so this is exact w.r.t. x @ (q*scale)
        y = x @ q.astype(x.dtype)
        return y * scale.astype(y.dtype)[..., 0, :]
    return x @ w


def einsum(spec: str, activations: jnp.ndarray, w, out_scale_shape) -> jnp.ndarray:
    """``jnp.einsum(spec, activations, w)`` where w may be a quantized
    (q, scale) tuple. ``out_scale_shape`` reshapes the per-output-channel
    scale for broadcast against the einsum result (the scheme's single owner
    lives here — callers never unpack the tuple themselves)."""
    if isinstance(w, tuple):
        q, scale = w
        y = jnp.einsum(spec, activations, q.astype(activations.dtype))
        return y * scale[..., 0, :].astype(y.dtype).reshape(out_scale_shape)
    return jnp.einsum(spec, activations, w)


def is_quantized(params: dict) -> bool:
    layers = params.get("layers", {})
    return any(isinstance(layers.get(k), tuple) for k in QUANTIZED_LAYER_KEYS)
