"""Mixture-of-experts MLP: a capacity-einsum layer for training and a
dropless sorted grouped-matmul layer for inference.

TPU-first MoE (GShard/Switch lineage): no scatters, no ragged shapes — tokens
are dispatched to experts through dense one-hot einsums so the whole block is
three MXU matmuls per expert plus two dispatch einsums, and GSPMD shards the
expert dimension over an ``ep`` mesh axis (the dispatch einsum's token
contraction becomes the all-to-all, inserted by XLA, riding ICI).

Capacity: each expert processes at most C = ceil(k * T / E * capacity_factor)
tokens; overflow tokens are dropped (their combine weight is zero, the
residual stream carries them unchanged) — the standard TPU trade for static
shapes. The router also returns the Switch load-balancing auxiliary loss.
That is ``moe_mlp``, the TRAINING path.

Inference (``moe_infer``, whatever runs under a KV cache) drops nothing: the
reference model computes every token-expert pair, so a capacity drop there is
left-out mathematics. Pairs are sorted by expert and the expert products run
as grouped matmuls over the sorted rows (megablox ``gmm`` on TPU, which skips
row tiles no group owns, so work follows the pairs routed here and not
tokens x experts, at a tile that holds the whole contraction, so each touched
expert's weights are read once; ``jax.lax.ragged_dot`` elsewhere). The layer
is told which experts it holds: it routes over the router's full width and
computes only its own experts' part, plus the zero-compute experts'
``gate * input``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def expert_capacity(tokens: int, n_experts: int, k: int, capacity_factor: float) -> int:
    capacity = int(tokens * k * capacity_factor / n_experts)
    # round up to a multiple of 8 for clean sublane tiling; min 8
    return max(8, ((capacity + 7) // 8) * 8)


def _scores(
    router_logits: jnp.ndarray,  # (T, E) fp32
    score_func: str,
    select_bias: jnp.ndarray | None,
    n_groups: int,
    topk_groups: int,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(probs, selection), both (T, E): the scores the gate values come from,
    and what the experts are CHOSEN by (scores + the selection-only bias, the
    groups that node-limited routing leaves out zeroed). Shared by the
    training path's ``top_k_routing`` and inference's ``select_experts``."""
    tokens, n_experts = router_logits.shape
    if score_func == "sigmoid":
        probs = jax.nn.sigmoid(router_logits)
    else:
        probs = jax.nn.softmax(router_logits, axis=-1)  # (T, E)
    selection = probs if select_bias is None else probs + select_bias.astype(probs.dtype)

    if n_groups > 1:
        # DeepSeek-V3 node-limited routing: rank expert GROUPS by the sum of
        # each group's top-2 biased scores, keep topk_groups, and zero the
        # rest out of selection — HF masks to 0.0 (not -inf), reproduced
        # exactly so group-edge tie behavior matches torch.topk
        group_sel = selection.reshape(tokens, n_groups, n_experts // n_groups)
        group_scores = jnp.sum(jax.lax.top_k(group_sel, 2)[0], axis=-1)  # (T, G)
        kept = jax.lax.top_k(group_scores, topk_groups)[1]               # (T, kept)
        group_mask = jnp.sum(
            jax.nn.one_hot(kept, n_groups, dtype=selection.dtype), axis=1
        )  # (T, G)
        expanded = jnp.repeat(group_mask, n_experts // n_groups, axis=-1)
        selection = jnp.where(expanded > 0, selection, 0.0)
    return probs, selection


def top_k_routing(
    router_logits: jnp.ndarray,  # (T, E) fp32
    k: int,
    capacity: int,
    valid: jnp.ndarray | None = None,  # (T,) 1.0 for real tokens
    norm_topk: bool = True,
    score_func: str = "softmax",       # "softmax" | "sigmoid" (DeepSeek-V3)
    select_bias: jnp.ndarray | None = None,  # (E,) selection-only bias
    routed_scale: float = 1.0,         # DeepSeek routed_scaling_factor
    n_groups: int = 1,                 # V3 node-limited routing: expert groups
    topk_groups: int = 1,              # ...of which this many stay selectable
):
    """Returns (dispatch (T, E, C), combine (T, E, C), aux_loss scalar).

    dispatch is a one-hot routing tensor; combine carries the router
    probability of each token's chosen experts at its capacity slot —
    renormalized over the chosen k when ``norm_topk`` (Mixtral and
    Qwen3-MoE's norm_topk_prob=True), raw softmax mass otherwise
    (norm_topk_prob=False checkpoints). ``valid`` masks padding tokens out
    of routing entirely — they take no capacity slot and contribute nothing
    to the aux loss statistics.

    DeepSeek-V3 routing: ``score_func='sigmoid'`` scores each expert
    independently; ``select_bias`` (the aux-loss-free balancing bias) shifts
    WHICH experts are chosen but never the gate values; ``routed_scale``
    multiplies the final combine weights.
    """
    tokens, n_experts = router_logits.shape
    probs, selection = _scores(router_logits, score_func, select_bias, n_groups, topk_groups)

    # iterative top-k (k is 1 or 2 in practice; unrolled, fully static)
    expert_masks = []
    gate_values = []
    masked = selection
    for _ in range(k):
        choice = jnp.argmax(masked, axis=-1)                       # (T,)
        one_hot = jax.nn.one_hot(choice, n_experts, dtype=probs.dtype)
        if valid is not None:
            one_hot = one_hot * valid[:, None]
        expert_masks.append(one_hot)
        gate_values.append(jnp.sum(probs * one_hot, axis=-1))      # (T,)
        # exclude by -inf, NOT by zeroing: a selection bias can drive every
        # non-chosen score negative, where a zeroed winner would stay the
        # argmax and be picked twice
        masked = jnp.where(one_hot > 0, -jnp.inf, masked)

    gate_stack = jnp.stack(gate_values, axis=-1)                   # (T, k)
    if norm_topk:  # chosen gates sum to 1 per token (Mixtral / DeepSeek-V3)
        gate_stack = gate_stack / jnp.maximum(
            jnp.sum(gate_stack, axis=-1, keepdims=True), 1e-9
        )
    if routed_scale != 1.0:
        gate_stack = gate_stack * routed_scale

    # capacity positions: for each expert, tokens are served in order; a
    # token's slot is its cumulative index among tokens routed to that expert
    dispatch = jnp.zeros((tokens, n_experts, capacity), dtype=probs.dtype)
    combine = jnp.zeros((tokens, n_experts, capacity), dtype=probs.dtype)
    for choice_index in range(k):
        mask = expert_masks[choice_index]                          # (T, E)
        # position within the expert, counting earlier-priority choices too
        prior = sum(expert_masks[:choice_index]) if choice_index else 0.0
        position = jnp.cumsum(mask, axis=0) - 1 + (
            jnp.sum(prior, axis=0, keepdims=True) if choice_index else 0.0
        )
        in_capacity = (position < capacity) & (mask > 0)
        slot = jax.nn.one_hot(position.astype(jnp.int32), capacity, dtype=probs.dtype)
        routed = jnp.where(in_capacity[..., None], slot * mask[..., None], 0.0)
        dispatch = dispatch + routed
        combine = combine + routed * gate_stack[:, choice_index][:, None, None]

    # Switch aux loss: E * Σ_e (token fraction to e) * (mean router prob of e)
    # — sigmoid scores don't sum to 1 per token, so normalize them for the
    # balance statistic (DeepSeek's seq-aux formulation does the same)
    denom = jnp.sum(valid) if valid is not None else float(tokens)
    denom = jnp.maximum(denom, 1.0)
    token_fraction = jnp.sum(expert_masks[0], axis=0) / denom
    stat_probs = (
        probs / jnp.maximum(jnp.sum(probs, axis=-1, keepdims=True), 1e-9)
        if score_func == "sigmoid"
        else probs
    )
    if valid is not None:
        mean_prob = jnp.sum(stat_probs * valid[:, None], axis=0) / denom
    else:
        mean_prob = jnp.mean(stat_probs, axis=0)
    aux_loss = n_experts * jnp.sum(token_fraction * mean_prob)
    return dispatch, combine, aux_loss


MOE_GROUP_SIZE = 1024  # routing group: bounds dispatch memory to O(T * g)


def moe_mlp(
    x: jnp.ndarray,              # (B, S, D)
    router_w: jnp.ndarray,       # (D, E)
    w_gate: jnp.ndarray,         # (E, D, F)
    w_up: jnp.ndarray,           # (E, D, F)
    w_down: jnp.ndarray,         # (E, F, D)
    k: int,
    capacity_factor: float,
    group_size: int = MOE_GROUP_SIZE,
    norm_topk: bool = True,
    router_b: jnp.ndarray | None = None,  # (E,) router bias (GPT-OSS)
    b_gate: jnp.ndarray | None = None,    # (E, F) expert projection biases
    b_up: jnp.ndarray | None = None,      # (E, F)
    b_down: jnp.ndarray | None = None,    # (E, D)
    glu_clamp: float = 0.0,               # GPT-OSS clamped GLU (limit 7.0)
    glu_alpha: float = 1.702,             # sigmoid temperature for the clamped GLU
    score_func: str = "softmax",          # DeepSeek-V3: "sigmoid"
    select_bias: jnp.ndarray | None = None,  # (E,) selection-only balance bias
    routed_scale: float = 1.0,            # DeepSeek routed_scaling_factor
    route_groups: int = 1,                # V3 node-limited group routing
    route_topk_groups: int = 1,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Sparse MoE feed-forward. Returns (output (B, S, D), aux_loss).

    Tokens are routed in fixed-size GROUPS (GShard style): capacity is
    per-group, so the (g, E, C) dispatch tensors stay O(T·g·k·cf) total
    instead of O(T²·k·cf) — without grouping a 32k-token Mixtral batch would
    need ~11 GB of routing tensors per layer. Trailing padding inside the
    last group is masked out of routing entirely (takes no capacity).
    """
    batch, seq, d_model = x.shape
    tokens = batch * seq
    n_experts = router_w.shape[-1]
    x_flat = x.reshape(tokens, d_model)

    group = min(group_size, tokens)
    n_groups = -(-tokens // group)
    padded = n_groups * group
    pad = padded - tokens
    if pad:
        x_flat = jnp.concatenate([x_flat, jnp.zeros((pad, d_model), x.dtype)])
    valid = (jnp.arange(padded) < tokens).astype(jnp.float32).reshape(n_groups, group)

    x_groups = x_flat.reshape(n_groups, group, d_model)
    router_logits = jnp.einsum(
        "gtd,de->gte", x_groups.astype(jnp.float32), router_w.astype(jnp.float32)
    )
    if router_b is not None:
        router_logits = router_logits + router_b.astype(jnp.float32)
    capacity = expert_capacity(group, n_experts, k, capacity_factor)
    dispatch, combine, aux_loss = jax.vmap(
        lambda logits, v: top_k_routing(
            logits, k, capacity, valid=v, norm_topk=norm_topk,
            score_func=score_func, select_bias=select_bias,
            routed_scale=routed_scale, n_groups=route_groups,
            topk_groups=route_topk_groups,
        )
    )(router_logits, valid)
    dispatch = dispatch.astype(x.dtype)   # (g, group, E, C)
    combine = combine.astype(x.dtype)

    from prime_tpu.models.quantize import einsum as q_einsum

    def expert_einsum(spec: str, activations: jnp.ndarray, weight, out_dim: int) -> jnp.ndarray:
        # int8 (q, scale) pairs dequant via the scheme's single owner
        return q_einsum(spec, activations, weight, (1, n_experts, 1, out_dim))

    # dispatch: (g,t,E,C)·(g,t,D) -> (g,E,C,D); under an ep-sharded expert dim
    # GSPMD turns the token contraction into the all-to-all over ICI
    expert_in = jnp.einsum("gtec,gtd->gecd", dispatch, x_groups)
    ff = w_gate[0].shape[-1] if isinstance(w_gate, tuple) else w_gate.shape[-1]
    gate = expert_einsum("gecd,edf->gecf", expert_in, w_gate, ff)
    up = expert_einsum("gecd,edf->gecf", expert_in, w_up, ff)
    if b_gate is not None:  # biases broadcast over the capacity slot axis;
        gate = gate + b_gate[None, :, None, :].astype(gate.dtype)
    if b_up is not None:
        up = up + b_up[None, :, None, :].astype(up.dtype)
    if glu_clamp:
        # GPT-OSS clamped GLU: gate capped above, up capped both ways, a
        # temperature inside the sigmoid, and a +1 on the linear branch —
        # ff = (up + 1) * gate * sigmoid(alpha * gate). Phantom capacity
        # slots produce nonzero activations here (bias + the +1), but their
        # combine weights are zero so nothing reaches the output.
        gate = jnp.clip(gate, max=glu_clamp)
        up = jnp.clip(up, min=-glu_clamp, max=glu_clamp)
        hidden = (up + 1.0) * (gate * jax.nn.sigmoid(glu_alpha * gate))
    else:
        hidden = jax.nn.silu(gate) * up
    expert_out = expert_einsum("gecf,efd->gecd", hidden, w_down, d_model)
    if b_down is not None:
        expert_out = expert_out + b_down[None, :, None, :].astype(expert_out.dtype)
    y = jnp.einsum("gtec,gecd->gtd", combine, expert_out)
    y = y.reshape(padded, d_model)[:tokens]
    return y.reshape(batch, seq, d_model), jnp.mean(aux_loss)


# ---- inference: dropless, sorted, grouped ----

MOE_ROW_TILE = 32  # rows a grouped-matmul tile holds (the kernel's tm)
# the most one sorted row buffer of moe_infer may hold: a token group's gathered
# input is ``rows x d_model``, and its gate / up / hidden / output buffers are
# each that or narrower, all linear in tokens (the training einsum's dispatch,
# MOE_GROUP_SIZE's, is quadratic)
MOE_ROW_BUFFER_BYTES = 256 * 2**20
# what the two buffers of a grouped product's weight tile may take of VMEM. The
# kernel takes no limit of its own, so the default scoped 16 MiB decides, and the
# row tile, the output tile and the accumulator lie beside the weights (compiled
# for the v5e at every served shape: tests/test_aot_lowering.py)
GMM_WEIGHT_VMEM_BYTES = 12 * 2**20
# what moe_infer counts, in the order of its stats vector: the token-expert
# pairs by where they went, the held experts that had a token, the rows the
# grouped products computed, the (layer x token-group) calls counted, and the
# times the three grouped products fetched an expert's matrix (``weight_reads``:
# 3 x experts_touched where each product's contraction is one tile)
MOE_STATS = (
    "local", "zero", "elsewhere", "experts_touched", "rows_computed", "calls", "weight_reads",
)


def select_experts(
    router_logits: jnp.ndarray,  # (T, E) fp32
    k: int,
    norm_topk: bool = True,
    score_func: str = "softmax",
    select_bias: jnp.ndarray | None = None,
    routed_scale: float = 1.0,
    n_groups: int = 1,
    topk_groups: int = 1,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """``top_k_routing``'s choice of experts without its capacity tensors:
    returns (experts (T, k) int32, gates (T, k) fp32). Selection is by
    score + ``select_bias``; the gates are the UNBIASED scores of the chosen
    experts, renormalised when ``norm_topk`` and multiplied by
    ``routed_scale``. Ties go to the lower index, as the unrolled argmax's."""
    probs, selection = _scores(router_logits, score_func, select_bias, n_groups, topk_groups)
    _, experts = jax.lax.top_k(selection, k)
    gates = jnp.take_along_axis(probs, experts, axis=-1)
    if norm_topk:
        gates = gates / jnp.maximum(jnp.sum(gates, axis=-1, keepdims=True), 1e-9)
    if routed_scale != 1.0:
        gates = gates * routed_scale
    return experts.astype(jnp.int32), gates


def _kernel_path() -> tuple[bool, bool]:
    """(use the megablox kernel, in interpret mode): the same rule as the
    other bare pallas calls: a TPU (or interpret mode), and no SPMD mesh."""
    from prime_tpu.models.quantize import _mesh_context_active
    from prime_tpu.ops.attention import _pallas_interpret

    if _mesh_context_active():
        return False, False
    interpret = _pallas_interpret()
    return interpret or jax.default_backend() == "tpu", interpret


def grouped_matmul_tiling(k: int, n: int, itemsize: int) -> tuple[int, int, int]:
    """The kernel's (tm, tk, tn) for weights (G, k, n). The products are bound by
    reading each touched expert's weights, and the kernel's weight block is
    ``(group, k_i, n_i)`` on a grid ``(n tiles, row-tile visits, k tiles)``: with
    the contraction in ONE tile a group's consecutive visits keep their block
    and the copy is skipped, so an expert crosses HBM once however many row
    tiles its group spans or straddles; with more, every visit fetches the
    expert again. So ``tk = k`` wherever two buffers of a ``(k, tn)`` tile fit
    ``GMM_WEIGHT_VMEM_BYTES``, and ``tn`` is the widest multiple of 128 dividing
    ``n`` that fits; only a contraction too long for the narrowest tile is
    walked in tiles."""
    tile = GMM_WEIGHT_VMEM_BYTES // 2 // itemsize  # elements of one weight tile
    widths = [w for w in range(128, n + 1, 128) if n % w == 0] or [n]
    fits = [w for w in widths if k * w <= tile]
    if fits:
        return MOE_ROW_TILE, k, fits[-1]
    return MOE_ROW_TILE, max(128, tile // widths[0] // 128 * 128), widths[0]


def grouped_matmul(
    rows: jnp.ndarray,         # (M, K) sorted by group; M a multiple of MOE_ROW_TILE
    weights: jnp.ndarray,      # (G, K, N), or a layer stack (L, G, K, N) with ``layer``
    group_sizes: jnp.ndarray,  # (G,) int32, sum <= M
    layer: jnp.ndarray | None = None,  # () which layer of a stacked ``weights``
) -> jnp.ndarray:
    """rows[group g's span] @ weights[g] for every group, (M, N) in rows'
    dtype. Rows past the groups' total are not computed (their content is
    unspecified; the caller masks them). A layer stack goes to the kernel
    whole, as L x G groups of which only ``layer``'s have rows: the kernel
    reads its layer's experts in place, where a one-layer slice would be
    copied into a buffer of its own first."""
    use_kernel, interpret = _kernel_path()
    if not use_kernel:
        if layer is not None:
            weights = weights[layer]
        return jax.lax.ragged_dot(rows, weights, group_sizes)
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    if layer is not None:
        layers, held = weights.shape[:2]
        weights = weights.reshape(layers * held, *weights.shape[2:])
        group_sizes = jax.lax.dynamic_update_slice(
            jnp.zeros((layers * held,), jnp.int32), group_sizes, (layer * held,)
        )

    tiling = grouped_matmul_tiling(*weights.shape[1:], weights.dtype.itemsize)
    return gmm(
        rows, weights, group_sizes, preferred_element_type=rows.dtype,
        tiling=tiling, interpret=interpret,
    )


def _expert_rows(rows, expert_of_row, w_gate, w_up, w_down, group_sizes, layer,
                 b_gate, b_up, b_down, glu_clamp, glu_alpha):
    """The held experts' SwiGLU over sorted rows (M, D) -> (M, D)."""
    gate = grouped_matmul(rows, w_gate, group_sizes, layer)
    up = grouped_matmul(rows, w_up, group_sizes, layer)
    if b_gate is not None:
        gate = gate + b_gate[expert_of_row].astype(gate.dtype)
    if b_up is not None:
        up = up + b_up[expert_of_row].astype(up.dtype)
    if glu_clamp:  # GPT-OSS clamped GLU (see moe_mlp)
        gate = jnp.clip(gate, max=glu_clamp)
        up = jnp.clip(up, min=-glu_clamp, max=glu_clamp)
        hidden = (up + 1.0) * (gate * jax.nn.sigmoid(glu_alpha * gate))
    else:
        hidden = jax.nn.silu(gate) * up
    out = grouped_matmul(hidden, w_down, group_sizes, layer)
    if b_down is not None:
        out = out + b_down[expert_of_row].astype(out.dtype)
    return out


def tile_aligned_groups(tokens: int, k: int, router_width: int) -> bool:
    """Whether ``moe_infer`` lays each group's rows from a multiple of
    ``MOE_ROW_TILE``, from the shapes alone: where, under even routing, a token
    group gives an expert ``tokens x k / router_width`` rows, half a tile or
    more. A group then takes ``ceil(rows / tile)`` visits of the kernel where a
    group laid wherever the one before ended overlaps ``1 + (rows - 1) / tile``
    on average; the price is a row buffer that holds a tile less a row more for
    every held expert. A decode step's groups of one to three rows seldom
    straddle a tile and its buffer would grow severalfold, so it stays dense."""
    return 2 * tokens * k >= router_width * MOE_ROW_TILE


def row_budget(tokens: int, k: int, held: int, router_width: int) -> int:
    """Rows of ``moe_infer``'s sorted buffer for a group of ``tokens``: the most
    pairs the held experts can get, in whole row tiles, and in the tile-aligned
    layout a tile less a row more for every group that can be non-empty."""
    pairs = tokens * min(k, held)
    rows = -(-pairs // MOE_ROW_TILE) * MOE_ROW_TILE
    if tile_aligned_groups(tokens, k, router_width):
        rows += min(held, pairs) * (MOE_ROW_TILE - 1) // MOE_ROW_TILE * MOE_ROW_TILE
    return rows


def infer_group_size(rows_per_token: int, d_model: int, itemsize: int) -> int:
    """Tokens ``moe_infer`` takes through as one group: the largest power of
    two whose sorted rows (``rows_per_token`` a token, ``d_model`` wide) stay
    inside ``MOE_ROW_BUFFER_BYTES`` (top-10 of 2048 in bfloat16: 4096 tokens;
    top-12 of 6144: 1024)."""
    tokens = MOE_ROW_BUFFER_BYTES // (rows_per_token * d_model * itemsize)
    return max(MOE_ROW_TILE, 2 ** (tokens.bit_length() - 1))


def moe_infer(
    x: jnp.ndarray,              # (B, S, D)
    router_w: jnp.ndarray,       # (D, n_routed + n_zero): the router's FULL width
    w_gate: jnp.ndarray,         # (held, D, F): the experts held here only
    w_up: jnp.ndarray,           # (held, D, F)          (or layer stacks (L, held, ...)
    w_down: jnp.ndarray,         # (held, F, D)           with ``layer``)
    k: int,
    layer: jnp.ndarray | None = None,  # () the layer of stacked expert weights
    first_expert: int = 0,       # the held range is [first_expert, first_expert + held)
    n_zero: int = 0,             # trailing router outputs that are identity experts
    token_mask: jnp.ndarray | None = None,  # (B, S) bool: False rows route nowhere
    group_size: int | None = None,  # tokens a group; None: ``infer_group_size``
    norm_topk: bool = True,
    router_b: jnp.ndarray | None = None,
    b_gate: jnp.ndarray | None = None,
    b_up: jnp.ndarray | None = None,
    b_down: jnp.ndarray | None = None,
    glu_clamp: float = 0.0,
    glu_alpha: float = 1.702,
    score_func: str = "softmax",
    select_bias: jnp.ndarray | None = None,
    routed_scale: float = 1.0,
    route_groups: int = 1,
    route_topk_groups: int = 1,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """The expert layer for inference. Returns (y (B, S, D), stats int32 in
    ``MOE_STATS`` order).

    For every token: the sum over its k picks that fall in the held range of
    ``gate * Expert(x)``, plus ``gate * x`` for its picks among the ``n_zero``
    zero-compute experts (every chip of a deployment computes these for its
    own tokens). Picks of experts held elsewhere add nothing here. DROPLESS:
    every pair in the held range is computed whatever the load: the pairs
    are sorted by expert into ``tokens * min(k, held)`` rows, which holds the
    worst case, and the grouped matmul computes only the row tiles a group
    owns, so the work follows the pairs (padded to ``MOE_ROW_TILE``), not
    the row budget. Where ``tile_aligned_groups`` says so every group starts
    on a row tile, and the budget holds a tile less a row more for each held
    expert. Tokens go through in groups of ``group_size`` so that a
    long prefill's row buffers stay bounded, and every group reads the touched
    experts' weights once: by default a group is as long as
    ``MOE_ROW_BUFFER_BYTES`` allows (``infer_group_size``), so that a chunk of
    a prompt is one group and one read wherever its rows fit."""
    batch, seq, d_model = x.shape
    tokens = batch * seq
    held = w_gate.shape[0 if layer is None else 1]
    n_routed = router_w.shape[-1] - n_zero
    x_flat = x.reshape(tokens, d_model)
    mask = (
        jnp.ones((tokens,), bool) if token_mask is None else token_mask.reshape(tokens)
    )
    per_token = min(k, held)
    if group_size is None:
        group_size = infer_group_size(per_token, d_model, x.dtype.itemsize)
    group = min(group_size, tokens)
    n_groups = -(-tokens // group)
    pad = n_groups * group - tokens
    if pad:
        x_flat = jnp.concatenate([x_flat, jnp.zeros((pad, d_model), x.dtype)])
        mask = jnp.concatenate([mask, jnp.zeros((pad,), bool)])
    aligned = tile_aligned_groups(group, k, router_w.shape[-1])
    rows_m = row_budget(group, k, held, router_w.shape[-1])
    # the products whose contraction is one tile fetch a touched expert once,
    # the others once a row-tile visit (``grouped_matmul_tiling``)
    d_ff = w_gate.shape[-1]
    once = sum(
        grouped_matmul_tiling(a, b, w_gate.dtype.itemsize)[1] == a
        for a, b in ((d_model, d_ff), (d_model, d_ff), (d_ff, d_model))
    )

    def one_group(xg, mg):
        # float32 all the way: the TPU's default rounds a float32 matmul's
        # operands to bfloat16, and a router rounded so picks other experts
        logits = jnp.dot(
            xg.astype(jnp.float32), router_w.astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST,
        )
        if router_b is not None:
            logits = logits + router_b.astype(jnp.float32)
        experts, gates = select_experts(
            logits, k, norm_topk=norm_topk, score_func=score_func,
            select_bias=select_bias, routed_scale=routed_scale,
            n_groups=route_groups, topk_groups=route_topk_groups,
        )
        live = mg[:, None]
        local = live & (experts >= first_expert) & (experts < first_expert + held)
        zero = live & (experts >= n_routed)
        # zero-compute experts: gate * input, no weights
        y = (jnp.sum(jnp.where(zero, gates, 0.0), axis=-1)[:, None] * xg).astype(x.dtype)
        # sort the pairs by held expert; pairs that are not local sort last
        key = jnp.where(local, experts - first_expert, held).reshape(-1)
        group_sizes = jnp.sum(
            key[:, None] == jnp.arange(held)[None, :], axis=0, dtype=jnp.int32
        )
        spans = group_sizes
        if aligned:
            # each group's rows start on a row tile: spare rows sorted in at
            # every group's end fill its last tile, and the kernel is given the
            # sizes rounded up to whole tiles, so a group of s rows is
            # ceil(s / tile) visits and none of them shared with a neighbour
            spare = (-group_sizes) % MOE_ROW_TILE
            spans = group_sizes + spare
            filler = jnp.where(
                jnp.arange(MOE_ROW_TILE - 1)[None, :] < spare[:, None],
                jnp.arange(held)[:, None], held,
            )
            key = jnp.concatenate([key, filler.reshape(-1)])
        order = jnp.argsort(key, stable=True)
        short = max(0, rows_m - key.size)  # the row budget is a whole tile
        expert_of_row = jnp.minimum(
            jnp.pad(key[order], (0, short), constant_values=held)[:rows_m], held - 1
        )
        token_of_row = order // k
        if aligned:  # a spare row carries the group's last token: computed, never picked
            token_of_row = jnp.minimum(token_of_row, group - 1)
        token_of_row = jnp.pad(token_of_row, (0, short))[:rows_m]
        row_of_pair = jnp.argsort(order)[: group * k]
        out = _expert_rows(
            xg[token_of_row], expert_of_row, w_gate, w_up, w_down,
            spans, layer, b_gate, b_up, b_down, glu_clamp, glu_alpha,
        )
        # back to (token, pick) order by each pair's row; a pair that is not
        # local has no row (or an uncomputed one) and is masked
        picked = out[jnp.minimum(row_of_pair, rows_m - 1)].reshape(group, k, d_model)
        weight = jnp.where(local, gates, 0.0).astype(jnp.float32)
        y = y + jnp.sum(
            jnp.where(local[..., None], picked.astype(jnp.float32), 0.0) * weight[..., None],
            axis=1,
        ).astype(x.dtype)
        # what ran, as counts: the pairs by where they went, the held experts
        # with a token, the rows the grouped products computed (tiles a
        # non-empty group overlaps x MOE_ROW_TILE), and the expert matrices
        # the three products fetched
        starts = jnp.cumsum(spans) - spans
        ends = starts + group_sizes
        visits = jnp.sum(jnp.where(
            group_sizes > 0,
            -(-ends // MOE_ROW_TILE) - starts // MOE_ROW_TILE, 0,
        ))
        n_local, n_zero_picks = jnp.sum(local), jnp.sum(zero)
        touched = jnp.sum(group_sizes > 0)
        stats = jnp.stack([
            n_local, n_zero_picks, jnp.sum(live) * k - n_local - n_zero_picks,
            touched, visits * MOE_ROW_TILE, 1, once * touched + (3 - once) * visits,
        ]).astype(jnp.int32)
        return y, stats

    if n_groups == 1:
        y, stats = one_group(x_flat, mask)
    else:
        y, stats = jax.lax.map(
            lambda args: one_group(*args),
            (x_flat.reshape(n_groups, group, d_model), mask.reshape(n_groups, group)),
        )
        y, stats = y.reshape(n_groups * group, d_model), jnp.sum(stats, axis=0)
    return y[:tokens].reshape(batch, seq, d_model), stats
