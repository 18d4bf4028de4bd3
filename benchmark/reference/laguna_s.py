"""Plain reference: Laguna's layers of more than one kind in float32
``jax.numpy``, for one chip's share of an expert-parallel deployment.

Written from the model's public ``config.json`` (catalog row ``Laguna-S-2.1``).
With ``norm(x; w) = x / sqrt(mean(x^2) + eps) * w`` (the recipe's ``w`` is one)
and layer ``i``'s kinds read off the config's own lists (``layer_types[i]``,
``mlp_layer_types[i]``, ``num_attention_heads_per_layer[i]``):

    h   = x + attention_i(norm(x))           out = h + feed_i(norm(h))

    attention, H query heads, KH key/value heads of hd, u the normed input:
        q = u W_q (H x hd)   k = u W_k (KH x hd)   v = u W_v   g = sigmoid(u W_g) (H)
        q, k rotated by ``rope_parameters[layer_types[i]]``: the first
            hd * partial_rotary_factor features of a head, feature j paired with
            j + half of them; "yarn": frequencies blended between theta^(-2j/r)
            and that over ``factor`` by the ramp between the correction dims of
            beta_fast and beta_slow (floor / ceil), cos and sin times
            attention_factor; "default": theta^(-2j/r), unscaled
        a_h = softmax(q_h k^T / sqrt(hd)) v over the keys at or before the
            query, and for "sliding_attention" no further back than
            sliding_window - 1 positions (the window counts the query's own)
        attention = concat_h(g_h a_h) W_o

    feed "dense":  W_down (silu(W_gate u) * W_up u), width intermediate_size
    feed "sparse": p = softmax(W_r u) over all routed experts, float32; P = the
        top-k; g_e = p_e / sum_P p (norm_topk_prob) * moe_routed_scaling_factor;
        sum_{e in P, e held here} g_e Expert_e(u) + Shared(u), both SwiGLU

    logits = norm(x_last) W_head

Nothing is cached, nothing is a kernel, the window is a mask. One sequence at
a time through a full causal pass at ``jax.default_matmul_precision("highest")``,
the attention a block of queries at a time so that 8192 positions fit.

**The share.** The configuration states which experts this chip holds
(``first_expert`` .. ``first_expert + num_experts`` of ``num_experts_published``)
and its slice of the vocabulary. The router keeps its published width and top-k;
a pick of an expert held on the other chip adds nothing, here as in the program;
the shared expert is computed by every chip for its own tokens. The held experts
are walked one by one, each over the rows that picked it (gathered a block of
rows at a time, every such row computed): grouping, not skipping. Rows past the
sequence's own length are padding, which nothing reads, and route nowhere.

**Departures and assumptions** (``assumed`` in the configuration file): random
weights by the program's recipe restated here (24 keys split from
``PRNGKey(weights_seed)``, ``KEY_OF`` below; a matrix ``normal * fan_in**-0.5``
rounded to bfloat16, the router float32; norm weights one); softmax routing, an
un-gated shared expert, no q/k norm, the head gate from the attention's normed
input without a bias and before ``o_proj``. It imports nothing of the program.
Weights stay stored as served (bfloat16) and are widened a layer's matrices, or
one expert, at a time.

``weight_round="int8"`` is the control: the same pass with every matrix (router
and embedding too) rounded to int8 per output channel, the nearest precision
below bfloat16.
"""

from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

KEY_OF = {
    "embed": 0, "lm_head": 1,
    "full.wq": 2, "full.wk": 3, "full.wv": 4, "full.wo": 5, "full.w_head_gate": 6,
    "window.wq": 7, "window.wk": 8, "window.wv": 9, "window.wo": 10, "window.w_head_gate": 11,
    "dense.w_gate": 12, "dense.w_up": 13, "dense.w_down": 14,
    "sparse.router": 15, "sparse.w_gate": 16, "sparse.w_up": 17, "sparse.w_down": 18,
    "sparse.w_shared_gate": 19, "sparse.w_shared_up": 20, "sparse.w_shared_down": 21,
}
STACK_OF = {"full_attention": "full", "sliding_attention": "window"}
EXPERTS = ("w_gate", "w_up", "w_down")
QUERY_BLOCK = 256  # queries a block of the attention: 72 heads x 256 x 8192 scores are 0.6 GB
ROW_BLOCK = 512    # rows of one expert computed at a time


def _heads(c: dict, kind: str) -> int:
    """Query heads of the layers of ``kind``, off the per-layer list."""
    return next(h for h, k in zip(c["num_attention_heads_per_layer"], c["layer_types"]) if k == kind)


def _shapes(c: dict) -> dict:
    """name -> (shape, fan_in, dtype) of every leaf, stacked over its kind's layers."""
    d, hd, kh = c["hidden_size"], c["head_dim"], c["num_key_value_heads"]
    ff, dff, sf = c["moe_intermediate_size"], c["intermediate_size"], c["shared_expert_intermediate_size"]
    held, width, vocab = c["num_experts"], c["num_experts_published"], c["vocab_size"]
    n_dense = c["mlp_layer_types"].count("dense")
    n_sparse = c["mlp_layer_types"].count("sparse")
    bf, f32 = jnp.bfloat16, jnp.float32
    shapes = {"embed": ((vocab, d), d, bf), "lm_head": ((d, vocab), d, bf)}
    for kind, stack in STACK_OF.items():
        n = c["layer_types"].count(kind)
        h = _heads(c, kind) if n else 0
        shapes |= {
            f"{stack}.wq": ((n, d, h * hd), d, bf), f"{stack}.wk": ((n, d, kh * hd), d, bf),
            f"{stack}.wv": ((n, d, kh * hd), d, bf), f"{stack}.wo": ((n, h * hd, d), h * hd, bf),
            f"{stack}.w_head_gate": ((n, d, h), d, bf),
        }
    return shapes | {
        "dense.w_gate": ((n_dense, d, dff), d, bf), "dense.w_up": ((n_dense, d, dff), d, bf),
        "dense.w_down": ((n_dense, dff, d), dff, bf),
        "sparse.router": ((n_sparse, d, width), d, f32),
        "sparse.w_gate": ((n_sparse, held, d, ff), d, bf),
        "sparse.w_up": ((n_sparse, held, d, ff), d, bf),
        "sparse.w_down": ((n_sparse, held, ff, d), ff, bf),
        "sparse.w_shared_gate": ((n_sparse, d, sf), d, bf),
        "sparse.w_shared_up": ((n_sparse, d, sf), d, bf),
        "sparse.w_shared_down": ((n_sparse, sf, d), sf, bf),
    }


def make_weights(c: dict, weights_seed: int, devices) -> dict:
    """Every leaf by the recipe, one jitted call a leaf (its float32 draw is
    gone before the next is made), on the first of ``devices``. Norm weights
    (one) are not stored: the pass states them."""
    keys = jax.random.split(jax.random.PRNGKey(weights_seed), 24)
    weights = {}
    with jax.default_device(devices[0]):
        for name, (shape, fan_in, dtype) in _shapes(c).items():
            make = jax.jit(lambda key, shape=shape, fan_in=fan_in, dtype=dtype: (
                jax.random.normal(key, shape, dtype=jnp.float32) * (fan_in ** -0.5)).astype(dtype))
            weights[name] = make(keys[KEY_OF[name]])
    return weights


def _round_int8(w, axis):
    """Symmetric int8 per output channel, dequantised."""
    scale = jnp.max(jnp.abs(w), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return jnp.clip(jnp.round(w / scale), -127, 127) * scale


def _widen(weight_round):
    if weight_round == "int8":  # per output channel: the scale spans the input axis
        return lambda w: _round_int8(w.astype(jnp.float32), -2)
    return lambda w: w.astype(jnp.float32)


def _norm(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _head_gate(score):
    return jax.nn.sigmoid(score)


def rope_frequencies(rope: dict, head_dim: int) -> tuple[np.ndarray, float]:
    """(inverse frequencies of the rotated pairs, what cos and sin are scaled by)
    for one of ``rope_parameters``' settings."""
    rotary = int(head_dim * rope.get("partial_rotary_factor", 1))
    theta = float(rope["rope_theta"])
    inv = theta ** (-np.arange(0, rotary, 2, dtype=np.float64) / rotary)
    if rope.get("rope_type", "default") == "default":
        return inv.astype(np.float32), 1.0
    if rope["rope_type"] != "yarn":
        raise ValueError(f"rope_type {rope['rope_type']!r} is not written down here")
    original = float(rope["original_max_position_embeddings"])

    def correction_dim(rotations: float) -> float:
        return rotary * math.log(original / (rotations * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(correction_dim(float(rope["beta_fast"]))), 0)
    high = min(math.ceil(correction_dim(float(rope["beta_slow"]))), rotary - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(rotary // 2, dtype=np.float64) - low) / (high - low), 0.0, 1.0)
    blended = inv / float(rope["factor"]) * ramp + inv * (1.0 - ramp)
    return blended.astype(np.float32), float(rope["attention_factor"])


def _rope(x, positions, inv, scale):
    """x (T, heads, hd): the first ``2 len(inv)`` features turn, feature j paired
    with j + len(inv); the rest pass."""
    half = inv.shape[0]
    angle = positions[:, None].astype(jnp.float32) * jnp.asarray(inv)[None, :]
    cos, sin = (jnp.cos(angle) * scale)[:, None, :], (jnp.sin(angle) * scale)[:, None, :]
    x1, x2, rest = x[..., :half], x[..., half:2 * half], x[..., 2 * half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1)


def window_of(c: dict, kind: str) -> int:
    """Keys a query of a layer of ``kind`` sees at most, its own among them (0: all)."""
    return int(c["sliding_window"]) if kind == "sliding_attention" else 0


def attention(u, p, positions, c, kind, up, gated=True):
    """One attention of ``kind`` over the normed ``u`` (T, d); ``p``: wq, wk, wv,
    wo, w_head_gate. ``gated`` False leaves the head gate out (a test's fault)."""
    length, hd, kh = u.shape[0], c["head_dim"], c["num_key_value_heads"]
    h = p["wq"].shape[-1] // hd
    inv, scale = rope_frequencies(c["rope_parameters"][kind], hd)
    q = _rope((u @ up(p["wq"])).reshape(length, h, hd), positions, inv, scale)
    k = _rope((u @ up(p["wk"])).reshape(length, kh, hd), positions, inv, scale)
    v = (u @ up(p["wv"])).reshape(length, kh, hd)
    q = q.reshape(length, kh, h // kh, hd)  # query head i reads K/V head i // (h / kh)
    window = window_of(c, kind)

    def block(args):
        q_b, pos_b = args  # (qb, kh, g, hd), (qb,)
        scores = jnp.einsum("qkgd,tkd->kgqt", q_b, k) * hd ** -0.5
        back = pos_b[:, None] - positions[None, :]
        seen = back >= 0
        if window:
            seen = seen & (back < window)
        probs = jax.nn.softmax(jnp.where(seen[None, None], scores, -jnp.inf), axis=-1)
        return jnp.einsum("kgqt,tkd->qkgd", probs, v)

    qb = length if length <= QUERY_BLOCK or length % QUERY_BLOCK else QUERY_BLOCK
    attended = jax.lax.map(
        block, (q.reshape(length // qb, qb, kh, h // kh, hd), positions.reshape(length // qb, qb))
    ).reshape(length, h, hd)
    if gated:
        attended = attended * _head_gate(u @ up(p["w_head_gate"]))[..., None]
    return attended.reshape(length, h * hd) @ up(p["wo"])


def dense(u, p, up):
    return (jax.nn.silu(u @ up(p["w_gate"])) * (u @ up(p["w_up"]))) @ up(p["w_down"])


def moe(u, p, c, up, valid=None, first=None, shared=True, scaled=True, layer=None):
    """The expert layer over ``u`` (T, d). ``p``: router (d, width), w_gate /
    w_up / w_down stacked over the experts held here (or, with ``layer``, the
    whole (layers, held, ...) stacks, of which one expert at a time is read: a
    layer's slice of them would be 2.4 GB beside the weights), the shared
    expert's three matrices. ``valid`` (T,) marks the sequence's own rows (None:
    all); ``first`` is the first held expert (default: the configuration's);
    ``shared`` False leaves the shared expert out (the test that adds the shares
    up counts it once), ``scaled`` False the routed scaling factor (a test's
    fault)."""
    length = u.shape[0]
    first = int(c.get("first_expert", 0)) if first is None else first
    probs = jax.nn.softmax(u @ up(p["router"]), axis=-1)
    _, picks = jax.lax.top_k(probs, c["num_experts_per_tok"])
    picked = jnp.zeros_like(probs).at[jnp.arange(length)[:, None], picks].set(1.0)
    if valid is not None:
        picked = picked * valid[:, None]
    gates = probs * picked
    if c.get("norm_topk_prob", True):
        gates = gates / jnp.maximum(jnp.sum(gates, axis=-1, keepdims=True), 1e-30)
    if scaled:
        gates = gates * float(c.get("moe_routed_scaling_factor", 1.0))
    rows_at_once = min(length, ROW_BLOCK)
    u_pad = jnp.concatenate([u, jnp.zeros((1, u.shape[1]), u.dtype)])  # row ``length``: nobody's

    def one(y, e):
        gate = jax.lax.dynamic_slice_in_dim(gates, first + e, 1, axis=1)[:, 0]
        mine = gate > 0
        rows = jnp.nonzero(mine, size=length, fill_value=length)[0]
        rows = jnp.concatenate([rows, jnp.full((rows_at_once,), length, rows.dtype)])
        gate_pad = jnp.concatenate([gate, jnp.zeros((1,), gate.dtype)])
        w_gate, w_up, w_down = (
            up(p[name][e] if layer is None else p[name][layer, e]) for name in EXPERTS)

        def some(state):
            at, y = state
            take = jax.lax.dynamic_slice_in_dim(rows, at, rows_at_once)
            x = u_pad[take]
            out = (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down
            return at + rows_at_once, y.at[take].add(gate_pad[take][:, None] * out, mode="drop")

        _, y = jax.lax.while_loop(lambda s: s[0] < jnp.sum(mine), some, (0, y))
        return y, None

    y, _ = jax.lax.scan(one, jnp.zeros_like(u), jnp.arange(p["w_gate"].shape[-3]))
    if shared:
        y = y + (jax.nn.silu(u @ up(p["w_shared_gate"])) * (u @ up(p["w_shared_up"]))) @ up(p["w_shared_down"])
    return y


def layers(x, weights, positions, c, up, valid=None):
    """Every layer over ``x`` (T, d), each at its index in its kind's stack."""
    eps = float(c["rms_norm_eps"])
    seen = {"full": 0, "window": 0, "dense": 0, "sparse": 0}
    for kind, feed in zip(c["layer_types"], c["mlp_layer_types"]):
        stack = STACK_OF[kind]
        p = {name: weights[f"{stack}.{name}"][seen[stack]]
             for name in ("wq", "wk", "wv", "wo", "w_head_gate")}
        x = x + attention(_norm(x, eps), p, positions, c, kind, up)
        seen[stack] += 1
        u, at = _norm(x, eps), seen[feed]
        if feed == "dense":
            x = x + dense(u, {name: weights[f"dense.{name}"][at] for name in EXPERTS}, up)
        else:  # the experts' stacks go in whole with the layer: one expert is read at a time
            p = {name: weights[f"sparse.{name}"] if name in EXPERTS else weights[f"sparse.{name}"][at]
                 for name in ("router", *EXPERTS, "w_shared_gate", "w_shared_up", "w_shared_down")}
            x = x + moe(u, p, c, up, valid, layer=at)
        seen[feed] += 1
    return x


@functools.partial(jax.jit, static_argnames=("static", "weight_round"))
def _score(weights, tokens, length, score_at, static, weight_round):
    c = json.loads(static)
    up = _widen(weight_round)
    positions = jnp.arange(tokens.shape[0])
    x = weights["embed"][tokens].astype(jnp.float32)
    if weight_round == "int8":  # a row of the embedding is its output channel
        x = _round_int8(x, 1)
    x = layers(x, weights, positions, c, up, valid=positions < length)
    x = _norm(x, float(c["rms_norm_eps"]))[score_at]
    return x @ up(weights["lm_head"])


def logits_at(c: dict, weights: dict, tokens, at, weight_round: str | None = None, length=None):
    """Float32 logits of one sequence's full pass at the positions ``at``;
    ``length``: the tokens that are the sequence's own (default: all)."""
    length = len(tokens) if length is None else length
    with jax.default_matmul_precision("highest"):
        return _score(weights, jnp.asarray(tokens, jnp.int32), jnp.asarray(length, jnp.int32),
                      jnp.asarray(at, jnp.int32), json.dumps(c, sort_keys=True), weight_round)


def score(c: dict, weights: dict, prompt: list[int], answer: list[int], pad_to: int,
          answer_pad: int, weight_round: str | None = None):
    """Teacher-force ``prompt + answer``; return float32 logits ``(len(answer),
    vocab)`` at the positions that predicted each answer token. A prompt that
    does not fit the slot beside its answer is scored on its TAIL, as the server
    serves it (``submit_text`` keeps the last ``capacity - max_tokens`` ids)."""
    prompt = list(prompt)[-(pad_to - len(answer)):]
    ids = prompt + list(answer)
    tokens = np.zeros((pad_to,), dtype=np.int32)
    tokens[: len(ids)] = ids
    at = np.zeros((answer_pad,), dtype=np.int32)
    n = min(len(answer), answer_pad)
    at[:n] = np.arange(len(prompt) - 1, len(prompt) - 1 + n)
    return logits_at(c, weights, tokens, at, weight_round, length=len(ids))[:n]
