"""Plain reference: Qwen3-Next's hybrid layers in float32 ``jax.numpy``, for one
chip's share of an expert-parallel deployment.

Written from the model's public ``config.json`` (catalog row
``Qwen3-Next-80B-A3B-Instruct``) and its description: 48 layers in periods of
``full_attention_interval`` = 4, three linear-attention layers (Gated DeltaNet:
the gated delta rule) and then one gated full attention, every layer followed by
a mixture of 512 experts (top-10, renormalised) plus one shared expert behind a
sigmoid gate. With ``norm(x; w) = x / sqrt(mean(x^2) + eps) * (1 + w)`` and
layer input ``x``:

    h   = x + mixer_i(norm(x; w_in_i))         mixer_i: full attention when
    out = h + moe(norm(h; w_post_i))           (i + 1) % 4 == 0, else the delta rule

    gated full attention (H query heads, KH key/value heads of hd):
        [query; gate]_head = W_q x  (2 hd a head)     k = W_k x     v = W_v x
        q = rope(norm(query; w_qn))   k = rope(norm(k; w_kn))    over hd, a head
        rope: the first hd * partial_rotary_factor features, feature j paired
              with j + half of them, theta from the config; the rest pass
        causal softmax(q k^T / sqrt(hd)) v, H / KH query heads a K/V head
        out = W_o (attended * sigmoid(gate))

    gated delta rule (n_k key heads of d_k, n_v value heads of d_v):
        [q; k; v; z] = W_qkvz x  a KEY head: q d_k, k d_k, v and z of its n_v / n_k
                                 value heads;       [b; a] = W_ba x  likewise
        [q; k; v] = silu(causal depthwise conv([q; k; v], 4 taps))       no bias
        beta = sigmoid(b)     g = -exp(A_log) softplus(a + dt_bias)
        q, k repeated to the value heads (key head j serves 2j, 2j + 1), each
        x / sqrt(sum x^2 + 1e-6) over d_k; q times d_k^-1/2
        a value head, S (d_k, d_v), position by position:
            S <- exp(g_t) S;  d = beta_t (v_t - k_t^T S);  S <- S + k_t (x) d;  o_t = q_t^T S
        y = w o / sqrt(mean o^2 + 1e-6) silu(z) over d_v (plain w), out = W_out y

    moe(u): p = softmax(W_r u) over all routed experts, float32; P = the top-k;
            g_e = p_e / sum_P p  for e in P;
            moe(u) = sum_{e in P, e held here} g_e Expert_e(u)
                     + sigmoid(w_sg . u) Shared(u)
            Expert(u) = W_down (silu(W_gate u) * W_up u)

The delta rule is the recurrence itself, one position a scan step: the
definition, where the program runs a chunked form. Nothing is cached, nothing
is a kernel. One sequence at a time through a full causal forward pass at
``jax.default_matmul_precision("highest")``.

**The share.** The configuration states which experts this chip holds
(``first_expert`` .. ``first_expert + num_experts`` of ``num_experts_published``)
and its slice of the vocabulary. The router keeps its published width and top-k;
a pick of an expert held on the other chip adds nothing, here as in the program
(model-configs guide, section 4); the shared expert is computed by every chip
for its own tokens. Every held expert is evaluated for every token and weighted
by its gate (zero where it was not picked).

**Departures and assumptions** (``assumed`` in the configuration file): random
weights by the program's recipe restated here (24 keys split from
``PRNGKey(weights_seed)``, ``KEY_OF`` below; a matrix, the conv taps and the
shared expert's gate vector ``normal * fan_in**-0.5`` rounded to bfloat16, the
router float32; ``(1 + w)`` norm weights zero and the gated norm's weight one;
``A_log`` uniform in [log 1e-3, log 1e-1] and ``dt_bias`` uniform in [-1, 1],
float32: the published initialisation forgets a token within one position under
random inputs); no multi-token-prediction head (``config.json`` has no key for
it). It imports nothing of the program. Weights stay stored as served (bfloat16)
and are widened a layer's matrices, or one expert, at a time.

``weight_round="int8"`` is the control: the same forward with every matrix
(router, embedding, the shared gate's vector too) rounded to int8 per output
channel, the nearest precision below bfloat16.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

KEY_OF = {
    "embed": 0, "lm_head": 1, "router": 2, "w_gate": 3, "w_up": 4, "w_down": 5,
    "w_shared_gate": 6, "w_shared_up": 7, "w_shared_down": 8, "shared_expert_gate": 9,
    "delta.w_qkvz": 10, "delta.w_ba": 11, "delta.conv_w": 12, "delta.A_log": 13,
    "delta.dt_bias": 14, "delta.w_out": 15,
    "attn.wq": 16, "attn.wk": 17, "attn.wv": 18, "attn.wo": 19,
}
EXPERTS = ("w_gate", "w_up", "w_down")


def _sizes(c: dict) -> dict:
    interval = c["full_attention_interval"]
    layers = c["num_hidden_layers"]
    n_k, n_v = c["linear_num_key_heads"], c["linear_num_value_heads"]
    d_k, d_v = c["linear_key_head_dim"], c["linear_value_head_dim"]
    return dict(
        d=c["hidden_size"], layers=layers, interval=interval, periods=layers // interval,
        linear=layers - layers // interval, h=c["num_attention_heads"],
        kh=c["num_key_value_heads"], hd=c["head_dim"], n_k=n_k, n_v=n_v, d_k=d_k, d_v=d_v,
        taps=c["linear_conv_kernel_dim"], channels=2 * n_k * d_k + n_v * d_v, value=n_v * d_v,
        ff=c["moe_intermediate_size"], shared=c["shared_expert_intermediate_size"],
        held=c["num_experts"], width=c["num_experts_published"], vocab=c["vocab_size"])


def _shapes(c: dict) -> dict:
    """name -> (shape, fan_in, dtype) of every leaf that is a normal draw."""
    z = _sizes(c)
    d, layers, linear, periods = z["d"], z["layers"], z["linear"], z["periods"]
    bf, f32 = jnp.bfloat16, jnp.float32
    return {
        "embed": ((z["vocab"], d), d, bf),
        "lm_head": ((d, z["vocab"]), d, bf),
        "router": ((layers, d, z["width"]), d, f32),
        "w_gate": ((layers, z["held"], d, z["ff"]), d, bf),
        "w_up": ((layers, z["held"], d, z["ff"]), d, bf),
        "w_down": ((layers, z["held"], z["ff"], d), z["ff"], bf),
        "w_shared_gate": ((layers, d, z["shared"]), d, bf),
        "w_shared_up": ((layers, d, z["shared"]), d, bf),
        "w_shared_down": ((layers, z["shared"], d), z["shared"], bf),
        "shared_expert_gate": ((layers, d), d, bf),
        "delta.w_qkvz": ((linear, d, z["channels"] + z["value"]), d, bf),
        "delta.w_ba": ((linear, d, 2 * z["n_v"]), d, bf),
        "delta.conv_w": ((linear, z["taps"], z["channels"]), z["taps"], bf),
        "delta.w_out": ((linear, z["value"], d), z["value"], bf),
        "attn.wq": ((periods, d, 2 * z["h"] * z["hd"]), d, bf),
        "attn.wk": ((periods, d, z["kh"] * z["hd"]), d, bf),
        "attn.wv": ((periods, d, z["kh"] * z["hd"]), d, bf),
        "attn.wo": ((periods, z["h"] * z["hd"], d), z["h"] * z["hd"], bf),
    }


def make_weights(c: dict, weights_seed: int, devices) -> dict:
    """Every leaf by the recipe, one jitted call a leaf (its float32 draw is
    gone before the next is made), on the first of ``devices``. Norm weights
    (zero, and the gated norm's one) are not stored: the forward states them."""
    keys = jax.random.split(jax.random.PRNGKey(weights_seed), 24)
    z = _sizes(c)
    weights = {}
    with jax.default_device(devices[0]):
        for name, (shape, fan_in, dtype) in _shapes(c).items():
            make = jax.jit(lambda key, shape=shape, fan_in=fan_in, dtype=dtype: (
                jax.random.normal(key, shape, dtype=jnp.float32) * (fan_in ** -0.5)).astype(dtype))
            weights[name] = make(keys[KEY_OF[name]])
        for name, low, high in (("delta.A_log", math.log(1e-3), math.log(1e-1)),
                                ("delta.dt_bias", -1.0, 1.0)):
            u = jax.random.uniform(keys[KEY_OF[name]], (z["linear"], z["n_v"]), dtype=jnp.float32)
            weights[name] = low + u * (high - low)
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


def _norm(x, eps, w=0.0):
    """``x / sqrt(mean x^2 + eps) * (1 + w)``; the recipe's ``w`` is zero."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * (1.0 + w)


def _unit(t):
    """A key head's vector at unit length: ``x / sqrt(sum x^2 + 1e-6)``."""
    return t * jax.lax.rsqrt(jnp.sum(t * t, axis=-1, keepdims=True) + 1e-6)


def _head_norm(t, eps):
    """The per-head norm of the full attention's queries and keys."""
    return _norm(t, eps)


def _output_gate(gate):
    return jax.nn.sigmoid(gate)


def _shared_gate(score):
    return jax.nn.sigmoid(score)


def _rope(x, positions, theta, rotary):
    """x (T, heads, hd): the first ``rotary`` features turn, feature j paired
    with j + rotary / 2; the rest pass."""
    half = rotary // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = positions[:, None].astype(jnp.float32) * inv[None, :]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    x1, x2, rest = x[..., :half], x[..., half:rotary], x[..., rotary:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1)


def attention(x, p, positions, c, up):
    """The gated full attention over ``x`` (T, d); ``p``: wq, wk, wv, wo."""
    z, eps = _sizes(c), float(c["rms_norm_eps"])
    length, h, kh, hd = x.shape[0], z["h"], z["kh"], z["hd"]
    rotary = int(hd * c["partial_rotary_factor"])
    theta = float(c["rope_theta"])
    qg = (x @ up(p["wq"])).reshape(length, h, 2 * hd)
    query, gate = qg[..., :hd], qg[..., hd:]
    q = _rope(_head_norm(query, eps), positions, theta, rotary)
    k = _rope(_head_norm((x @ up(p["wk"])).reshape(length, kh, hd), eps), positions, theta, rotary)
    v = (x @ up(p["wv"])).reshape(length, kh, hd)
    k, v = (jnp.repeat(t, h // kh, axis=1) for t in (k, v))  # query head i reads K/V head i // (h / kh)
    scores = jnp.einsum("qhd,khd->hqk", q, k) * hd ** -0.5
    causal = positions[:, None] >= positions[None, :]
    probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), axis=-1)
    attended = jnp.einsum("hqk,khd->qhd", probs, v)
    return (attended * _output_gate(gate)).reshape(length, h * hd) @ up(p["wo"])


def delta_rule(x, p, c, up):
    """The gated delta rule over ``x`` (T, d), the state from zero; ``p``:
    w_qkvz, w_ba, conv_w, A_log, dt_bias, w_out."""
    z = _sizes(c)
    length, n_k, n_v, d_k, d_v = x.shape[0], z["n_k"], z["n_v"], z["d_k"], z["d_v"]
    per = n_v // n_k
    qkvz = (x @ up(p["w_qkvz"])).reshape(length, n_k, 2 * d_k + 2 * per * d_v)
    q, k = qkvz[..., :d_k], qkvz[..., d_k:2 * d_k]
    v = qkvz[..., 2 * d_k:2 * d_k + per * d_v]
    gate = qkvz[..., 2 * d_k + per * d_v:].reshape(length, n_v, d_v)
    ba = (x @ up(p["w_ba"])).reshape(length, n_k, 2 * per)
    b, a = ba[..., :per].reshape(length, n_v), ba[..., per:].reshape(length, n_v)

    mixed = jnp.concatenate([t.reshape(length, -1) for t in (q, k, v)], axis=-1)  # (T, channels)
    taps = z["taps"]
    padded = jnp.concatenate([jnp.zeros((taps - 1, mixed.shape[1]), mixed.dtype), mixed])
    conv_w = p["conv_w"].astype(jnp.float32)  # (taps, channels): tap j weighs position t - (taps - 1) + j
    mixed = jax.nn.silu(sum(padded[j:j + length] * conv_w[j] for j in range(taps)))

    q = jnp.repeat(_unit(mixed[:, :n_k * d_k].reshape(length, n_k, d_k)), per, axis=1) * d_k ** -0.5
    k = jnp.repeat(_unit(mixed[:, n_k * d_k:2 * n_k * d_k].reshape(length, n_k, d_k)), per, axis=1)
    v = mixed[:, 2 * n_k * d_k:].reshape(length, n_v, d_v)
    beta = jax.nn.sigmoid(b)
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(a + p["dt_bias"])

    def step(state, inputs):
        q_t, k_t, v_t, beta_t, g_t = inputs
        state = jnp.exp(g_t)[:, None, None] * state
        d = beta_t[:, None] * (v_t - jnp.einsum("hk,hkv->hv", k_t, state))
        state = state + k_t[:, :, None] * d[:, None, :]
        return state, jnp.einsum("hk,hkv->hv", q_t, state)

    _, o = jax.lax.scan(step, jnp.zeros((n_v, d_k, d_v), jnp.float32), (q, k, v, beta, g))
    y = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + 1e-6) * jax.nn.silu(gate)
    return y.reshape(length, n_v * d_v) @ up(p["w_out"])


def moe(u, p, c, up, first=None, shared=True):
    """The expert layer over ``u`` (T, d). ``p``: router (d, width), w_gate /
    w_up / w_down stacked over the experts held here, the shared expert's three
    matrices and its gate vector. ``first`` is the first held expert (default:
    the configuration's); ``shared`` False leaves the shared expert out (the
    test that adds the shares up counts it once). Returns (T, d)."""
    first = int(c.get("first_expert", 0)) if first is None else first
    probs = jax.nn.softmax(u @ up(p["router"]), axis=-1)
    _, picks = jax.lax.top_k(probs, c["num_experts_per_tok"])
    picked = jnp.zeros_like(probs).at[jnp.arange(u.shape[0])[:, None], picks].set(1.0)
    gates = probs * picked
    if c.get("norm_topk_prob", True):
        gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    experts = tuple(p[name] for name in EXPERTS)

    def one(y, e):
        w_gate, w_up, w_down = (up(w[e]) for w in experts)
        out = (jax.nn.silu(u @ w_gate) * (u @ w_up)) @ w_down
        return y + jax.lax.dynamic_slice_in_dim(gates, first + e, 1, axis=1) * out, None

    y, _ = jax.lax.scan(one, jnp.zeros_like(u), jnp.arange(experts[0].shape[0]))
    if shared:
        out = (jax.nn.silu(u @ up(p["w_shared_gate"])) * (u @ up(p["w_shared_up"]))) @ up(p["w_shared_down"])
        y = y + _shared_gate(u @ up(p["shared_expert_gate"][:, None])) * out
    return y


def layers(x, weights, positions, c, up):
    """Every layer over ``x`` (T, d)."""
    z, eps = _sizes(c), float(c["rms_norm_eps"])
    for i in range(z["layers"]):
        period, within = divmod(i, z["interval"])
        normed = _norm(x, eps)
        if within == z["interval"] - 1:
            p = {name: weights[f"attn.{name}"][period] for name in ("wq", "wk", "wv", "wo")}
            x = x + attention(normed, p, positions, c, up)
        else:
            row = period * (z["interval"] - 1) + within
            p = {name: weights[f"delta.{name}"][row]
                 for name in ("w_qkvz", "w_ba", "conv_w", "A_log", "dt_bias", "w_out")}
            x = x + delta_rule(normed, p, c, up)
        p = {name: weights[name][i] for name in
             ("router", *EXPERTS, "w_shared_gate", "w_shared_up", "w_shared_down", "shared_expert_gate")}
        x = x + moe(_norm(x, eps), p, c, up)
    return x


@functools.partial(jax.jit, static_argnames=("static", "weight_round"))
def _score(weights, tokens, score_at, static, weight_round):
    c = dict(static)
    up = _widen(weight_round)
    positions = jnp.arange(tokens.shape[0])
    x = weights["embed"][tokens].astype(jnp.float32)
    if weight_round == "int8":  # a row of the embedding is its output channel
        x = _round_int8(x, 1)
    x = layers(x, weights, positions, c, up)
    x = _norm(x, float(c["rms_norm_eps"]))[score_at]
    return x @ up(weights["lm_head"])


def logits_at(c: dict, weights: dict, tokens, at, weight_round: str | None = None):
    """Float32 logits of one sequence's full pass at the positions ``at``."""
    static = tuple(sorted((k, v) for k, v in c.items() if isinstance(v, (int, float, bool))))
    with jax.default_matmul_precision("highest"):
        return _score(weights, jnp.asarray(tokens, jnp.int32), jnp.asarray(at, jnp.int32),
                      static, weight_round)


def score(c: dict, weights: dict, prompt: list[int], answer: list[int], pad_to: int,
          answer_pad: int, weight_round: str | None = None):
    """Teacher-force ``prompt + answer``; return float32 logits ``(len(answer),
    vocab)`` at the positions that predicted each answer token. A prompt that
    does not fit the slot beside its answer is scored on its TAIL, as the server
    serves it (``submit_text`` keeps the last ``capacity - max_tokens`` ids): the
    chat template's two role words lie outside the traffic generator's clip, so
    a request drawn at both clips' corner arrives two ids too long."""
    prompt = list(prompt)[-(pad_to - len(answer)):]
    ids = prompt + list(answer)
    tokens = np.zeros((pad_to,), dtype=np.int32)
    tokens[: len(ids)] = ids
    at = np.zeros((answer_pad,), dtype=np.int32)
    n = min(len(answer), answer_pad)
    at[:n] = np.arange(len(prompt) - 1, len(prompt) - 1 + n)
    return logits_at(c, weights, tokens, at, weight_round)[:n]
