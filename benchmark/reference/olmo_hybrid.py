"""Plain reference: Olmo-Hybrid's layers in float32 ``jax.numpy``, for the first
stage of a two-stage pipeline.

Written from the model's public ``config.json`` (catalog row ``Olmo-Hybrid-7B``,
``model_type`` ``olmo_hybrid``) and what its keys name: 32 layers whose
``layer_types`` come in periods of three ``linear_attention`` (Gated DeltaNet:
the gated delta rule, the ``linear_*`` keys as ``qwen3_next`` has them) and one
``full_attention``, every layer followed by a dense gated feed-forward, on the
OLMo-2 family's residual path: no norm on a sub-layer's input, an RMSNorm on
its output. With ``N(x; w) = x / sqrt(mean(x^2) + eps) * w`` (plain ``w``) and
layer input ``x``:

    h   = x + N(mixer_i(x); w_a_i)             mixer_i: full attention where
    out = h + N(W_down (silu(W_gate h) * W_up h); w_f_i)    layer_types[i] says so,
                                                            else the delta rule
    logits = W_head N(out_last; w_final)       embedding and head untied

    full attention (H heads of hd = hidden / H, as many K/V heads):
        q = N(W_q x; w_q)    k = N(W_k x; w_k)   over ALL H hd features    v = W_v x
        causal softmax(q k^T / sqrt(hd)) v a head;  out = W_o attended
        no rotary embedding (assumed: rope_parameters.rope_theta is null), no gate

    gated delta rule (n heads, keys d_k, values d_v; one value head a key head):
        [q; k; v; z] = W_qkvz x  every head's q (d_k each), then every k, every v (d_v
                                 each), every z;  [b; a] = W_ba x  every b, then every a
                                 (separate projections side by side)
        [q; k; v] = silu(causal depthwise conv([q; k; v], 4 taps))       no bias
        beta = 2 sigmoid(b)  (linear_allow_neg_eigval; sigmoid(b) without it)
        g = -exp(A_log) softplus(a + dt_bias)
        q, k: x / sqrt(sum x^2 + 1e-6) over d_k; q times d_k^-1/2
        a head, S (d_k, d_v), position by position:
            S <- exp(g_t) S;  d = beta_t (v_t - k_t^T S);  S <- S + k_t (x) d;  o_t = q_t^T S
        y = w o / sqrt(mean o^2 + 1e-6) silu(z) over d_v, out = W_out y

The delta rule is the recurrence itself, one position a scan step from a zero
state: the definition, where the program runs a chunked form, a kernel at decode,
and may start a prompt from a state its prefix cache kept (this file knows nothing
of that: a turn's whole prompt is scored from zero, which is what a restored
state has to reproduce). Nothing is cached, nothing is a kernel. One sequence at a
time through a full causal forward pass at ``jax.default_matmul_precision("highest")``.

**The cut.** ``num_hidden_layers`` is what this chip holds (the first 16 of 32:
stage 0 of 2); the final norm and the head, stage 1's, are kept here so that
logits can be compared. ``layer_types`` is read for its first
``num_hidden_layers`` entries.

**Departures and assumptions** (``assumed`` in the configuration file): no rotary
embedding in the full layers; the linear layers take the same post-norm residual
as the full ones; random weights by the program's recipe restated here (24 keys
split from ``PRNGKey(weights_seed)``, ``KEY_OF`` below; a matrix and the conv taps
``normal * fan_in**-0.5`` rounded to bfloat16; every norm weight one; ``A_log``
uniform in [log 1e-3, log 1e-1] and ``dt_bias`` uniform in [-1, 1], float32: the
published initialisation forgets a token within one position under random
inputs). It imports nothing of the program. Weights stay stored as served
(bfloat16) and are widened a layer's matrices at a time.

``weight_round="int8"`` is the control: the same forward with every matrix
(embedding too) rounded to int8 per output channel, the nearest precision below
bfloat16.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

KEY_OF = {
    "embed": 0, "lm_head": 1, "w_gate": 3, "w_up": 4, "w_down": 5,
    "delta.w_qkvz": 10, "delta.w_ba": 11, "delta.conv_w": 12, "delta.A_log": 13,
    "delta.dt_bias": 14, "delta.w_out": 15,
    "attn.wq": 16, "attn.wk": 17, "attn.wv": 18, "attn.wo": 19,
}
DELTA = ("w_qkvz", "w_ba", "conv_w", "A_log", "dt_bias", "w_out")
ATTENTION = ("wq", "wk", "wv", "wo")
FEED_FORWARD = ("w_gate", "w_up", "w_down")


def full_layers(c: dict) -> tuple[bool, ...]:
    """Which of the layers held here is a full attention."""
    return tuple(kind == "full_attention" for kind in c["layer_types"][: c["num_hidden_layers"]])


def _sizes(c: dict) -> dict:
    full = full_layers(c)
    n, d_k, d_v = c["linear_num_value_heads"], c["linear_key_head_dim"], c["linear_value_head_dim"]
    if c["linear_num_key_heads"] != n:
        raise ValueError("one value head a key head: linear_num_key_heads != linear_num_value_heads")
    h = c["num_attention_heads"]
    return dict(
        d=c["hidden_size"], layers=len(full), full=sum(full), linear=len(full) - sum(full),
        h=h, kh=c["num_key_value_heads"], hd=c.get("head_dim") or c["hidden_size"] // h,
        n=n, d_k=d_k, d_v=d_v, taps=c["linear_conv_kernel_dim"], channels=n * (2 * d_k + d_v),
        value=n * d_v, ff=c["intermediate_size"], vocab=c["vocab_size"])


def _shapes(c: dict) -> dict:
    """name -> (shape, fan_in) of every leaf that is a normal draw in bfloat16."""
    z = _sizes(c)
    d, layers, linear, full = z["d"], z["layers"], z["linear"], z["full"]
    return {
        "embed": ((z["vocab"], d), d),
        "lm_head": ((d, z["vocab"]), d),
        "w_gate": ((layers, d, z["ff"]), d),
        "w_up": ((layers, d, z["ff"]), d),
        "w_down": ((layers, z["ff"], d), z["ff"]),
        "delta.w_qkvz": ((linear, d, z["channels"] + z["value"]), d),
        "delta.w_ba": ((linear, d, 2 * z["n"]), d),
        "delta.conv_w": ((linear, z["taps"], z["channels"]), z["taps"]),
        "delta.w_out": ((linear, z["value"], d), z["value"]),
        "attn.wq": ((full, d, z["h"] * z["hd"]), d),
        "attn.wk": ((full, d, z["kh"] * z["hd"]), d),
        "attn.wv": ((full, d, z["kh"] * z["hd"]), d),
        "attn.wo": ((full, z["h"] * z["hd"], d), z["h"] * z["hd"]),
    }


def make_weights(c: dict, weights_seed: int, devices) -> dict:
    """Every leaf by the recipe, one jitted call a leaf (its float32 draw is
    gone before the next is made), on the first of ``devices``. Norm weights
    (one each) are not stored: the forward states them."""
    keys = jax.random.split(jax.random.PRNGKey(weights_seed), 24)
    z = _sizes(c)
    weights = {}
    with jax.default_device(devices[0]):
        for name, (shape, fan_in) in _shapes(c).items():
            make = jax.jit(lambda key, shape=shape, fan_in=fan_in: (
                jax.random.normal(key, shape, dtype=jnp.float32) * (fan_in ** -0.5)).astype(jnp.bfloat16))
            weights[name] = make(keys[KEY_OF[name]])
        for name, low, high in (("delta.A_log", math.log(1e-3), math.log(1e-1)),
                                ("delta.dt_bias", -1.0, 1.0)):
            u = jax.random.uniform(keys[KEY_OF[name]], (z["linear"], z["n"]), dtype=jnp.float32)
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


def _norm(x, eps):
    """``x / sqrt(mean x^2 + eps) * w``; the recipe's ``w`` is one."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _unit(t):
    """A head's vector at unit length: ``x / sqrt(sum x^2 + 1e-6)``."""
    return t * jax.lax.rsqrt(jnp.sum(t * t, axis=-1, keepdims=True) + 1e-6)


def _key_heads(t):
    """A key head's vector for the value heads it serves: its own one, so nothing
    is repeated (``qwen3_next`` repeats each to two)."""
    return t


def _beta(b, c):
    return jax.nn.sigmoid(b) * (2.0 if c.get("linear_allow_neg_eigval") else 1.0)


def _qk_norm(t, eps):
    """The full attention's q/k norm: over every head's features at once."""
    return _norm(t, eps)


def _positions(q, k, positions):
    """What the full attention does to queries and keys for their positions:
    nothing (assumed: no rotary embedding)."""
    return q, k


def attention(x, p, positions, c, up):
    """The full attention over ``x`` (T, d); ``p``: wq, wk, wv, wo."""
    z, eps = _sizes(c), float(c["rms_norm_eps"])
    length, h, kh, hd = x.shape[0], z["h"], z["kh"], z["hd"]
    q = _qk_norm(x @ up(p["wq"]), eps).reshape(length, h, hd)
    k = _qk_norm(x @ up(p["wk"]), eps).reshape(length, kh, hd)
    v = (x @ up(p["wv"])).reshape(length, kh, hd)
    q, k = _positions(q, k, positions)
    k, v = (jnp.repeat(t, h // kh, axis=1) for t in (k, v))  # one K/V head a head here
    scores = jnp.einsum("qhd,khd->hqk", q, k) * hd ** -0.5
    causal = positions[:, None] >= positions[None, :]
    probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), axis=-1)
    attended = jnp.einsum("hqk,khd->qhd", probs, v)
    return attended.reshape(length, h * hd) @ up(p["wo"])


def delta_rule(x, p, c, up):
    """The gated delta rule over ``x`` (T, d), the state from zero; ``p``:
    w_qkvz, w_ba, conv_w, A_log, dt_bias, w_out."""
    z = _sizes(c)
    length, n, d_k, d_v = x.shape[0], z["n"], z["d_k"], z["d_v"]
    qkvz = x @ up(p["w_qkvz"])
    mixed, gate = qkvz[:, :z["channels"]], qkvz[:, z["channels"]:].reshape(length, n, d_v)
    ba = x @ up(p["w_ba"])
    b, a = ba[:, :n], ba[:, n:]
    taps = z["taps"]
    padded = jnp.concatenate([jnp.zeros((taps - 1, mixed.shape[1]), mixed.dtype), mixed])
    conv_w = p["conv_w"].astype(jnp.float32)  # (taps, channels): tap j weighs position t - (taps - 1) + j
    mixed = jax.nn.silu(sum(padded[j:j + length] * conv_w[j] for j in range(taps)))

    q = _key_heads(_unit(mixed[:, :n * d_k].reshape(length, n, d_k))) * d_k ** -0.5
    k = _key_heads(_unit(mixed[:, n * d_k:2 * n * d_k].reshape(length, n, d_k)))
    v = mixed[:, 2 * n * d_k:].reshape(length, n, d_v)
    beta = _beta(b, c)
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(a + p["dt_bias"])

    def step(state, inputs):
        q_t, k_t, v_t, beta_t, g_t = inputs
        state = jnp.exp(g_t)[:, None, None] * state
        d = beta_t[:, None] * (v_t - jnp.einsum("hk,hkv->hv", k_t, state))
        state = state + k_t[:, :, None] * d[:, None, :]
        return state, jnp.einsum("hk,hkv->hv", q_t, state)

    _, o = jax.lax.scan(step, jnp.zeros((n, d_k, d_v), jnp.float32), (q, k, v, beta, g))
    y = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + 1e-6) * jax.nn.silu(gate)
    return y.reshape(length, n * d_v) @ up(p["w_out"])


def feed_forward(x, p, up):
    return (jax.nn.silu(x @ up(p["w_gate"])) * (x @ up(p["w_up"]))) @ up(p["w_down"])


def _residual(x, sub_layer, eps):
    """A sub-layer on the residual stream: its input as it is, its output normed."""
    return x + _norm(sub_layer(x), eps)


def layers(x, weights, positions, c, up):
    """Every layer held here over ``x`` (T, d)."""
    eps = float(c["rms_norm_eps"])
    full = linear = 0
    for i, is_full in enumerate(full_layers(c)):
        if is_full:
            p = {name: weights[f"attn.{name}"][full] for name in ATTENTION}
            x = _residual(x, lambda u, p=p: attention(u, p, positions, c, up), eps)
            full += 1
        else:
            p = {name: weights[f"delta.{name}"][linear] for name in DELTA}
            x = _residual(x, lambda u, p=p: delta_rule(u, p, c, up), eps)
            linear += 1
        p = {name: weights[name][i] for name in FEED_FORWARD}
        x = _residual(x, lambda u, p=p: feed_forward(u, p, up), eps)
    return x


def _static(c: dict) -> tuple:
    """The configuration's numbers and its layer pattern, hashable."""
    items = {k: v for k, v in c.items() if isinstance(v, (int, float, bool))}
    items["layer_types"] = tuple(c["layer_types"])
    return tuple(sorted(items.items()))


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
    with jax.default_matmul_precision("highest"):
        return _score(weights, jnp.asarray(tokens, jnp.int32), jnp.asarray(at, jnp.int32),
                      _static(c), weight_round)


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
    return logits_at(c, weights, tokens, at, weight_round)[:n]
