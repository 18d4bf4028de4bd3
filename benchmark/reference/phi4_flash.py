"""Plain reference: Phi-4-mini-flash-reasoning in float32 ``jax.numpy``.

Written from the layer equations of the issue that brought the configuration
(ISSUE 30; the published ``phi4flash`` model class's defaults where
config.json is silent, each listed in the configuration file under
``assumed``). ``n`` layers, every one ``x += mixer(LN1(x)); x += W2 (silu(g) *
u)`` with ``[g; u] = W1 LN2(x)``, LayerNorm with weight and bias, no positional
encoding of any kind, logits ``LN_f(x) E^T``:

    0, 2, .., n/2-2   state-space (Mamba-1)
    1, 3, .., n/2-1   differential attention, window (a query at p sees keys p-w+1 .. p)
    n/2               state-space; its scan output M, BEFORE the gate, is the memory
    n/2+1             differential attention, full causal
    n/2+2, n/2+4, ..  gated memory unit: W_out' (silu(W_in' h) * M), M at the same position
    n/2+3, n/2+5, ..  differential cross attention: own queries, K/V of layer n/2+1

State-space: ``[xs; z] = W_in h``; ``u = silu(conv1d(xs) + b_conv)`` (causal,
depthwise, k taps); ``[dt; B; C] = W_x u``; ``delta = softplus(W_dt dt +
b_dt)``; ``A = -exp(A_log)``; ``s_t = exp(delta_t A) s_{t-1} + delta_t B_t
u_t``; ``y_t = C_t . s_t + D u_t``; out ``W_out (y * silu(z))``.

Differential attention: query pair j = heads 2j, 2j+1; K/V pair g = j // 2 =
heads 2g, 2g+1; ``V_g = [v_2g; v_2g+1]``; ``O_t = softmax(q_{2j+t} . k_{2g+t}
/ sqrt(hd)) V_g``; ``lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init``,
``lambda_init = 0.8 - 0.6 exp(-0.3 l)``; ``out_j = RMSNorm(O_0 - lambda O_1)
(1 - lambda_init)``, written back as heads 2j, 2j+1, then ``W_o . + b_o``.

No cache, no ring, no kernels, no batching: one sequence at a time, a full
pass at ``jax.default_matmul_precision("highest")``, a ``lax.scan`` over
positions for the recurrence, and four explicit softmax-times-value products
a query pair (each of the two softmaxes against each half of ``V_g``).

It imports nothing of the program and takes nothing the program made. The
served weights are random; the reference makes its own by restating the recipe
(``assumed.weights`` in the configuration file). If the program's weights ever
differ from this recipe, the comparison fails, which is the point.

Departures from the equations, all of form and none of value:
- the feed-forward's ``W1`` is drawn as its two halves (gate, up), as the
  recipe draws them;
- ``A_log`` is held (d_state, d_inner), the transpose of the equations' (c, n);
- so that a 4096-token request fits one chip, a layer is one jitted call
  (weights widened to float32 a layer at a time) and the attention goes one
  query pair at a time (``lax.map``); nothing is approximated.

``weight_round="int8"`` is the control: the same pass with every matrix
rounded to int8 per output channel, the nearest precision below bfloat16.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

# the recipe's key numbers (32 keys split from PRNGKey(weights_seed)); a leaf
# of the first or second layer of a pair in group g draws from
# fold_in(keys[number], 2 g + half)
KEY_OF = {
    "embed": 0,
    "ssm.w_in": 1, "ssm.conv_w": 2, "ssm.conv_b": 3, "ssm.w_x": 4, "ssm.w_dt": 5,
    "ssm.b_dt": 6, "ssm.w_out": 7,
    "attn.wqkv": 8, "attn.bqkv": 9, "attn.wo": 10, "attn.bo": 11, "attn.lambdas": 12,
    "gmu.w_in": 13, "gmu.w_out": 14,
    "w_gate": 15, "w_up": 16, "w_down": 17,
}
GROUPS = ("window", "middle", "cross")  # pairs of (ssm, attn), (ssm, attn), (gmu, cross attn)
# rounded by the int8 control (per output channel); everything else is small
MATRICES = ("w_in", "w_x", "w_dt", "w_out", "wqkv", "wo", "w_gate", "w_up", "w_down")


def _sizes(c: dict) -> dict:
    d = c["hidden_size"]
    heads, kv_heads = c["num_attention_heads"], c["num_key_value_heads"]
    return dict(
        d=d, ff=c["intermediate_size"], vocab=c["vocab_size"], layers=c["num_hidden_layers"],
        heads=heads, kv_heads=kv_heads, hd=d // heads, window=c["sliding_window"],
        di=c["ssm_expand"] * d, n=c["ssm_d_state"], k=c["ssm_d_conv"], r=c["ssm_dt_rank"],
        eps=float(c["layer_norm_eps"]),
    )


def make_weights(c: dict, weights_seed: int, devices) -> dict:
    """Every array of the recipe, on ``devices[0]``: matrices ``normal *
    fan_in**-0.5`` and linear biases ``normal * 0.02`` rounded to bfloat16,
    the lambda vectors ``normal * 0.1`` (float32), norm weights one and biases
    zero, ``A_log[n, c] = log(n + 1)``, ``D`` one, ``b_dt`` the inverse
    softplus of a log-uniform draw in [1e-3, 1e-1] (those three float32)."""
    z = _sizes(c)
    d, ff, di, n, k, r, hd = z["d"], z["ff"], z["di"], z["n"], z["k"], z["r"], z["hd"]
    qw, kvw = z["heads"] * hd, z["kv_heads"] * hd
    pairs = {"window": z["layers"] // 4, "middle": 1, "cross": z["layers"] // 4 - 1}

    with jax.default_device(devices[0]):
        keys = jax.random.split(jax.random.PRNGKey(weights_seed), 32)

        def draw(key, shape, scale, out=jnp.bfloat16):
            make = jax.jit(
                lambda key: (jax.random.normal(key, shape, dtype=jnp.float32) * scale).astype(out)
            )
            return make(key)

        def layer(group: int, half: int, count: int, kind: str) -> dict:
            def key(leaf):
                return jax.random.fold_in(keys[KEY_OF[leaf]], 2 * group + half)

            lead = (count,)
            out = {
                "norm1": jnp.ones((*lead, d), jnp.bfloat16),
                "norm1_b": jnp.zeros((*lead, d), jnp.bfloat16),
                "norm2": jnp.ones((*lead, d), jnp.bfloat16),
                "norm2_b": jnp.zeros((*lead, d), jnp.bfloat16),
                "w_gate": draw(key("w_gate"), (*lead, d, ff), d**-0.5),
                "w_up": draw(key("w_up"), (*lead, d, ff), d**-0.5),
                "w_down": draw(key("w_down"), (*lead, ff, d), ff**-0.5),
            }
            if kind == "ssm":
                uniform = jax.jit(
                    lambda key: jax.random.uniform(key, (*lead, di), dtype=jnp.float32)
                )(key("ssm.b_dt"))
                dt = jnp.exp(uniform * (math.log(1e-1) - math.log(1e-3)) + math.log(1e-3))
                out |= {
                    "w_in": draw(key("ssm.w_in"), (*lead, d, 2 * di), d**-0.5),
                    "conv_w": draw(key("ssm.conv_w"), (*lead, k, di), k**-0.5),
                    "conv_b": draw(key("ssm.conv_b"), (*lead, di), 0.02),
                    "w_x": draw(key("ssm.w_x"), (*lead, di, r + 2 * n), di**-0.5),
                    "w_dt": draw(key("ssm.w_dt"), (*lead, r, di), r**-0.5),
                    "b_dt": dt + jnp.log(-jnp.expm1(-dt)),
                    "A_log": jnp.broadcast_to(
                        jnp.log(jnp.arange(1, n + 1, dtype=jnp.float32))[:, None], (*lead, n, di)
                    ),
                    "D": jnp.ones((*lead, di), jnp.float32),
                    "w_out": draw(key("ssm.w_out"), (*lead, di, d), di**-0.5),
                }
            elif kind == "gmu":
                out |= {
                    "w_in": draw(key("gmu.w_in"), (*lead, d, di), d**-0.5),
                    "w_out": draw(key("gmu.w_out"), (*lead, di, d), di**-0.5),
                }
            else:  # "attn": q, k, v in one matrix; "cross": q alone
                width = qw if kind == "cross" else qw + 2 * kvw
                out |= {
                    "wqkv": draw(key("attn.wqkv"), (*lead, d, width), d**-0.5),
                    "bqkv": draw(key("attn.bqkv"), (*lead, width), 0.02),
                    "wo": draw(key("attn.wo"), (*lead, qw, d), qw**-0.5),
                    "bo": draw(key("attn.bo"), (*lead, d), 0.02),
                    "lambdas": draw(key("attn.lambdas"), (*lead, 4, hd), 0.1, out=jnp.float32),
                    "subln": jnp.ones((*lead, 2 * hd), jnp.bfloat16),
                }
            return out

        kinds = {"window": ("ssm", "attn"), "middle": ("ssm", "attn"), "cross": ("gmu", "cross")}
        weights = {
            "embed": draw(keys[KEY_OF["embed"]], (z["vocab"], d), d**-0.5),
            "final_norm": jnp.ones((d,), jnp.bfloat16),
            "final_norm_bias": jnp.zeros((d,), jnp.bfloat16),
        }
        for g, name in enumerate(GROUPS):
            weights[name] = tuple(
                layer(g, half, pairs[name], kind) for half, kind in enumerate(kinds[name])
            )
    return weights


def _round_int8(w, axis):
    """Symmetric int8 per output channel, dequantised."""
    scale = jnp.max(jnp.abs(w), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return jnp.clip(jnp.round(w / scale), -127, 127) * scale


def _widen(lp: dict, weight_round: str | None) -> dict:
    """One layer's arrays in float32, its matrices rounded for the control."""
    out = {}
    for name, w in lp.items():
        w = w.astype(jnp.float32)
        if weight_round == "int8" and name in MATRICES:
            w = _round_int8(w, 0)
        out[name] = w
    return out


# Small named pieces of the equations, so that a test can put a named fault in
# one of them and see the tolerance tell it (tests/test_phi4_flash.py).

def _lambda_init(layer_index):
    return 0.8 - 0.6 * jnp.exp(-0.3 * layer_index)


def _pair_heads(j: int, heads: int, kv_heads: int):
    """Query pair j and its K/V pair, by stripes: heads (2j, 2j+1), (2g, 2g+1)."""
    g = j // 2
    return (2 * j, 2 * j + 1), (2 * g, 2 * g + 1)


def _norm_scale(lam_init: float) -> float:
    return 1.0 - lam_init


def _memory_of(y, z):
    """What the state-space layer hands on: its scan output BEFORE the gate."""
    return y


def _shared_layer(layers: int) -> int:
    """The attention layer whose keys and values the cross layers read."""
    return layers // 2 + 1


def _layer_norm(x, weight, bias, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * weight + bias


def _feed_forward(x, lp, eps):
    h = _layer_norm(x, lp["norm2"], lp["norm2_b"], eps)
    return x + (jax.nn.silu(h @ lp["w_gate"]) * (h @ lp["w_up"])) @ lp["w_down"]


@functools.partial(jax.jit, static_argnames=("static", "weight_round"))
def _state_space_layer(x, lp, static, weight_round):
    """Returns (x after mixer and feed-forward, the scan output y before the gate)."""
    di, n, k, r, eps = static
    lp = _widen(lp, weight_round)
    length = x.shape[0]
    h = _layer_norm(x, lp["norm1"], lp["norm1_b"], eps)
    xz = h @ lp["w_in"]
    xs, z = xz[:, :di], xz[:, di:]
    padded = jnp.concatenate([jnp.zeros((k - 1, di), jnp.float32), xs], axis=0)
    # tap j weighs the input k - 1 - j positions back; the last tap the current one
    conv = sum(padded[j : j + length] * lp["conv_w"][j] for j in range(k)) + lp["conv_b"]
    u = jax.nn.silu(conv)
    dbc = u @ lp["w_x"]
    dt, b, c = dbc[:, :r], dbc[:, r : r + n], dbc[:, r + n :]
    delta = jax.nn.softplus(dt @ lp["w_dt"] + lp["b_dt"])
    a = -jnp.exp(lp["A_log"])  # (n, di)

    def step(s, inputs):
        delta_t, u_t, b_t, c_t = inputs
        s = jnp.exp(delta_t[None, :] * a) * s + (delta_t * u_t)[None, :] * b_t[:, None]
        return s, c_t @ s

    _, y = jax.lax.scan(step, jnp.zeros((n, di), jnp.float32), (delta, u, b, c))
    y = y + lp["D"] * u
    x = x + (y * jax.nn.silu(z)) @ lp["w_out"]
    return _feed_forward(x, lp, eps), _memory_of(y, z)


@functools.partial(jax.jit, static_argnames=("eps", "weight_round"))
def _memory_layer(x, memory, lp, eps, weight_round):
    lp = _widen(lp, weight_round)
    h = _layer_norm(x, lp["norm1"], lp["norm1_b"], eps)
    x = x + (jax.nn.silu(h @ lp["w_in"]) * memory) @ lp["w_out"]
    return _feed_forward(x, lp, eps)


def _differential(q, k, v, layer_index, lp, window, hd, eps):
    """q (T, heads, hd), k and v (T, kv_heads, hd) -> (T, heads * hd)."""
    length, heads = q.shape[0], q.shape[1]
    positions = jnp.arange(length)
    seen = positions[:, None] >= positions[None, :]
    if window:
        seen = seen & (positions[:, None] - positions[None, :] < window)
    lam_init = _lambda_init(layer_index)
    lq1, lk1, lq2, lk2 = lp["lambdas"]
    lam = jnp.exp(jnp.sum(lq1 * lk1)) - jnp.exp(jnp.sum(lq2 * lk2)) + lam_init

    def weights_of(query, key):
        scores = (query @ key.T) / math.sqrt(hd)
        return jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)

    def pair(j):
        (qa, qb), (ka, kb) = _pair_heads(j, heads, k.shape[1])
        q1, q2 = q[:, qa], q[:, qb]
        k1, k2 = k[:, ka], k[:, kb]
        v1, v2 = v[:, ka], v[:, kb]
        # four softmax-times-value products: each query's weights against each value head
        o11, o12 = weights_of(q1, k1) @ v1, weights_of(q1, k1) @ v2
        o21, o22 = weights_of(q2, k2) @ v1, weights_of(q2, k2) @ v2
        o_first, o_second = jnp.concatenate([o11, o12], -1), jnp.concatenate([o21, o22], -1)
        diff = o_first - lam * o_second  # (T, 2 hd)
        normed = diff * jax.lax.rsqrt(jnp.mean(diff * diff, axis=-1, keepdims=True) + eps)
        return normed * lp["subln"] * _norm_scale(lam_init)

    out = jax.lax.map(pair, jnp.arange(heads // 2))  # (pairs, T, 2 hd): heads 2j, 2j+1 side by side
    return out.transpose(1, 0, 2).reshape(length, heads * hd)


@functools.partial(jax.jit, static_argnames=("static", "window", "weight_round"))
def _attention_layer(x, lp, static, layer_index, window, weight_round):
    """Self attention; returns (x, this layer's keys, its values)."""
    heads, kv_heads, hd, eps = static
    lp = _widen(lp, weight_round)
    length = x.shape[0]
    h = _layer_norm(x, lp["norm1"], lp["norm1_b"], eps)
    qkv = h @ lp["wqkv"] + lp["bqkv"]
    q = qkv[:, : heads * hd].reshape(length, heads, hd)
    k = qkv[:, heads * hd : (heads + kv_heads) * hd].reshape(length, kv_heads, hd)
    v = qkv[:, (heads + kv_heads) * hd :].reshape(length, kv_heads, hd)
    x = x + _differential(q, k, v, layer_index, lp, window, hd, eps) @ lp["wo"] + lp["bo"]
    return _feed_forward(x, lp, eps), k, v


@functools.partial(jax.jit, static_argnames=("static", "weight_round"))
def _cross_layer(x, k, v, lp, static, layer_index, weight_round):
    """Own queries over another layer's keys and values, full causal."""
    heads, _, hd, eps = static
    lp = _widen(lp, weight_round)
    length = x.shape[0]
    h = _layer_norm(x, lp["norm1"], lp["norm1_b"], eps)
    q = (h @ lp["wqkv"] + lp["bqkv"]).reshape(length, heads, hd)
    x = x + _differential(q, k, v, layer_index, lp, 0, hd, eps) @ lp["wo"] + lp["bo"]
    return _feed_forward(x, lp, eps)


@functools.partial(jax.jit, static_argnames=("eps", "weight_round"))
def _head(x, score_at, weights, eps, weight_round):
    embed = weights["embed"].astype(jnp.float32)
    if weight_round == "int8":
        embed = _round_int8(embed, 1)
    x = _layer_norm(x[score_at], weights["final_norm"].astype(jnp.float32),
                    weights["final_norm_bias"].astype(jnp.float32), eps)
    return x @ embed.T


@functools.partial(jax.jit, static_argnames=("weight_round",))
def _embed(tokens, embed, weight_round):
    embed = embed.astype(jnp.float32)
    if weight_round == "int8":
        embed = _round_int8(embed, 1)
    return embed[tokens]


def logits_at(c: dict, weights: dict, tokens, score_at, weight_round: str | None = None):
    """Float32 logits ``(len(score_at), vocab)`` of one sequence's full pass."""
    z = _sizes(c)
    ssm_static = (z["di"], z["n"], z["k"], z["r"], z["eps"])
    attn_static = (z["heads"], z["kv_heads"], z["hd"], z["eps"])

    def at(stack, i):
        return {name: w[i] for name, w in stack.items()}

    with jax.default_matmul_precision("highest"):
        x = _embed(jnp.asarray(tokens), weights["embed"], weight_round)
        layer = 0
        keys_values = {}  # by the attention layer's index
        ssm_stack, attn_stack = weights["window"]
        for i in range(z["layers"] // 4):
            x, _ = _state_space_layer(x, at(ssm_stack, i), ssm_static, weight_round)
            x, *keys_values[layer + 1] = _attention_layer(
                x, at(attn_stack, i), attn_static, jnp.float32(layer + 1), z["window"], weight_round)
            layer += 2
        ssm_stack, attn_stack = weights["middle"]
        x, memory = _state_space_layer(x, at(ssm_stack, 0), ssm_static, weight_round)
        x, *keys_values[layer + 1] = _attention_layer(
            x, at(attn_stack, 0), attn_static, jnp.float32(layer + 1), 0, weight_round)
        layer += 2
        k, v = keys_values[_shared_layer(z["layers"])]
        gmu_stack, cross_stack = weights["cross"]
        for i in range(z["layers"] // 4 - 1):
            x = _memory_layer(x, memory, at(gmu_stack, i), z["eps"], weight_round)
            x = _cross_layer(x, k, v, at(cross_stack, i), attn_static, jnp.float32(layer + 1),
                             weight_round)
            layer += 2
        return _head(x, jnp.asarray(score_at), weights, z["eps"], weight_round)


def score(c: dict, weights: dict, prompt: list[int], answer: list[int], pad_to: int,
          answer_pad: int, weight_round: str | None = None):
    """Teacher-force ``prompt + answer``; return float32 logits ``(len(answer),
    vocab)`` at the positions that predicted each answer token. The sequence is
    padded to ``pad_to`` (one program a capacity); padding lies after every
    scored position, and nothing here looks ahead."""
    ids = (list(prompt) + list(answer))[:pad_to]
    tokens = np.zeros((pad_to,), dtype=np.int32)
    tokens[: len(ids)] = ids
    at = np.zeros((answer_pad,), dtype=np.int32)
    n = min(len(answer), answer_pad)
    at[:n] = np.arange(len(prompt) - 1, len(prompt) - 1 + n)
    return logits_at(c, weights, tokens, at, weight_round)[:n]
