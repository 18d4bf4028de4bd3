"""Plain reference: the Qwen3 dense decoder in float32 ``jax.numpy``.

Written from the published description (Qwen3 technical report and the
``Qwen3ForCausalLM`` config): pre-norm decoder blocks, grouped-query attention
with a per-head RMSNorm on queries and keys before the rotary embedding
(half-split convention, theta from the config), SwiGLU feed-forward, a final
RMSNorm, and an output head tied to the embedding where the config says so. No
cache, no kernels, no batching: one sequence at a time through a full causal
forward pass at ``jax.default_matmul_precision("highest")``.

It imports nothing of the program and takes nothing the program made. The
served weights are random; the reference makes its own by restating the recipe
(``assumed.weights`` in the configuration file): 17 keys split from
``PRNGKey(weights_seed)``, each matrix ``normal * fan_in**-0.5`` rounded to
bfloat16, norm weights one. Weights stay bfloat16, as served, and are upcast a
layer at a time inside the scan. If the program's weights ever differ from this
recipe, the comparison fails, which is the point.

``weight_round="int8"`` is the control: the same forward with every matrix
rounded to int8 per output channel, the nearest precision below bfloat16.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

KEY_OF = {"embed": 0, "wq": 1, "wk": 2, "wv": 3, "wo": 4, "w_gate": 5, "w_up": 6,
          "w_down": 7, "lm_head": 8}


def _shapes(c: dict) -> dict:
    d, hd, layers = c["hidden_size"], c["head_dim"], c["num_hidden_layers"]
    h, kh, ff, vocab = (c["num_attention_heads"], c["num_key_value_heads"],
                        c["intermediate_size"], c["vocab_size"])
    shapes = {
        "embed": ((vocab, d), d), "wq": ((layers, d, h * hd), d), "wk": ((layers, d, kh * hd), d),
        "wv": ((layers, d, kh * hd), d), "wo": ((layers, h * hd, d), h * hd),
        "w_gate": ((layers, d, ff), d), "w_up": ((layers, d, ff), d),
        "w_down": ((layers, ff, d), ff),
    }
    if not c["tie_word_embeddings"]:
        shapes["lm_head"] = ((d, vocab), d)
    return shapes


def _shardings(c: dict, devices):
    if len(devices) == 1:
        return None
    mesh = Mesh(np.array(devices), ("x",))
    spec = {name: NamedSharding(mesh, P("x", None) if name == "embed"
                                else P(*([None] * (len(shape) - 1)), "x"))
            for name, (shape, _) in _shapes(c).items()}
    return spec


def make_weights(c: dict, weights_seed: int, devices) -> dict:
    """All matrices in one jitted call, bfloat16, on ``devices``."""
    shapes = _shapes(c)

    def init(key):
        keys = jax.random.split(key, 17)
        return {name: (jax.random.normal(keys[KEY_OF[name]], shape, dtype=jnp.float32)
                       * (fan_in ** -0.5)).astype(jnp.bfloat16)
                for name, (shape, fan_in) in shapes.items()}

    shardings = _shardings(c, devices)
    if shardings is None:
        with jax.default_device(devices[0]):
            return jax.jit(init)(jax.random.PRNGKey(weights_seed))
    return jax.jit(init, out_shardings=shardings)(jax.random.PRNGKey(weights_seed))


def _round_int8(w, axis):
    """Symmetric int8 per output channel, dequantised."""
    scale = jnp.max(jnp.abs(w), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return jnp.clip(jnp.round(w / scale), -127, 127) * scale


def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _rope(x, positions, theta):
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = positions[:, None].astype(jnp.float32) * inv[None, :]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


@functools.partial(jax.jit, static_argnames=("static", "weight_round"))
def _score(weights, tokens, score_at, static, weight_round):
    (hd, h, kh, eps, theta, tied, qk_norm) = static
    up = (lambda w, axis: _round_int8(w.astype(jnp.float32), axis)) if weight_round == "int8" \
        else (lambda w, axis: w.astype(jnp.float32))
    length = tokens.shape[0]
    positions = jnp.arange(length)
    causal = positions[:, None] >= positions[None, :]
    embed = up(weights["embed"], 1)
    x = embed[tokens]
    layer_names = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")

    def layer(x, lp):
        lp = {name: up(w, 0) for name, w in lp.items()}
        hidden = _rms(x, eps)
        q = (hidden @ lp["wq"]).reshape(length, h, hd)
        k = (hidden @ lp["wk"]).reshape(length, kh, hd)
        v = (hidden @ lp["wv"]).reshape(length, kh, hd)
        if qk_norm:
            q, k = _rms(q, eps), _rms(k, eps)
        q, k = _rope(q, positions, theta), _rope(k, positions, theta)
        k, v = jnp.repeat(k, h // kh, axis=1), jnp.repeat(v, h // kh, axis=1)
        scores = jnp.einsum("qhd,khd->hqk", q, k) * (hd ** -0.5)
        scores = jnp.where(causal[None], scores, -jnp.inf)
        attended = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)
        x = x + attended.reshape(length, h * hd) @ lp["wo"]
        hidden = _rms(x, eps)
        x = x + (jax.nn.silu(hidden @ lp["w_gate"]) * (hidden @ lp["w_up"])) @ lp["w_down"]
        return x, None

    x, _ = jax.lax.scan(layer, x, {name: weights[name] for name in layer_names})
    x = _rms(x, eps)[score_at]
    head = embed.T if tied else up(weights["lm_head"], 0)
    return x @ head


def score(c: dict, weights: dict, prompt: list[int], answer: list[int], pad_to: int,
          answer_pad: int, weight_round: str | None = None):
    """Teacher-force ``prompt + answer``; return float32 logits ``(len(answer),
    vocab)`` at the positions that predicted each answer token."""
    ids = (list(prompt) + list(answer))[:pad_to]
    tokens = np.zeros((pad_to,), dtype=np.int32)
    tokens[: len(ids)] = ids
    at = np.zeros((answer_pad,), dtype=np.int32)
    n = min(len(answer), answer_pad)
    at[:n] = np.arange(len(prompt) - 1, len(prompt) - 1 + n)
    static = (c["head_dim"], c["num_attention_heads"], c["num_key_value_heads"],
              float(c["rms_norm_eps"]), float(c["rope_theta"]), bool(c["tie_word_embeddings"]),
              bool(c.get("qk_norm", True)))
    with jax.default_matmul_precision("highest"):
        logits = _score(weights, jnp.asarray(tokens), jnp.asarray(at), static, weight_round)
    return logits[:n]
