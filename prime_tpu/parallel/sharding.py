"""Sharding rules for the Llama param/activation pytrees.

Megatron-style tensor parallelism + ZeRO-3 fsdp, expressed as PartitionSpecs
over the (dp, fsdp, tp) mesh from prime_tpu.parallel.mesh. XLA inserts the
collectives (all-gather for fsdp params, psum for tp partials) — nothing here
issues communication explicitly.

Layout choices (scaling-book recipe):
- attention: wq/wk/wv shard the *head* output dim on tp, wo shards its input
  dim on tp → one psum per attention block;
- mlp: w_gate/w_up shard d_ff on tp, w_down shards d_ff on tp → one psum;
- fsdp shards the other (d_model / vocab) dim of every large matrix;
- norms are replicated (tiny);
- batch is sharded over (dp, fsdp) jointly — fsdp is also a data axis.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from prime_tpu.models.config import ModelConfig


def param_specs(config: ModelConfig) -> dict[str, Any]:
    if config.is_moe:
        # experts ride the ep axis; within an expert the same megatron layout
        mlp_specs = {
            "router": P(None, None, None),  # tiny + fp32: replicate
            "w_gate": P(None, "ep", "fsdp", "tp"),
            "w_up": P(None, "ep", "fsdp", "tp"),
            "w_down": P(None, "ep", "tp", "fsdp"),
        }
        if config.moe_bias:  # GPT-OSS: biases land with their projections
            mlp_specs |= {
                "router_bias": P(None, None),
                "b_gate": P(None, "ep", "tp"),
                "b_up": P(None, "ep", "tp"),
                "b_down": P(None, "ep", "fsdp"),
            }
        if config.moe_score_bias:
            mlp_specs["score_bias"] = P(None, None)  # tiny fp32: replicate
        if config.n_shared_experts:
            # the shared expert is a dense MLP: megatron layout, no ep axis
            mlp_specs |= {
                "w_shared_gate": P(None, "fsdp", "tp"),
                "w_shared_up": P(None, "fsdp", "tp"),
                "w_shared_down": P(None, "tp", "fsdp"),
            }
    else:
        mlp_specs = {
            "w_gate": P(None, "fsdp", "tp"),
            "w_up": P(None, "fsdp", "tp"),
            "w_down": P(None, "tp", "fsdp"),
        }
    attn_bias_specs: dict[str, Any] = {}
    if config.attn_bias:
        # bias vectors live on the projection output dim — same tp split as
        # their matrices' output columns
        attn_bias_specs = {"bq": P(None, "tp"), "bk": P(None, "tp"), "bv": P(None, "tp")}
    if config.attn_out_bias:
        attn_bias_specs["bo"] = P(None, "fsdp")  # d_model dim, like wo's output
    if config.qk_norm:
        # (L, head_dim) weights shared across heads: replicate
        attn_bias_specs |= {"q_norm": P(None, None), "k_norm": P(None, None)}
    if config.attn_sinks:
        # (L, H) per-head logits: the head axis rides tp like the q heads
        # they normalize (each device needs only its own heads' sinks)
        attn_bias_specs["sinks"] = P(None, "tp")
    if config.qk_norm_full:
        # (L, H*hd) on the projection output dim — same tp split as the
        # matrices' output columns so the norm weight lands with its slice
        attn_bias_specs |= {"q_norm_full": P(None, "tp"), "k_norm_full": P(None, "tp")}
    if config.post_norms:
        attn_bias_specs |= {
            "attn_post_norm": P(None, None),
            "mlp_post_norm": P(None, None),
        }
    pre_norm_specs = (
        {"attn_norm": P(None, None), "mlp_norm": P(None, None)}
        if config.pre_norms
        else {}
    )
    if config.mla:
        # MLA: query heads and wkv_b's per-head output columns ride tp
        # (h-major flat layout splits whole heads when h % tp == 0); the
        # shared latent projections are head-free and ride fsdp only
        attn_specs: dict[str, Any] = {
            "wkv_a": P(None, "fsdp", None),
            "kv_a_norm": P(None, None),
            "wkv_b": P(None, None, "tp"),
            "wo": P(None, "tp", "fsdp"),
        }
        if config.q_lora_rank is not None:
            attn_specs |= {
                "wq_a": P(None, "fsdp", None),
                "q_a_norm": P(None, None),
                "wq_b": P(None, None, "tp"),
            }
        else:
            attn_specs["wq"] = P(None, "fsdp", "tp")
    else:
        attn_specs = {
            "wq": P(None, "fsdp", "tp"),
            "wk": P(None, "fsdp", "tp"),
            "wv": P(None, "fsdp", "tp"),
            "wo": P(None, "tp", "fsdp"),
        }
    specs: dict[str, Any] = {
        "embed": P("tp", "fsdp"),              # (V, D) vocab on tp, d_model on fsdp
        "layers": {
            **attn_specs,
            **pre_norm_specs,
            **attn_bias_specs,
            **mlp_specs,
        },
        "final_norm": P(None),
    }
    if config.first_k_dense:
        # DeepSeek dense-prefix stack: same attention/norm layout, dense MLP
        specs["dense_layers"] = {
            **attn_specs,
            **pre_norm_specs,
            **attn_bias_specs,
            "w_gate": P(None, "fsdp", "tp"),
            "w_up": P(None, "fsdp", "tp"),
            "w_down": P(None, "tp", "fsdp"),
        }
    if not config.tie_embeddings:
        specs["lm_head"] = P("fsdp", "tp")
    return specs


def batch_spec() -> P:
    return P(("dp", "fsdp"), None)


def cp_batch_spec() -> P:
    """Context-parallel training batches: sequence sharded over sp (ring
    attention rotates the KV blocks; everything elementwise stays local)."""
    return P(("dp", "fsdp"), "sp")


def ring_qkv_axes(mesh, kv_heads: int):
    """(batch_axis, head_axis) for ring attention on ``mesh`` — the ONE
    owner of the axis-name policy (model code must not re-hardcode it).
    Batch rides the data axes; heads ride tp when present (per-head math
    shards cleanly under megatron layout). A tp axis that can't divide the
    kv heads is an error rather than silent replication of every head's
    attention on every tp device."""
    batch = tuple(a for a in ("dp", "fsdp") if a in mesh.shape) or None
    tp = mesh.shape.get("tp", 1)
    if tp > 1 and kv_heads % tp:
        raise ValueError(
            f"ring attention: tp={tp} must divide n_kv_heads={kv_heads}"
        )
    return batch, ("tp" if tp > 1 else None)


def lengths_spec() -> P:
    return P(("dp", "fsdp"))


def cache_spec() -> P:
    """KV cache (L, B, KH, hd, C): batch on the data axes, kv-heads on tp.

    Pinning this matters for serving: without a constraint XLA may replicate
    the zeros-initialised cache, which for an 8B model at long context is the
    difference between fitting v5e HBM and OOM.
    """
    return P(None, ("dp", "fsdp"), "tp", None, None)


def cache_spec_for(config, sp: bool = False) -> P:
    """The cache spec a model's KV cache layout admits: MLA caches have ONE
    kv 'head' (the shared latent), so the head axis must stay replicated —
    putting tp there would demand 1 % tp == 0. Non-MLA picks the standard
    (sp_)cache_spec. Callers still prune against their mesh."""
    base = sp_cache_spec() if sp else cache_spec()
    if getattr(config, "mla", False):
        return P(base[0], base[1], None, *base[3:])
    return base


def serving_cache_spec(config, mesh) -> P:
    """THE serving KV-cache spec for ``mesh``: ``cache_spec_for`` (MLA keeps
    its single-latent head axis replicated; an sp axis shards the slot
    dimension for long-context serving) pruned to the axes the mesh actually
    has. One owner for the derivation the engine, serve_model, and the eval
    runner all need — a change to the MLA/sp rules lands in every consumer."""
    has_sp = mesh.shape.get("sp", 1) > 1
    return prune_spec(cache_spec_for(config, sp=has_sp), mesh)


def sp_cache_spec() -> P:
    """KV cache (L, B, KH, hd, C) with the SLOT axis sharded over sp: a
    long-context cache larger than one chip's HBM spreads across the
    slice. Pass as ``generate(..., cache_spec=sp_cache_spec())`` under a
    mesh with an sp axis — GSPMD inserts the slot-axis collectives for
    the decode reads/writes (the hand-optimized per-step combine is
    long_context.sp_decode_attention)."""
    return P(None, ("dp", "fsdp"), "tp", None, "sp")


def logits_spec() -> P:
    return P(("dp", "fsdp"), None, "tp")


def prune_spec(spec: P, mesh) -> P:
    """Drop axis names the mesh doesn't have (e.g. 'ep' on a (dp,fsdp,tp)
    serving mesh): those dims fall back to replicated instead of erroring."""
    axes = set(mesh.axis_names)

    def keep(element):
        if element is None:
            return None
        if isinstance(element, tuple):
            kept = tuple(a for a in element if a in axes)
            return kept if len(kept) > 1 else (kept[0] if kept else None)
        return element if element in axes else None

    return P(*(keep(element) for element in spec))


def param_shardings(mesh, config: ModelConfig):
    return jax.tree.map(
        lambda spec: NamedSharding(mesh, prune_spec(spec, mesh)),
        param_specs(config),
        is_leaf=lambda x: isinstance(x, P),
    )


def shard_params(params, mesh, config: ModelConfig):
    """Place a param pytree onto the mesh (device_put with NamedShardings)."""
    return jax.device_put(params, param_shardings(mesh, config))


def init_params_sharded(rng, config: ModelConfig, mesh, dtype=jnp.bfloat16):
    """Random-init params created directly in their shardings: the init runs
    as one jitted program with ``out_shardings``, so each device generates
    only its own shards and no device ever holds the whole model (eager
    ``init_params`` + ``shard_params`` materialises every leaf, and its fp32
    transients, on device 0 first)."""
    from prime_tpu.models.llama import init_params

    return jax.jit(
        init_params, static_argnums=(1, 2), out_shardings=param_shardings(mesh, config)
    )(rng, config, dtype)


def shard_batch(batch, mesh, spec: P | None = None):
    """Place a (B, S) batch: data axes on B by default; pass
    ``cp_batch_spec()`` to also shard S over sp (context parallelism).
    Unknown axes prune to replicated so one spec serves any mesh."""
    return jax.device_put(batch, NamedSharding(mesh, prune_spec(spec or batch_spec(), mesh)))
