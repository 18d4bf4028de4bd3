"""Pipeline parallelism: GPipe microbatch schedule over a ``pp`` mesh axis.

TPU-first pipelining (scaling-book recipe): the stacked (L, ...) layer
parameters are sharded on their leading axis over ``pp`` — each device holds
a contiguous stage of L/P layers — and activations hop stage-to-stage with
``lax.ppermute`` inside one ``shard_map``. The schedule is the classic GPipe
fill/drain loop: with M microbatches and P stages, M + P - 1 ticks, bubble
fraction (P-1)/(M+P-1). Everything is a single compiled program: the tick
loop is a ``lax.fori_loop``, microbatch selection is a dynamic index, and
stage activity is masking (idle stages compute on garbage that is never
collected — the standard static-shape trade).

Embedding/unembedding run replicated outside the pipelined region (cheap at
the scales where pp matters less than the layer stack; a production variant
folds them into the first/last stages). Gradients flow through ppermute and
the final psum, so the same function backpropagates for training.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from prime_tpu.models.config import ModelConfig
from prime_tpu.ops.norms import rms_norm
from prime_tpu.ops.rope import rope_frequencies


def pipeline_param_specs(config: ModelConfig) -> dict:
    """Like sharding.param_specs but stages the layer stack over pp."""
    if config.first_k_dense:
        raise ValueError(
            "pipeline parallelism does not stage DeepSeek dense-prefix "
            "models (first_k_dense > 0): the two stacks would need separate "
            "pp layouts"
        )
    if config.is_moe:
        mlp_spec = {
            "router": P("pp", None, None),
            "w_gate": P("pp", None, None, None),
            "w_up": P("pp", None, None, None),
            "w_down": P("pp", None, None, None),
        }
        if config.moe_bias:  # GPT-OSS biases stage with their projections
            mlp_spec |= {
                "router_bias": P("pp", None),
                "b_gate": P("pp", None, None),
                "b_up": P("pp", None, None),
                "b_down": P("pp", None, None),
            }
        if config.moe_score_bias:  # DeepSeek-V3 balance bias
            mlp_spec |= {"score_bias": P("pp", None)}
        if config.n_shared_experts:  # DeepSeekMoE shared expert (dense MLP)
            mlp_spec |= {
                "w_shared_gate": P("pp", None, None),
                "w_shared_up": P("pp", None, None),
                "w_shared_down": P("pp", None, None),
            }
    else:
        mlp_spec = {
            "w_gate": P("pp", None, None),
            "w_up": P("pp", None, None),
            "w_down": P("pp", None, None),
        }
    if config.mla:
        attn_spec = {
            "wkv_a": P("pp", None, None),
            "kv_a_norm": P("pp", None),
            "wkv_b": P("pp", None, None),
            "wo": P("pp", None, None),
        }
        if config.q_lora_rank is not None:
            attn_spec |= {
                "wq_a": P("pp", None, None),
                "q_a_norm": P("pp", None),
                "wq_b": P("pp", None, None),
            }
        else:
            attn_spec["wq"] = P("pp", None, None)
    else:
        attn_spec = {
            "wq": P("pp", None, None),
            "wk": P("pp", None, None),
            "wv": P("pp", None, None),
            "wo": P("pp", None, None),
        }
    layer_spec = {
        **attn_spec,
        **mlp_spec,
    }
    if config.pre_norms:
        layer_spec |= {"attn_norm": P("pp", None), "mlp_norm": P("pp", None)}
    if config.attn_bias:
        layer_spec |= {"bq": P("pp", None), "bk": P("pp", None), "bv": P("pp", None)}
    if config.attn_out_bias:
        layer_spec |= {"bo": P("pp", None)}
    if config.qk_norm:
        layer_spec |= {"q_norm": P("pp", None), "k_norm": P("pp", None)}
    if config.attn_sinks:
        layer_spec |= {"sinks": P("pp", None)}
    if config.qk_norm_full:
        layer_spec |= {"q_norm_full": P("pp", None), "k_norm_full": P("pp", None)}
    if config.post_norms:
        layer_spec |= {"attn_post_norm": P("pp", None), "mlp_post_norm": P("pp", None)}
    specs = {
        "embed": P(None, None),
        "layers": layer_spec,
        "final_norm": P(None),
    }
    if not config.tie_embeddings:
        specs["lm_head"] = P(None, None)
    return specs


def _stage_forward(
    layers_local, sliding_local, x, positions, rope_tables, rope_tables_local,
    config: ModelConfig,
):
    """Run this device's contiguous stage of layers (scan, no cache). The
    per-layer sliding flags ride the scan exactly like in forward() — they
    were computed GLOBALLY and sharded over pp with the layer stack, so an
    alternating-window schedule stays aligned across stages."""
    from prime_tpu.models.llama import _attention_block, _mlp_block

    def layer_fn(carry, scanned):
        x, aux_sum = carry
        lp, sliding = scanned
        if config.mla:
            from prime_tpu.models.mla import mla_attention_block

            x, _ = mla_attention_block(
                x, lp, positions, rope_tables, config, (), None, None, False, "xla"
            )
        else:
            x, _ = _attention_block(
                x, lp, positions, rope_tables, config, (), None, None, False, "xla",
                sliding=sliding, rope_tables_local=rope_tables_local,
            )
        x, aux = _mlp_block(x, lp, config)
        return (x, aux_sum + aux), None

    # runs inside run_pipeline's shard_map: the zero init must carry the same
    # pp-varying marker the scanned layer params give the aux output
    aux_zero = jax.lax.pcast(jnp.zeros((), jnp.float32), ("pp",), to="varying")
    (x, aux_total), _ = jax.lax.scan(
        layer_fn, (x, aux_zero), (layers_local, sliding_local)
    )
    return x, aux_total


def pipeline_forward(
    params,
    tokens: jnp.ndarray,       # (B, S) with B divisible by n_microbatches
    config: ModelConfig,
    mesh,
    n_microbatches: int,
    return_aux: bool = False,
) -> jnp.ndarray:
    """Pipelined training forward. Returns logits (B, S, V) fp32 (plus the
    microbatch-averaged MoE load-balance aux when ``return_aux``)."""
    stages = mesh.shape["pp"]
    if config.n_layers % stages:
        raise ValueError(f"n_layers={config.n_layers} must divide into pp={stages} stages")
    batch, seq = tokens.shape
    if batch % n_microbatches:
        raise ValueError(f"batch {batch} not divisible by {n_microbatches} microbatches")
    micro = batch // n_microbatches

    x = params["embed"][tokens]                       # (B, S, D) replicated
    if config.scale_embed:
        x = x * jnp.asarray(config.d_model**0.5, dtype=x.dtype)
    x_mb = x.reshape(n_microbatches, micro, seq, x.shape[-1])
    positions = jnp.broadcast_to(jnp.arange(seq, dtype=jnp.int32)[None, :], (micro, seq))
    rope_tables = rope_frequencies(
        # MLA ropes only the shared qk_rope sub-head (mirrors llama.forward)
        config.qk_rope_head_dim if config.mla else config.head_dim,
        max(seq, config.max_seq_len), config.rope_theta,
        # must match forward()'s rope math exactly (incl. the round-4
        # families: non-truncated yarn, LongRoPE, partial rotary; the
        # no-cache path selects LongRoPE factors by seq)
        scale=config.rope_scale, llama3=config.rope_llama3, yarn=config.rope_yarn,
        yarn_truncate=config.rope_yarn_truncate, longrope=config.rope_longrope,
        longrope_select=seq, partial=config.partial_rotary,
    )
    rope_tables_local = (
        rope_frequencies(config.head_dim, max(seq, config.max_seq_len), config.rope_local_theta)
        if config.rope_local_theta is not None
        else None
    )
    from prime_tpu.models.llama import sliding_layer_flags

    sliding_flags = sliding_layer_flags(config)  # (L,), stages over pp below

    layer_specs = pipeline_param_specs(config)["layers"]

    @functools.partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(layer_specs, P("pp"), P()),
        out_specs=(P(), P()),
    )
    def run_pipeline(layers_local, sliding_local, x_mb):
        stage_index = jax.lax.axis_index("pp")
        perm = [(i, i + 1) for i in range(stages - 1)]  # forward shift, no wraparound

        def tick(t, carry):
            state, outs, aux_acc = carry
            mb_in = jnp.clip(t, 0, n_microbatches - 1)
            fresh = jax.lax.dynamic_index_in_dim(x_mb, mb_in, axis=0, keepdims=False)
            x_in = jnp.where(stage_index == 0, fresh, state)
            y, aux = _stage_forward(
                layers_local, sliding_local, x_in, positions, rope_tables,
                rope_tables_local, config,
            )
            # this stage processes microbatch t - stage_index at tick t; aux
            # from bubble ticks (garbage inputs outside that range) must not
            # pollute the MoE load-balance signal
            mb_here = t - stage_index
            real = (mb_here >= 0) & (mb_here < n_microbatches)
            aux_acc = aux_acc + jnp.where(real, aux, 0.0)
            # the last stage finishes microbatch t-(P-1) at tick t
            mb_out = t - (stages - 1)
            collect = (stage_index == stages - 1) & (mb_out >= 0) & (mb_out < n_microbatches)
            slot = jnp.clip(mb_out, 0, n_microbatches - 1)
            updated = jax.lax.dynamic_update_index_in_dim(outs, y, slot, axis=0)
            outs = jnp.where(collect, updated, outs)
            if stages > 1:
                state = jax.lax.ppermute(y, "pp", perm)
            else:
                state = y
            return state, outs, aux_acc

        # mark the zero carries as pp-varying so the loop carry types match
        # the ppermute/masked outputs (jax's manual-axes varying tracking)
        state0 = jax.lax.pcast(jnp.zeros_like(x_mb[0]), ("pp",), to="varying")
        outs0 = jax.lax.pcast(jnp.zeros_like(x_mb), ("pp",), to="varying")
        aux0 = jax.lax.pcast(jnp.zeros((), jnp.float32), ("pp",), to="varying")
        _, outs, aux_acc = jax.lax.fori_loop(
            0, n_microbatches + stages - 1, tick, (state0, outs0, aux0)
        )
        # only the last stage holds real outputs; psum broadcasts them to all
        # (aux sums every stage's layers — the same sum-over-layers forward()
        # returns — averaged over microbatches)
        logits_all = jax.lax.psum(jnp.where(stage_index == stages - 1, outs, 0.0), "pp")
        aux_all = jax.lax.psum(aux_acc, "pp") / n_microbatches
        return logits_all, aux_all

    hidden, aux_total = run_pipeline(params["layers"], sliding_flags, x_mb)  # (M, mb, S, D)
    hidden = hidden.reshape(batch, seq, -1)
    hidden = rms_norm(
        hidden, params["final_norm"], config.rms_eps, plus_one=config.norm_plus_one
    )
    head = params["embed"].T if config.tie_embeddings else params["lm_head"]
    from prime_tpu.ops.attention import _apply_softcap

    logits = _apply_softcap((hidden @ head).astype(jnp.float32), config.final_softcap)
    return (logits, aux_total) if return_aux else logits


def make_pipeline_train_step(
    config: ModelConfig,
    optimizer,
    mesh,
    n_microbatches: int,
    aux_weight: float = 0.01,   # MoE load-balance loss weight (Switch default)
):
    """Jitted pipelined train step (params staged over pp via
    shard_pipeline_params). Same contract as trainer.make_train_step."""
    if config.mla:
        from prime_tpu.models.mla import validate_mla_config

        # the stage forward calls the MLA block directly — the same loud
        # rejection forward() applies must fire here, or pipeline training
        # would silently run different attention math than serving
        validate_mla_config(config)
    from prime_tpu.train.trainer import TrainState, apply_gradients, cross_entropy_loss

    def loss_fn(params, tokens, targets, mask):
        if config.is_moe:
            logits, aux = pipeline_forward(
                params, tokens, config, mesh, n_microbatches, return_aux=True
            )
            return cross_entropy_loss(logits, targets, mask) + aux_weight * aux
        logits = pipeline_forward(params, tokens, config, mesh, n_microbatches)
        return cross_entropy_loss(logits, targets, mask)

    def train_step(state: TrainState, tokens, targets, mask):
        loss, grads = jax.value_and_grad(loss_fn)(state.params, tokens, targets, mask)
        new_state, grad_norm = apply_gradients(state, grads, optimizer)
        return new_state, {"loss": loss, "grad_norm": grad_norm}

    return jax.jit(train_step, donate_argnums=(0,))


def shard_pipeline_params(params, mesh, config: ModelConfig):
    """Place params for the pipeline: layer stack staged over pp, rest
    replicated."""
    from jax.sharding import NamedSharding

    shardings = jax.tree.map(
        lambda spec: NamedSharding(mesh, spec),
        pipeline_param_specs(config),
        is_leaf=lambda x: isinstance(x, P),
    )
    return jax.device_put(params, shardings)
