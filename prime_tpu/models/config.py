"""Model architecture configs (Llama-3 family presets).

Frozen + hashable so a config can ride as a static jit argument.
"""

from __future__ import annotations

from dataclasses import dataclass, replace


@dataclass(frozen=True)
class ModelConfig:
    name: str
    vocab_size: int
    d_model: int
    n_layers: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    max_seq_len: int = 8192
    rope_theta: float = 500000.0
    rms_eps: float = 1e-5
    tie_embeddings: bool = False
    # decoupled per-head width (Qwen3/Gemma-style); None derives from d_model
    head_dim_override: int | None = None
    # q/k/v projection biases (Qwen2 family)
    attn_bias: bool = False
    # output-projection bias too (Llama-arch checkpoints with attention_bias;
    # Qwen2 biases only q/k/v)
    attn_out_bias: bool = False
    # per-head RMSNorm on q/k before rope (Qwen3 family)
    qk_norm: bool = False
    # full-width RMSNorm on the flat q/k projections before the head reshape
    # (OLMo-2: the rms statistic spans all heads jointly)
    qk_norm_full: bool = False
    # --- Gemma-family architecture knobs ---
    act: str = "silu"                 # MLP activation: "silu" | "gelu_tanh"
    norm_plus_one: bool = False       # RMSNorm scales by (1 + w)
    post_norms: bool = False          # norms on block outputs (Gemma2/3, OLMo-2)
    # input norms before each sublayer (every family EXCEPT OLMo-2, which is
    # post-norm only: sublayer output normed before the residual add)
    pre_norms: bool = True
    scale_embed: bool = False         # hidden *= sqrt(d_model) after embedding
    attn_softcap: float = 0.0         # tanh softcap on attention scores
    final_softcap: float = 0.0        # tanh softcap on output logits
    query_scale: float | None = None  # sm_scale = query_scale**-0.5 (else head_dim)
    sliding_window: int = 0           # window size for the sliding layers
    # which layers slide when sliding_window > 0: "even" (Gemma2 alternation,
    # even-index layers slide) | "uniform" (every layer slides, Mistral-style)
    # | "N:1" (Gemma3-style period: N sliding layers then 1 global, e.g.
    # "5:1"). Explicit so a config wanting a different pattern fails loudly
    # instead of silently inheriting the Gemma2 alternation.
    sliding_pattern: str = "even"
    # Gemma3: sliding (local) layers rope with their own base frequency;
    # None = all layers share rope_theta
    rope_local_theta: float | None = None
    # linear RoPE position scaling on the global-layer table (Gemma3 4b+
    # long-context stretch: factor 8)
    rope_scale: float = 1.0
    # Llama 3.1+ frequency-dependent rope scaling: (factor, low_freq_factor,
    # high_freq_factor, original_max_position). Mutually exclusive with
    # rope_scale; tuple-typed so the config stays hashable for jit
    rope_llama3: tuple[float, float, float, float] | None = None
    # YaRN NTK-by-parts scaling: (factor, beta_fast, beta_slow,
    # original_max_position, attention_factor) — attention_factor resolved at
    # load (incl. mscale variants) so model code just scales the tables
    rope_yarn: tuple[float, float, float, float, float] | None = None
    # GPT-OSS ships yarn with truncate=false: correction bounds stay
    # fractional instead of floor/ceil, shifting the interpolation ramp
    rope_yarn_truncate: bool = True
    # Phi-3.5 LongRoPE: (short_factors, long_factors, original_max_position,
    # attention_factor) — per-dim learned frequency rescales; the long set
    # applies when the table covers more than the pretrained range
    rope_longrope: tuple[tuple[float, ...], tuple[float, ...], float, float] | None = None
    # Phi-2-style partial rotary: only the first head_dim*partial_rotary
    # features of each head rotate, the tail passes through position-free
    partial_rotary: float = 1.0
    # GPT-OSS attention sinks: one learned logit per head joins every
    # softmax normalization (no value contribution) — a drain for attention
    # mass that otherwise piles onto early tokens
    attn_sinks: bool = False
    # mixture-of-experts (0 experts = dense MLP; Mixtral-style top-k routing)
    n_experts: int = 0
    experts_per_token: int = 2
    capacity_factor: float = 1.25
    # renormalize the chosen top-k gates to sum 1 (Mixtral, Qwen3-MoE w/
    # norm_topk_prob=True); False keeps raw softmax mass
    norm_topk: bool = True
    # GPT-OSS: biases on the router and every expert projection
    moe_bias: bool = False
    # GPT-OSS clamped GLU: ff = (up+1) * gate * sigmoid(1.702*gate) with
    # gate clamped above and up clamped both ways at this limit (0 = plain
    # silu gating)
    moe_glu_clamp: float = 0.0
    # --- DeepSeekMoE knobs ---
    # always-on shared expert(s): a dense silu MLP of width
    # n_shared_experts * d_ff added to every token's routed output
    n_shared_experts: int = 0
    # routing score function: "softmax" (Mixtral/Qwen) | "sigmoid"
    # (DeepSeek-V3 independent per-expert scores)
    moe_score_func: str = "softmax"
    # learned selection-only bias (V3 aux-loss-free balancing: shifts WHICH
    # experts are picked, never the gate values)
    moe_score_bias: bool = False
    # multiplier on the final routed combine weights (routed_scaling_factor)
    routed_scaling_factor: float = 1.0
    # V3 node-limited routing: experts partition into moe_n_groups groups,
    # only the moe_topk_groups best (by top-2 score sum) stay selectable
    moe_n_groups: int = 1
    moe_topk_groups: int = 1
    # DeepSeek first_k_dense_replace: the first k layers run a DENSE MLP of
    # width dense_ff (HF intermediate_size) instead of the MoE — the forward
    # scans the dense-prefix stack and the MoE stack separately
    first_k_dense: int = 0
    dense_ff: int | None = None  # dense-prefix MLP width (defaults to d_ff)

    # --- DeepSeek-style multi-head latent attention (MLA) ---
    # kv_lora_rank set => MLA: K/V live as ONE shared per-token latent
    # [c_kv (kv_lora_rank); k_pe (qk_rope_head_dim)] instead of per-head
    # K/V — the decode cache shrinks ~(2*H*hd)/(rank+rope)x. q_lora_rank
    # adds the low-rank query path (DeepSeek-V2/V3; the Lite models use a
    # direct query projection).
    kv_lora_rank: int | None = None
    q_lora_rank: int | None = None
    qk_rope_head_dim: int = 64    # roped sub-head, shared across heads (MQA-style)
    qk_nope_head_dim: int = 128   # position-free sub-head, absorbed into the latent
    v_head_dim: int = 128         # per-head value width out of the latent
    # DeepSeek-yarn long-context: multiplier on the MLA softmax scale
    # (yarn_get_mscale(factor, mscale_all_dim)^2 — HF applies it to
    # attention scaling, NOT the rope tables)
    attn_scale_mult: float = 1.0
    # LongCat-Flash: the low-rank norms' outputs are scaled by
    # sqrt(d_model / rank) (config.json mla_scale_q_lora / mla_scale_kv_lora)
    mla_scale_q_lora: bool = False
    mla_scale_kv_lora: bool = False

    # --- block kind ---
    # "standard": attention + one feed-forward a layer. "shortcut_moe"
    # (LongCat-Flash): a layer is TWO attention sub-layers with a dense FFN
    # (width dense_ff) each, and one MoE (expert width d_ff) that reads the
    # first sub-layer's post-attention norm and is added after the second
    # sub-layer's FFN. Its attention/FFN/norm params stack (L, 2, ...), and
    # the cache holds two rows a layer (cache_rows). "sambay"
    # (Phi-4-mini-flash): layers come in pairs, each layer a mixer and a
    # feed-forward under LayerNorm, no positional encoding. n_layers/4 pairs
    # of (state-space, window attention), one pair of (state-space that also
    # hands on its scan output, full attention), then n_layers/4 - 1 pairs
    # of (gated memory unit over that output, cross attention over the full
    # layer's K/V). Attention is differential. The cache holds a ring of
    # sliding_window columns a window layer, ONE full-length row, and a
    # recurrent state and a conv tail a state-space layer (models/llama.py
    # CACHE_LEAVES). "gated_delta" (Qwen3-Next): the scan's unit is a PERIOD of
    # full_attention_interval layers, each a mixer and the expert feed-forward
    # under (1 + w) RMSNorm: linear attention (the gated delta rule,
    # models/delta.py) in all but the period's last layer, which is full
    # attention. The cache holds a K/V row a PERIOD and, a linear layer, a
    # float32 matrix state a value head and a conv tail.
    block: str = "standard"
    # --- the "gated_delta" block ---
    full_attention_interval: int = 0  # layers a period; its last is full attention
    linear_key_heads: int = 0         # the delta rule's key heads, each serving
    linear_value_heads: int = 0       # value_heads / key_heads value heads
    linear_key_dim: int = 128         # a key head's width
    linear_value_dim: int = 128       # a value head's width
    linear_conv: int = 4              # taps of the causal conv over [q; k; v]
    # full attention's query projection is twice as wide: a head's second half
    # is a gate, and the attention output is multiplied by its sigmoid
    attn_output_gate: bool = False
    # the delta rule's beta is 2 sigmoid(b), so a state's transition
    # I - beta k k^T has eigenvalues in (-1, 1] (Olmo-Hybrid linear_allow_neg_eigval)
    linear_neg_eigval: bool = False
    # the delta rule's input projections are separate matrices side by side
    # ([all q | all k | all v | all z], [all b | all a]) and not laid out a key
    # head (models/delta.py ``grouped``)
    linear_grouped_proj: bool = False
    # False: the attention layers turn neither queries nor keys (a model whose
    # position comes through its recurrent layers; Olmo-Hybrid rope_theta null)
    rotary: bool = True
    # --- state-space (Mamba-1) mixer of the "sambay" block ---
    ssm_d_inner: int | None = None   # None: 2 * d_model
    ssm_d_state: int = 16
    ssm_d_conv: int = 4
    ssm_dt_rank: int | None = None   # None: ceil(d_model / 16)
    # zero-compute (identity) experts: router outputs n_experts.. add
    # gate * input, no weights (LongCat-Flash zero_expert_num)
    n_zero_experts: int = 0
    # --- this chip's share of an expert-parallel deployment ---
    # n_experts stays the PUBLISHED count (the router's routed width); a
    # replica that holds only experts [first_expert, first_expert +
    # n_experts_held) computes their part of the MoE output and nothing for
    # picks held elsewhere (ops/moe.py moe_infer). None = all held here.
    # Every block kind's expert layer honours it (the standard block's through
    # the dropless layer of a cached forward only).
    n_experts_held: int | None = None
    first_expert: int = 0
    # the published vocabulary when vocab_size is this chip's slice of it:
    # ids, logits and sampling are over the slice, which holds none of the
    # tokenizer's special rows, so `prime serve --continuous` ends a stream
    # from it at max_tokens and never on an end-of-sequence id (serve_model)
    vocab_published: int | None = None
    # --- layers of more than one kind on the standard block (Laguna) ---
    # the published config's own lists, one entry a layer: ``layer_types``
    # "full_attention" | "sliding_attention", ``mlp_layer_types`` "dense" (a
    # SwiGLU of width dense_ff) | "sparse" (the expert layer). Set together. The
    # kinds differ in SHAPE, so each has a parameter stack of its own
    # (models/llama.py ``_init_kinds_params``) and the layer walk is over runs of
    # the pattern (``kind_segments``), not one scan under a traced flag: a full
    # layer has n_heads query heads, a K/V row, rope_theta / partial_rotary /
    # rope_yarn; a sliding layer window_heads query heads, a ring of
    # sliding_window columns, rope_local_theta over the whole head, unscaled
    layer_types: tuple[str, ...] | None = None
    mlp_layer_types: tuple[str, ...] | None = None
    window_heads: int | None = None   # a sliding layer's query heads (None: n_heads)
    # one scalar gate a head and token, sigmoid(u W_g), on the attention's
    # output before the output projection (Laguna gating "per-head")
    attn_head_gate: bool = False

    def __post_init__(self) -> None:
        if self.layer_types is None and self.mlp_layer_types is None:
            return
        kinds = (self.layer_types or (), self.mlp_layer_types or ())
        if (
            self.block != "standard" or not self.sliding_window
            or any(len(k) != self.n_layers for k in kinds)
            or set(kinds[0]) - {"full_attention", "sliding_attention"}
            or set(kinds[1]) - {"dense", "sparse"}
            or ("sparse" in kinds[1] and not self.is_moe)
        ):
            raise ValueError(
                f"{self.name}: layer_types and mlp_layer_types are set together on the "
                "standard block, one entry a layer ('full_attention' | 'sliding_attention', "
                "'dense' | 'sparse'), with a sliding_window and, for 'sparse', experts"
            )

    @property
    def full_layers(self) -> int:
        """Layers that keep a full-length K/V row, where ``layer_types`` says."""
        return (self.layer_types or ()).count("full_attention")

    @property
    def window_layers(self) -> int:
        """Layers that keep a ring of ``sliding_window`` columns instead."""
        return (self.layer_types or ()).count("sliding_attention")

    @property
    def heads_per_layer(self) -> tuple[int, ...]:
        """Query heads a layer (the published ``num_attention_heads_per_layer``)."""
        if self.layer_types is None:
            return (self.n_heads,) * self.n_layers
        return tuple(
            self.n_heads if kind == "full_attention" else self.window_heads or self.n_heads
            for kind in self.layer_types
        )

    @property
    def kind_segments(self) -> tuple[tuple[int, int, int], ...]:
        """``layer_types`` x ``mlp_layer_types`` cut into (first layer, layers a
        unit, units) runs, each a unit of the pattern repeated: what the layer
        walk scans (a run of one unit is unrolled). Greedy from the front, the
        unit (up to 8 layers) that covers most; the published Laguna lists give
        (0, 1, 1), (1, 4, 11), (45, 1, 3), their first five layers (0, 1, 1),
        (1, 1, 3), (4, 1, 1)."""
        pairs = list(zip(self.layer_types or (), self.mlp_layer_types or ()))
        out, at = [], 0
        while at < len(pairs):
            best = (1, 1)
            for unit in range(1, min(8, len(pairs) - at) + 1):
                units = 1
                while pairs[at + units * unit : at + (units + 1) * unit] == pairs[at : at + unit]:
                    units += 1
                if unit * units > best[0] * best[1] and (unit == 1 or units > 1):
                    best = (unit, units)
            out.append((at, *best))
            at += best[0] * best[1]
        return tuple(out)

    @property
    def cache_rows(self) -> int:
        """Rows of the stacked KV cache's leading axis: one an attention that
        keeps a full-length row (the sambay block has one such layer; under
        ``layer_types`` the full layers only, a sliding layer keeps a ring)."""
        if self.layer_types is not None:
            return self.full_layers
        if self.block == "sambay":
            return 1
        if self.block == "gated_delta":
            return self.n_layers // self.full_attention_interval
        return self.n_layers * (2 if self.block == "shortcut_moe" else 1)

    @property
    def row_attentions(self) -> int:
        """Attentions one forward makes over a full-length K/V row: one a cache
        row, and on the sambay block one more a cross pair (each reads the one
        row the middle layer wrote)."""
        return self.cache_rows + self.cross_pairs

    @property
    def sliding_layers(self) -> tuple[bool, ...]:
        """Which layers ``sliding_pattern`` makes slide, one entry a layer (all
        False without a ``sliding_window``): the pattern's one owner, read by
        ``models/llama.py::sliding_layer_flags`` and by the count below."""
        layers = range(self.n_layers)
        if not self.sliding_window:
            return (False,) * self.n_layers
        if self.sliding_pattern == "even":  # Gemma2: even layers slide
            return tuple(i % 2 == 0 for i in layers)
        if self.sliding_pattern == "uniform":  # Mistral-style: all layers slide
            return (True,) * self.n_layers
        if self.sliding_pattern.endswith(":1"):  # Gemma3 "5:1": every (N+1)th is global
            period = int(self.sliding_pattern[:-2]) + 1
            return tuple((i + 1) % period != 0 for i in layers)
        raise ValueError(
            f"Unknown sliding_pattern {self.sliding_pattern!r} "
            "(want 'even' | 'uniform' | 'N:1')"
        )

    @property
    def sliding_row_attentions(self) -> int:
        """Of ``row_attentions``, those whose window runs over a full-length
        row: a standard block's sliding layers (under ``layer_types`` a sliding
        layer keeps a ring and is no row attention; the other blocks' row
        attentions have no window)."""
        if self.block != "standard" or self.layer_types is not None or self.mla:
            return 0
        return sum(self.sliding_layers)

    @property
    def linear_layers(self) -> int:
        """gated_delta: layers that carry a matrix state; 0 for other blocks."""
        if self.block != "gated_delta":
            return 0
        return self.n_layers - self.n_layers // self.full_attention_interval

    @property
    def linear_conv_channels(self) -> int:
        """gated_delta: the channels [q; k; v] the causal conv runs over."""
        return (
            2 * self.linear_key_heads * self.linear_key_dim
            + self.linear_value_heads * self.linear_value_dim
        )

    @property
    def window_pairs(self) -> int:
        """sambay: (state-space, window attention) pairs; 0 for other blocks."""
        return self.n_layers // 4 if self.block == "sambay" else 0

    @property
    def cross_pairs(self) -> int:
        """sambay: (memory unit, cross attention) pairs; 0 for other blocks."""
        return self.n_layers // 4 - 1 if self.block == "sambay" else 0

    @property
    def cross_half_layers(self) -> int:
        """Layers that keep no state and whose output only the head reads
        (the sambay block's cross half); 0 for other blocks."""
        return 2 * self.cross_pairs

    @property
    def d_inner(self) -> int:
        return self.ssm_d_inner or 2 * self.d_model

    @property
    def dt_rank(self) -> int:
        return self.ssm_dt_rank or -(-self.d_model // 16)

    @property
    def experts_held(self) -> int:
        return self.n_experts if self.n_experts_held is None else self.n_experts_held

    @property
    def shared_ff(self) -> int:
        """Width of the always-on shared expert(s): n_shared_experts of d_ff."""
        return self.n_shared_experts * self.d_ff

    @property
    def router_width(self) -> int:
        return self.n_experts + self.n_zero_experts

    @property
    def head_dim(self) -> int:
        return self.head_dim_override or self.d_model // self.n_heads

    @property
    def mla(self) -> bool:
        return self.kv_lora_rank is not None

    @property
    def mla_cache_dim(self) -> int:
        """Per-token latent the cache stores: [c_kv; roped k_pe]."""
        assert self.kv_lora_rank is not None
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def is_moe(self) -> bool:
        return self.n_experts > 0

    @property
    def param_count(self) -> int:
        embed = self.vocab_size * self.d_model
        head = 0 if self.tie_embeddings else self.d_model * self.vocab_size
        if self.mla:
            nope, rope = self.qk_nope_head_dim, self.qk_rope_head_dim
            rank, vd, h = self.kv_lora_rank, self.v_head_dim, self.n_heads
            if self.q_lora_rank is not None:
                attn = self.d_model * self.q_lora_rank + self.q_lora_rank * (
                    1 + h * (nope + rope)
                )
            else:
                attn = self.d_model * h * (nope + rope)
            attn += (
                self.d_model * (rank + rope)  # wkv_a
                + rank                        # kv_a_norm
                + rank * h * (nope + vd)      # wkv_b
                + h * vd * self.d_model       # wo
            )
        else:
            attn = self.d_model * self.head_dim * (2 * self.n_heads + 2 * self.n_kv_heads)
        if self.attn_bias:
            attn += self.head_dim * (self.n_heads + 2 * self.n_kv_heads)
        if self.attn_out_bias:
            attn += self.d_model
        if self.qk_norm:
            attn += 2 * self.head_dim
        if self.qk_norm_full:
            attn += (self.n_heads + self.n_kv_heads) * self.head_dim
        if self.attn_sinks:
            attn += self.n_heads
        if self.is_moe:
            mlp = self.n_experts * 3 * self.d_model * self.d_ff + self.d_model * self.n_experts
            if self.moe_bias:
                mlp += self.n_experts * (2 * self.d_ff + self.d_model) + self.n_experts
            if self.moe_score_bias:
                mlp += self.n_experts
            if self.n_shared_experts:
                mlp += 3 * self.d_model * self.n_shared_experts * self.d_ff
        else:
            mlp = 3 * self.d_model * self.d_ff
        norms = ((2 if self.pre_norms else 0) + (2 if self.post_norms else 0)) * self.d_model
        if self.block == "sambay":
            d, di, n, r = self.d_model, self.d_inner, self.ssm_d_state, self.dt_rank
            qw, kvw = self.n_heads * self.head_dim, self.n_kv_heads * self.head_dim
            ffn = 3 * d * self.d_ff + 4 * d  # two LayerNorms (weight, bias) a layer
            ssm = (
                d * 2 * di + di * (self.ssm_d_conv + 1) + di * (r + 2 * n)
                + r * di + di + di * n + di + di * d
            )
            differential = 4 * self.head_dim + 2 * self.head_dim  # the lambdas, the 2-head norm
            self_attn = d * (qw + 2 * kvw) + qw + 2 * kvw + qw * d + d + differential
            cross_attn = d * qw + qw + qw * d + d + differential
            memory = 2 * d * di
            pairs = self.window_pairs + 1
            return (
                embed + head + 2 * d + self.n_layers * ffn
                + pairs * (ssm + self_attn) + self.cross_pairs * (memory + cross_attn)
            )
        if self.block == "gated_delta":
            d, ff = self.d_model, self.d_ff
            value = self.linear_value_heads * self.linear_value_dim
            delta = (
                d * (self.linear_conv_channels + value) + d * 2 * self.linear_value_heads
                + self.linear_conv * self.linear_conv_channels + 2 * self.linear_value_heads
                + self.linear_value_dim + value * d
            )
            query = self.n_heads * self.head_dim
            full = (
                d * ((2 if self.attn_output_gate else 1) * query + 2 * self.n_kv_heads * self.head_dim)
                + query * d
            )
            if self.qk_norm:
                full += 2 * self.head_dim
            if self.qk_norm_full:
                full += query + self.n_kv_heads * self.head_dim
            if self.is_moe:
                feed = (
                    self.experts_held * 3 * d * ff + d * self.n_experts
                    + self.n_shared_experts * 3 * d * ff + d
                )
            else:
                feed = 3 * d * ff
            return (
                embed + head + d + self.n_layers * (feed + norms)
                + self.linear_layers * delta + self.cache_rows * full
            )
        if self.block == "shortcut_moe":
            # two attentions, two dense FFNs and their norms; one router (+
            # selection bias) and the experts HELD here
            dense_mlp = 3 * self.d_model * (self.dense_ff or self.d_ff)
            experts = (
                self.experts_held * 3 * self.d_model * self.d_ff
                + self.d_model * self.router_width
                + (self.router_width if self.moe_score_bias else 0)
            )
            return (
                embed + head + self.d_model
                + self.n_layers * (2 * (attn + norms + dense_mlp) + experts)
            )
        if self.layer_types is not None:
            # by kind: each attention at its own head count (and its head gate),
            # a dense layer at dense_ff, a sparse one over the experts HELD here
            d, kv = self.d_model, 2 * self.n_kv_heads * self.head_dim
            attn_total = sum(
                d * (2 * h * self.head_dim + kv) + (d * h if self.attn_head_gate else 0)
                for h in self.heads_per_layer
            )
            sparse = (
                self.experts_held * 3 * d * self.d_ff + d * self.n_experts
                + 3 * d * self.shared_ff
            )
            dense = 3 * d * (self.dense_ff or self.d_ff)
            mlp_total = sum(dense if k == "dense" else sparse for k in self.mlp_layer_types)
            return embed + head + attn_total + mlp_total + self.n_layers * norms + d
        if self.first_k_dense:
            dense_mlp = 3 * self.d_model * (self.dense_ff or self.d_ff)
            mlp_total = (
                (self.n_layers - self.first_k_dense) * mlp
                + self.first_k_dense * dense_mlp
            )
        else:
            mlp_total = self.n_layers * mlp
        return (
            embed + head + self.n_layers * (attn + norms) + mlp_total + self.d_model
        )

    def scaled(self, **overrides) -> "ModelConfig":
        return replace(self, **overrides)


MODEL_PRESETS: dict[str, ModelConfig] = {
    "llama3-8b": ModelConfig(
        name="llama3-8b",
        vocab_size=128256,
        d_model=4096,
        n_layers=32,
        n_heads=32,
        n_kv_heads=8,
        d_ff=14336,
    ),
    "llama3-70b": ModelConfig(
        name="llama3-70b",
        vocab_size=128256,
        d_model=8192,
        n_layers=80,
        n_heads=64,
        n_kv_heads=8,
        d_ff=28672,
    ),
    # Llama 3.2: frequency-dependent llama3 rope scaling (factor 32 over the
    # 8k pretraining window) — matches the released checkpoints' config.json
    "llama3.2-1b": ModelConfig(
        name="llama3.2-1b",
        vocab_size=128256,
        d_model=2048,
        n_layers=16,
        n_heads=32,
        n_kv_heads=8,
        d_ff=8192,
        tie_embeddings=True,
        rope_llama3=(32.0, 1.0, 4.0, 8192.0),
    ),
    "llama3.2-3b": ModelConfig(
        name="llama3.2-3b",
        vocab_size=128256,
        d_model=3072,
        n_layers=28,
        n_heads=24,
        n_kv_heads=8,
        d_ff=8192,
        tie_embeddings=True,
        rope_llama3=(32.0, 1.0, 4.0, 8192.0),
    ),
    # Qwen2.5 family: q/k/v biases, 1M rope theta, small sizes tie embeddings
    "qwen2.5-0.5b": ModelConfig(
        name="qwen2.5-0.5b",
        vocab_size=151936,
        d_model=896,
        n_layers=24,
        n_heads=14,
        n_kv_heads=2,
        d_ff=4864,
        max_seq_len=32768,
        rope_theta=1000000.0,
        rms_eps=1e-6,
        tie_embeddings=True,
        attn_bias=True,
    ),
    "qwen2.5-1.5b": ModelConfig(
        name="qwen2.5-1.5b",
        vocab_size=151936,
        d_model=1536,
        n_layers=28,
        n_heads=12,
        n_kv_heads=2,
        d_ff=8960,
        max_seq_len=32768,
        rope_theta=1000000.0,
        rms_eps=1e-6,
        tie_embeddings=True,
        attn_bias=True,
    ),
    "qwen2.5-7b": ModelConfig(
        name="qwen2.5-7b",
        vocab_size=152064,
        d_model=3584,
        n_layers=28,
        n_heads=28,
        n_kv_heads=4,
        d_ff=18944,
        max_seq_len=32768,
        rope_theta=1000000.0,
        rms_eps=1e-6,
        attn_bias=True,
    ),
    "qwen2.5-14b": ModelConfig(
        name="qwen2.5-14b",
        vocab_size=152064,
        d_model=5120,
        n_layers=48,
        n_heads=40,
        n_kv_heads=8,
        d_ff=13824,
        max_seq_len=32768,
        rope_theta=1000000.0,
        rms_eps=1e-6,
        attn_bias=True,
    ),
    # Qwen3 family: decoupled head_dim 128, per-head q/k RMSNorm, no biases
    "qwen3-0.6b": ModelConfig(
        name="qwen3-0.6b",
        vocab_size=151936,
        d_model=1024,
        n_layers=28,
        n_heads=16,
        n_kv_heads=8,
        d_ff=3072,
        max_seq_len=40960,
        rope_theta=1000000.0,
        rms_eps=1e-6,
        tie_embeddings=True,
        head_dim_override=128,
        qk_norm=True,
    ),
    "qwen3-4b": ModelConfig(
        name="qwen3-4b",
        vocab_size=151936,
        d_model=2560,
        n_layers=36,
        n_heads=32,
        n_kv_heads=8,
        d_ff=9728,
        max_seq_len=40960,
        rope_theta=1000000.0,
        rms_eps=1e-6,
        tie_embeddings=True,
        head_dim_override=128,
        qk_norm=True,
    ),
    "qwen3-8b": ModelConfig(
        name="qwen3-8b",
        vocab_size=151936,
        d_model=4096,
        n_layers=36,
        n_heads=32,
        n_kv_heads=8,
        d_ff=12288,
        max_seq_len=40960,
        rope_theta=1000000.0,
        rms_eps=1e-6,
        head_dim_override=128,
        qk_norm=True,
    ),
    # Gemma 2 family: GeGLU, (1+w) norms, post-norms, scaled embeddings,
    # softcapping, alternating 4k sliding-window / global layers
    "gemma2-2b": ModelConfig(
        name="gemma2-2b",
        vocab_size=256000,
        d_model=2304,
        n_layers=26,
        n_heads=8,
        n_kv_heads=4,
        d_ff=9216,
        max_seq_len=8192,
        rope_theta=10000.0,
        rms_eps=1e-6,
        tie_embeddings=True,
        head_dim_override=256,
        act="gelu_tanh",
        norm_plus_one=True,
        post_norms=True,
        scale_embed=True,
        attn_softcap=50.0,
        final_softcap=30.0,
        query_scale=256,
        sliding_window=4096,
    ),
    "gemma2-9b": ModelConfig(
        name="gemma2-9b",
        vocab_size=256000,
        d_model=3584,
        n_layers=42,
        n_heads=16,
        n_kv_heads=8,
        d_ff=14336,
        max_seq_len=8192,
        rope_theta=10000.0,
        rms_eps=1e-6,
        tie_embeddings=True,
        head_dim_override=256,
        act="gelu_tanh",
        norm_plus_one=True,
        post_norms=True,
        scale_embed=True,
        attn_softcap=50.0,
        final_softcap=30.0,
        query_scale=256,
        sliding_window=4096,
    ),
    "gemma2-27b": ModelConfig(
        name="gemma2-27b",
        vocab_size=256000,
        d_model=4608,
        n_layers=46,
        n_heads=32,
        n_kv_heads=16,
        d_ff=36864,
        max_seq_len=8192,
        rope_theta=10000.0,
        rms_eps=1e-6,
        tie_embeddings=True,
        head_dim_override=128,
        act="gelu_tanh",
        norm_plus_one=True,
        post_norms=True,
        scale_embed=True,
        attn_softcap=50.0,
        final_softcap=30.0,
        query_scale=144,
        sliding_window=4096,
    ),
    # OLMo-2 family: post-norm-only blocks (no input norms; sublayer outputs
    # normed before the residual add) + full-width q/k RMSNorm
    "olmo2-7b": ModelConfig(
        name="olmo2-7b",
        vocab_size=100352,
        d_model=4096,
        n_layers=32,
        n_heads=32,
        n_kv_heads=32,
        d_ff=11008,
        max_seq_len=4096,
        rope_theta=500000.0,
        rms_eps=1e-6,
        pre_norms=False,
        post_norms=True,
        qk_norm_full=True,
    ),
    "olmo2-13b": ModelConfig(
        name="olmo2-13b",
        vocab_size=100352,
        d_model=5120,
        n_layers=40,
        n_heads=40,
        n_kv_heads=40,
        d_ff=13824,
        max_seq_len=4096,
        rope_theta=500000.0,
        rms_eps=1e-6,
        pre_norms=False,
        post_norms=True,
        qk_norm_full=True,
    ),
    # Phi-3 family: llama math behind fused qkv/gate_up projections (split at
    # load); phi-4 shares the phi3 model_type with a 100k vocab
    "phi3-mini": ModelConfig(
        name="phi3-mini",
        vocab_size=32064,
        d_model=3072,
        n_layers=32,
        n_heads=32,
        n_kv_heads=32,
        d_ff=8192,
        max_seq_len=4096,
        rope_theta=10000.0,
        rms_eps=1e-5,
        sliding_window=2047,        # every layer slides (released 4k config)
        sliding_pattern="uniform",
    ),
    "phi4-14b": ModelConfig(
        name="phi4-14b",
        vocab_size=100352,
        d_model=5120,
        n_layers=40,
        n_heads=40,
        n_kv_heads=10,
        d_ff=17920,
        max_seq_len=16384,
        rope_theta=250000.0,
        rms_eps=1e-5,
    ),
    # Qwen3-MoE: qk-norm attention over 128 fine-grained experts, top-8,
    # raw-softmax gates renormalized per norm_topk_prob (True on the released
    # 30B-A3B), expert width 768 (moe_intermediate_size)
    "qwen3-30b-a3b": ModelConfig(
        name="qwen3-30b-a3b",
        vocab_size=151936,
        d_model=2048,
        n_layers=48,
        n_heads=32,
        n_kv_heads=4,
        d_ff=768,
        max_seq_len=32768,
        rope_theta=1000000.0,
        rms_eps=1e-6,
        head_dim_override=128,
        qk_norm=True,
        n_experts=128,
        experts_per_token=8,
        norm_topk=True,
        capacity_factor=2.0,
    ),
    # Gemma 3 family (text towers): Gemma2's GeGLU/(1+w)/post-norms/scaled
    # embeddings, minus the softcaps, plus per-head qk-norm, a 5:1
    # sliding/global schedule, and dual-frequency rope (global 1M — linearly
    # scaled x8 on 4b+ — local 10k). max_seq_len capped at 32k here (the
    # no-cache rope table is materialized at max_seq_len; serving longer
    # contexts sizes tables from the KV capacity instead).
    "gemma3-1b": ModelConfig(
        name="gemma3-1b",
        vocab_size=262144,
        d_model=1152,
        n_layers=26,
        n_heads=4,
        n_kv_heads=1,
        d_ff=6912,
        max_seq_len=32768,
        rope_theta=1000000.0,
        rope_local_theta=10000.0,
        rms_eps=1e-6,
        tie_embeddings=True,
        head_dim_override=256,
        qk_norm=True,
        act="gelu_tanh",
        norm_plus_one=True,
        post_norms=True,
        scale_embed=True,
        query_scale=256,
        sliding_window=512,
        sliding_pattern="5:1",
    ),
    "gemma3-4b": ModelConfig(
        name="gemma3-4b",
        vocab_size=262208,
        d_model=2560,
        n_layers=34,
        n_heads=8,
        n_kv_heads=4,
        d_ff=10240,
        max_seq_len=32768,
        rope_theta=1000000.0,
        rope_local_theta=10000.0,
        rope_scale=8.0,
        rms_eps=1e-6,
        tie_embeddings=True,
        head_dim_override=256,
        qk_norm=True,
        act="gelu_tanh",
        norm_plus_one=True,
        post_norms=True,
        scale_embed=True,
        query_scale=256,
        sliding_window=1024,
        sliding_pattern="5:1",
    ),
    "gemma3-12b": ModelConfig(
        name="gemma3-12b",
        vocab_size=262208,
        d_model=3840,
        n_layers=48,
        n_heads=16,
        n_kv_heads=8,
        d_ff=15360,
        max_seq_len=32768,
        rope_theta=1000000.0,
        rope_local_theta=10000.0,
        rope_scale=8.0,
        rms_eps=1e-6,
        tie_embeddings=True,
        head_dim_override=256,
        qk_norm=True,
        act="gelu_tanh",
        norm_plus_one=True,
        post_norms=True,
        scale_embed=True,
        query_scale=256,
        sliding_window=1024,
        sliding_pattern="5:1",
    ),
    "mixtral-8x7b": ModelConfig(
        name="mixtral-8x7b",
        vocab_size=32000,
        d_model=4096,
        n_layers=32,
        n_heads=32,
        n_kv_heads=8,
        d_ff=14336,
        max_seq_len=32768,
        rope_theta=1000000.0,
        n_experts=8,
        experts_per_token=2,
    ),
    # small configs for tests / benches that still exercise every code path
    "debug-128m": ModelConfig(
        name="debug-128m",
        vocab_size=32000,
        d_model=768,
        n_layers=12,
        n_heads=12,
        n_kv_heads=4,
        d_ff=2048,
        max_seq_len=2048,
    ),
    # GPT-OSS (openai 2025): all-MoE with router+expert biases and clamped
    # GLU, per-head attention sinks, even-alternating sliding window 128,
    # q/k/v/o biases, non-truncated YaRN x32 over a 4k pretrain range.
    # attention_factor = mscale_of(32) = 0.1*ln(32)+1 ≈ 1.3466 (resolved here
    # like every other preset so model code only scales tables).
    "gpt-oss-20b": ModelConfig(
        name="gpt-oss-20b",
        vocab_size=201088,
        d_model=2880,
        n_layers=24,
        n_heads=64,
        n_kv_heads=8,
        d_ff=2880,
        max_seq_len=32768,
        rope_theta=150000.0,
        rms_eps=1e-5,
        head_dim_override=64,
        attn_bias=True,
        attn_out_bias=True,
        attn_sinks=True,
        sliding_window=128,
        sliding_pattern="even",
        rope_yarn=(32.0, 32.0, 1.0, 4096.0, 1.3465735902799727),
        rope_yarn_truncate=False,
        n_experts=32,
        experts_per_token=4,
        norm_topk=True,
        capacity_factor=2.0,
        moe_bias=True,
        moe_glu_clamp=7.0,
    ),
    "gpt-oss-120b": ModelConfig(
        name="gpt-oss-120b",
        vocab_size=201088,
        d_model=2880,
        n_layers=36,
        n_heads=64,
        n_kv_heads=8,
        d_ff=2880,
        max_seq_len=32768,
        rope_theta=150000.0,
        rms_eps=1e-5,
        head_dim_override=64,
        attn_bias=True,
        attn_out_bias=True,
        attn_sinks=True,
        sliding_window=128,
        sliding_pattern="even",
        rope_yarn=(32.0, 32.0, 1.0, 4096.0, 1.3465735902799727),
        rope_yarn_truncate=False,
        n_experts=128,
        experts_per_token=4,
        norm_topk=True,
        capacity_factor=2.0,
        moe_bias=True,
        moe_glu_clamp=7.0,
    ),
    "tiny-test": ModelConfig(
        name="tiny-test",
        vocab_size=512,
        d_model=128,
        n_layers=2,
        n_heads=4,
        n_kv_heads=2,
        d_ff=256,
        max_seq_len=512,
    ),
    # DeepSeek-V2-Lite at published scale (15.7B total / ~2.4B active):
    # MLA with direct query projection, 64 fine-grained experts (top-6) + 2
    # shared, one dense-prefix layer — the real checkpoint's architecture
    # (HF deepseek-ai/DeepSeek-V2-Lite config.json values)
    "deepseek-v2-lite": ModelConfig(
        name="deepseek-v2-lite",
        vocab_size=102400,
        d_model=2048,
        n_layers=27,
        n_heads=16,
        n_kv_heads=16,
        d_ff=1408,            # moe_intermediate_size (per-expert width)
        max_seq_len=32768,
        rope_theta=10000.0,
        rms_eps=1e-6,
        kv_lora_rank=512,
        qk_rope_head_dim=64,
        qk_nope_head_dim=128,
        v_head_dim=128,
        n_experts=64,
        experts_per_token=6,
        capacity_factor=2.0,
        n_shared_experts=2,
        moe_score_func="softmax",  # V2 gates with softmax (V3 moved to sigmoid)
        routed_scaling_factor=1.0,
        norm_topk=False,           # V2-Lite ships norm_topk_prob=false
        first_k_dense=1,
        dense_ff=10944,            # the prefix layer's dense intermediate
        # the checkpoint's yarn long-context: factor 40 over a 4096 window;
        # mscale == mscale_all_dim == 0.707 so the table attention factor
        # cancels to 1.0 and the whole mscale rides the softmax scale:
        # (0.1*0.707*ln(40)+1)^2. max_seq_len capped at 32k (the no-cache
        # forward materializes rope tables at this length; serving sizes
        # tables from KV capacity, so longer contexts still work).
        rope_yarn=(40.0, 32.0, 1.0, 4096.0, 1.0),
        attn_scale_mult=1.5896261651208736,
    ),
    # ~1B dense model with DeepSeek-V2-dimension MLA (rank 512 latent, 64
    # rope, 128 nope/value heads): the bench model for the latent-cache
    # long-context story — its decode cache is ~9x smaller than a
    # GQA model's at the same context
    "mla-1b": ModelConfig(
        name="mla-1b",
        vocab_size=32000,
        d_model=2048,
        n_layers=16,
        n_heads=16,
        n_kv_heads=16,
        d_ff=8192,
        max_seq_len=8192,
        kv_lora_rank=512,
        qk_rope_head_dim=64,
        qk_nope_head_dim=128,
        v_head_dim=128,
    ),
    # DeepSeek-V2-Lite-shaped MLA at test scale: direct query projection
    # (q_lora_rank=None), shared-latent KV cache, absorbed decode
    "tiny-mla": ModelConfig(
        name="tiny-mla",
        vocab_size=512,
        d_model=128,
        n_layers=2,
        n_heads=4,
        n_kv_heads=4,  # MLA has no GQA grouping; kept == n_heads for clarity
        d_ff=256,
        max_seq_len=512,
        kv_lora_rank=32,
        qk_rope_head_dim=16,
        qk_nope_head_dim=32,
        v_head_dim=32,
    ),
    # DeepSeek-V2/V3-style low-rank query path at test scale
    "tiny-mla-qlora": ModelConfig(
        name="tiny-mla-qlora",
        vocab_size=512,
        d_model=128,
        n_layers=2,
        n_heads=4,
        n_kv_heads=4,
        d_ff=256,
        max_seq_len=512,
        kv_lora_rank=32,
        q_lora_rank=48,
        qk_rope_head_dim=16,
        qk_nope_head_dim=32,
        v_head_dim=32,
    ),
    "tiny-moe": ModelConfig(
        name="tiny-moe",
        vocab_size=512,
        d_model=128,
        n_layers=2,
        n_heads=4,
        n_kv_heads=2,
        d_ff=256,
        max_seq_len=512,
        n_experts=4,
        experts_per_token=2,
        capacity_factor=2.0,
    ),
    # DeepSeek-V3 architecture at test scale: MLA + sigmoid-scored routing
    # with a selection-only balance bias, routed scaling, and an always-on
    # shared expert. Dense-prefix layers (first_k_dense_replace) and group
    # routing (n_group) are modeled too — covered by the HF-parity fixtures
    # in tests/test_mla.py rather than this preset
    "tiny-deepseek": ModelConfig(
        name="tiny-deepseek",
        vocab_size=512,
        d_model=128,
        n_layers=2,
        n_heads=4,
        n_kv_heads=4,
        d_ff=64,              # per-expert width (fine-grained experts)
        max_seq_len=512,
        kv_lora_rank=32,
        q_lora_rank=48,
        qk_rope_head_dim=16,
        qk_nope_head_dim=32,
        v_head_dim=32,
        n_experts=8,
        experts_per_token=2,
        capacity_factor=2.0,
        n_shared_experts=2,
        moe_score_func="sigmoid",
        moe_score_bias=True,
        routed_scaling_factor=2.5,
    ),
    # LongCat-Flash-Chat, one chip's share of a 32-way expert deployment
    # (meituan-longcat/LongCat-Flash-Chat config.json): published widths,
    # 4 of 28 shortcut-connected double layers, experts 0-15 of 512 held
    # (rank 0 of 32) beside the 256 zero-compute experts every chip
    # computes, an eighth of the vocabulary. benchmark/configs/
    # longcat-flash-ep32.json states the cut.
    "longcat-flash-ep32": ModelConfig(
        name="longcat-flash-ep32",
        vocab_size=16384,
        vocab_published=131072,
        d_model=6144,
        n_layers=4,
        n_heads=64,
        n_kv_heads=64,
        d_ff=2048,             # expert_ffn_hidden_size
        dense_ff=12288,        # ffn_hidden_size (the two dense FFNs)
        max_seq_len=131072,
        rope_theta=10000000.0,
        rms_eps=1e-5,
        kv_lora_rank=512,
        q_lora_rank=1536,
        qk_rope_head_dim=64,
        qk_nope_head_dim=128,
        v_head_dim=128,
        mla_scale_q_lora=True,
        mla_scale_kv_lora=True,
        block="shortcut_moe",
        n_experts=512,
        n_experts_held=16,
        first_expert=0,
        n_zero_experts=256,
        experts_per_token=12,
        moe_score_func="softmax",
        moe_score_bias=True,
        routed_scaling_factor=6.0,
        norm_topk=False,
    ),
    # the same block kind at test scale: 2 double layers, 8 routed experts
    # (4 held here, from expert 2) + 4 zero experts, top-3, an eighth of
    # the vocabulary
    "tiny-longcat": ModelConfig(
        name="tiny-longcat",
        vocab_size=512,
        vocab_published=4096,
        d_model=128,
        n_layers=2,
        n_heads=4,
        n_kv_heads=4,
        d_ff=64,
        dense_ff=256,
        max_seq_len=512,
        rope_theta=10000000.0,
        kv_lora_rank=32,
        q_lora_rank=48,
        qk_rope_head_dim=16,
        qk_nope_head_dim=32,
        v_head_dim=32,
        mla_scale_q_lora=True,
        mla_scale_kv_lora=True,
        block="shortcut_moe",
        n_experts=8,
        n_experts_held=4,
        first_expert=2,
        n_zero_experts=4,
        experts_per_token=3,
        moe_score_func="softmax",
        moe_score_bias=True,
        routed_scaling_factor=6.0,
        norm_topk=False,
    ),
    # Phi-4-mini-flash-reasoning whole (microsoft/Phi-4-mini-flash-reasoning
    # config.json): 32 layers at the published widths and vocabulary. The
    # state-space sizes, the biases and the differential attention are the
    # published model class's defaults (benchmark/configs/phi4-mini-flash.json
    # lists them under `assumed`); they give the published 3.85 B parameters.
    "phi4-mini-flash": ModelConfig(
        name="phi4-mini-flash",
        vocab_size=200064,
        d_model=2560,
        n_layers=32,
        n_heads=40,
        n_kv_heads=20,
        d_ff=10240,
        max_seq_len=262144,
        rms_eps=1e-5,          # layer_norm_eps
        tie_embeddings=True,
        sliding_window=512,
        block="sambay",
    ),
    # the same block kind at test scale: 8 layers, so each of the six layer
    # kinds is there (2 window pairs, the middle pair, 1 cross pair)
    "tiny-phi4flash": ModelConfig(
        name="tiny-phi4flash",
        vocab_size=512,
        d_model=64,
        n_layers=8,
        n_heads=4,
        n_kv_heads=2,
        d_ff=128,
        max_seq_len=512,
        rms_eps=1e-5,
        tie_embeddings=True,
        sliding_window=8,
        block="sambay",
        ssm_d_state=4,
    ),
    # Qwen3-Next-80B-A3B-Instruct, one chip's share of a 2-way expert
    # deployment (Qwen/Qwen3-Next-80B-A3B-Instruct config.json): published
    # widths, ONE period of the 12 (three gated-delta-rule layers and a gated
    # full attention, each followed by the expert layer), experts 0-255 of 512
    # held (rank 0 of 2) beside the gated shared expert, half the vocabulary.
    # benchmark/configs/qwen3-next-ep2.json states the cut.
    "qwen3-next-ep2": ModelConfig(
        name="qwen3-next-ep2",
        vocab_size=75968,
        vocab_published=151936,
        d_model=2048,
        n_layers=4,
        n_heads=16,
        n_kv_heads=2,
        d_ff=512,              # moe_intermediate_size
        max_seq_len=262144,
        rope_theta=10000000.0,
        rms_eps=1e-6,
        head_dim_override=256,
        qk_norm=True,
        norm_plus_one=True,
        partial_rotary=0.25,
        attn_output_gate=True,
        block="gated_delta",
        full_attention_interval=4,
        linear_key_heads=16,
        linear_value_heads=32,
        linear_key_dim=128,
        linear_value_dim=128,
        linear_conv=4,
        n_experts=512,
        n_experts_held=256,
        first_expert=0,
        experts_per_token=10,
        norm_topk=True,
        n_shared_experts=1,    # shared_expert_intermediate_size 512 = 1 x d_ff
    ),
    # the same block kind at test scale: two periods, 8 routed experts (4 held
    # here, from expert 2), top-3, an eighth of the vocabulary
    "tiny-qwen3next": ModelConfig(
        name="tiny-qwen3next",
        vocab_size=512,
        vocab_published=4096,
        d_model=64,
        n_layers=8,
        n_heads=4,
        n_kv_heads=2,
        d_ff=32,
        max_seq_len=512,
        rope_theta=10000000.0,
        rms_eps=1e-6,
        head_dim_override=32,
        qk_norm=True,
        norm_plus_one=True,
        partial_rotary=0.25,
        attn_output_gate=True,
        block="gated_delta",
        full_attention_interval=4,
        linear_key_heads=2,
        linear_value_heads=4,
        linear_key_dim=16,
        linear_value_dim=16,
        linear_conv=4,
        n_experts=8,
        n_experts_held=4,
        first_expert=2,
        experts_per_token=3,
        norm_topk=True,
        n_shared_experts=1,
    ),
    # Olmo-Hybrid-7B, the first stage of a 2-stage pipeline (allenai/
    # Olmo-Hybrid-7B config.json): published widths, 16 of the 32 layers (four
    # whole periods of three gated-delta-rule layers and one full attention),
    # each followed by a dense feed-forward, OLMo-2's post-norm residual path
    # and whole-width q/k norm, one value head a key head at (96, 192), beta =
    # 2 sigmoid(b), no rotary embedding (rope_theta null). This chip keeps the
    # final norm and the head so that logits can be compared.
    # benchmark/configs/olmo-hybrid-7b-pp2.json states the cut and what is assumed.
    "olmo-hybrid-7b-pp2": ModelConfig(
        name="olmo-hybrid-7b-pp2",
        vocab_size=100352,
        d_model=3840,
        n_layers=16,
        n_heads=30,
        n_kv_heads=30,
        d_ff=11008,
        max_seq_len=65536,
        rms_eps=1e-6,
        pre_norms=False,
        post_norms=True,
        qk_norm_full=True,
        rotary=False,
        block="gated_delta",
        full_attention_interval=4,
        linear_key_heads=30,
        linear_value_heads=30,
        linear_key_dim=96,
        linear_value_dim=192,
        linear_conv=4,
        linear_neg_eigval=True,
        linear_grouped_proj=True,
    ),
    # the same block at test scale: two periods, 3 heads of (12, 24), so that a
    # transposed or padded state tile fails; full-attention heads of 32, so that
    # a snapshot of the state costs what 34 tokens of bfloat16 K/V cost and the
    # stride rule gives 64 (serve/engine.py snapshot_stride; 32 in float32)
    "tiny-olmohybrid": ModelConfig(
        name="tiny-olmohybrid",
        vocab_size=512,
        d_model=96,
        n_layers=8,
        n_heads=3,
        n_kv_heads=3,
        d_ff=192,
        max_seq_len=512,
        rms_eps=1e-6,
        pre_norms=False,
        post_norms=True,
        qk_norm_full=True,
        rotary=False,
        block="gated_delta",
        full_attention_interval=4,
        linear_key_heads=3,
        linear_value_heads=3,
        linear_key_dim=12,
        linear_value_dim=24,
        linear_conv=4,
        linear_neg_eigval=True,
        linear_grouped_proj=True,
    ),
    # Laguna-S-2.1, one chip's share of a 2-way expert deployment (poolside/
    # Laguna-S-2.1 config.json): published widths, the first 5 of the 48 layers
    # by the config's own lists (the dense full-attention layer 0, then one whole
    # period: three window layers of 72 heads on rings of 512 and a full layer of
    # 48 on a row, each with per-head output gates and the expert layer), experts
    # 0-127 of 256 held (rank 0 of 2) beside the shared expert, half the
    # vocabulary. Full layers rotate half a head under YaRN at theta 5e5, window
    # layers the whole head at 1e4. benchmark/configs/laguna-s-ep2.json states
    # the cut and what is assumed.
    "laguna-s-ep2": ModelConfig(
        name="laguna-s-ep2",
        vocab_size=50176,
        vocab_published=100352,
        d_model=3072,
        n_layers=5,
        n_heads=48,
        window_heads=72,
        n_kv_heads=8,
        d_ff=1024,             # moe_intermediate_size
        dense_ff=12288,        # intermediate_size (layer 0)
        max_seq_len=1048576,
        rope_theta=500000.0,
        rope_local_theta=10000.0,
        partial_rotary=0.5,
        rope_yarn=(128.0, 32.0, 1.0, 8192.0, 1.4852030263919618),
        rms_eps=1e-6,
        head_dim_override=128,
        sliding_window=512,
        layer_types=("full_attention",) + ("sliding_attention",) * 3 + ("full_attention",),
        mlp_layer_types=("dense",) + ("sparse",) * 4,
        attn_head_gate=True,
        n_experts=256,
        n_experts_held=128,
        first_expert=0,
        experts_per_token=10,
        norm_topk=True,
        routed_scaling_factor=2.5,
        n_shared_experts=1,    # shared_expert_intermediate_size 1024 = 1 x d_ff
    ),
    # the same layer walk at test scale: 9 layers, so that the dense first layer
    # and two whole periods are there; 4 and 6 query heads over 2 K/V heads
    # (groups of 2 and 3, one no power of two), a window of 8, 8 routed experts
    # (4 held here, from expert 2), top-3, an eighth of the vocabulary
    "tiny-laguna": ModelConfig(
        name="tiny-laguna",
        vocab_size=512,
        vocab_published=4096,
        d_model=64,
        n_layers=9,
        n_heads=4,
        window_heads=6,
        n_kv_heads=2,
        d_ff=32,
        dense_ff=128,
        max_seq_len=512,
        rope_theta=500000.0,
        rope_local_theta=10000.0,
        partial_rotary=0.5,
        rope_yarn=(4.0, 32.0, 1.0, 64.0, 1.1386294361119891),
        rms_eps=1e-6,
        head_dim_override=16,
        sliding_window=8,
        layer_types=(("full_attention",) + ("sliding_attention",) * 3) * 2 + ("full_attention",),
        mlp_layer_types=("dense",) + ("sparse",) * 8,
        attn_head_gate=True,
        n_experts=8,
        n_experts_held=4,
        first_expert=2,
        experts_per_token=3,
        norm_topk=True,
        routed_scaling_factor=2.5,
        n_shared_experts=1,
    ),
    # GPT-OSS architecture at test scale: sinks + biased clamped-GLU MoE +
    # alternating window + non-truncated yarn, all exercised on CPU
    "tiny-gptoss": ModelConfig(
        name="tiny-gptoss",
        vocab_size=512,
        d_model=128,
        n_layers=2,
        n_heads=4,
        n_kv_heads=2,
        d_ff=96,
        max_seq_len=512,
        rope_theta=150000.0,
        head_dim_override=32,
        attn_bias=True,
        attn_out_bias=True,
        attn_sinks=True,
        sliding_window=8,
        sliding_pattern="even",
        rope_yarn=(32.0, 32.0, 1.0, 64.0, 1.3465735902799727),
        rope_yarn_truncate=False,
        n_experts=4,
        experts_per_token=2,
        norm_topk=True,
        capacity_factor=2.0,
        moe_bias=True,
        moe_glu_clamp=7.0,
    ),
}


def get_config(name: str) -> ModelConfig:
    try:
        return MODEL_PRESETS[name]
    except KeyError:
        valid = ", ".join(sorted(MODEL_PRESETS))
        raise ValueError(f"Unknown model {name!r}: expected one of {valid}") from None
