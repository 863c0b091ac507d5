"""Model interface for the TPU runtime.

The reference wraps user-provided ``torch.nn.Module``s; the TPU-native
equivalent is a functional model: a pytree of parameters plus pure
``init``/``apply``/``loss`` functions. The engine only relies on this
protocol, so users can bring flax/haiku modules via thin adapters
(models/adapters.py).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Protocol

import jax
from jax.sharding import PartitionSpec

PyTree = Any
Rules = list[tuple[str, PartitionSpec]]


@dataclasses.dataclass
class ModelConfig:
    vocab_size: int = 32000
    hidden_size: int = 768
    intermediate_size: int = 3072
    num_layers: int = 12
    num_heads: int = 12
    num_kv_heads: int | None = None  # None -> MHA
    max_seq_len: int = 1024
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    tie_embeddings: bool = True
    lm_head_bias: bool = False      # Phi / GPT-J biased vocab projection
    # architecture switches
    norm_type: str = "layernorm"        # layernorm | rmsnorm
    activation: str = "gelu"            # gelu | relu | swiglu
    position_embedding: str = "learned"  # learned | rope | alibi (Bloom);
    #                                      anything else: DecoderLM adds no
    #                                      positions ("none", "nope": the
    #                                      model has none; "rope_by_kind":
    #                                      models/mellum.py rotates by its
    #                                      rope_parameters)
    use_bias: bool = True
    attn_qkv_bias: bool = False     # qkv biases even when use_bias=False
    #                                 (Qwen-style)
    mlp_bias: bool | None = None    # None -> use_bias; GPT-J: attn
    #                                 unbiased but fc_in/fc_out biased
    parallel_residual: bool = False  # Falcon/Phi-2: x + attn(h) + mlp(h)
    #                                  with a single input norm (no ln2)
    parallel_dual_norm: bool = False  # GPT-NeoX: parallel residual but
    #                                   attn/mlp each get their own norm
    embed_layernorm: bool = False   # Bloom: LayerNorm after word embed
    rotary_pct: float = 1.0         # partial rotary (GPT-NeoX/Phi-2)
    sliding_window: int | None = None  # Mistral windowed attention
    attn_head_dim: int = 0          # the width of an attention head where it
    #                                 is published apart from the hidden
    #                                 size (0: hidden_size // num_heads)
    # MoE (0 experts = dense; reference: deepspeed/moe)
    num_experts: int = 0
    moe_num_shared_experts: int = 0  # Qwen2-MoE always-on experts
    moe_top_k: int = 2
    moe_norm_topk: bool = True      # renormalize top-k probs (Mixtral
    #                                 yes, Qwen2-MoE norm_topk_prob)
    capacity_factor: float = 1.25
    min_capacity: int = 4
    router_aux_loss_coef: float = 0.01
    moe_router_activation: str = "softmax"  # softmax | sigmoid (bias-
    #                                 corrected selection, Kimi-Linear)
    routed_scaling_factor: float = 1.0
    moe_intermediate_size: int = 0  # expert width where it differs from
    #                                 the dense FFN's (0 = the same)
    moe_held_experts: int = 0       # experts HELD here of num_experts, the
    #                                 router's width (0 = all): one chip's
    #                                 share under expert parallelism (the
    #                                 first of them)
    # a stack of more than one kind of layer (models/kimi_linear.py):
    # 1-based layer numbers as the published config gives them; both empty
    # = one kind of layer (every other family)
    kda_layers: tuple = ()          # KDA linear attention (ops/kda.py)
    full_attn_layers: tuple = ()    # latent attention (MLA)
    first_k_dense_replace: int = 0  # leading layers whose FFN is dense
    kda_num_heads: int = 0
    kda_head_dim: int = 128
    kda_conv_size: int = 4
    kda_gate_rank: int = 128        # width of the low-rank decay / output
    #                                 gate maps (the head width)
    kda_head_groups: int = 1        # run the KDA heads in this many groups,
    #                                 one after the other (ops/kda.py): the
    #                                 chunked form's operands live a group
    #                                 at a time
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    mla_use_nope: bool = False      # no rotation on either part of q, k
    # Mamba-2 state-space layers among attention layers
    # (models/granite_hybrid.py, ops/ssd.py); key names as published
    layer_types: tuple | list = ()  # "mamba" | "attention" a layer
    #                                 (models/granite_hybrid.py), or
    #                                 "sliding_attention" | "full_attention"
    #                                 (models/mellum.py: sliding_window holds
    #                                 for the first kind alone); empty = one
    #                                 kind of layer
    rope_parameters: dict = dataclasses.field(default_factory=dict)
    #                                 a rotary table a kind of layer_types,
    #                                 as published: {kind: {rope_type,
    #                                 rope_theta, ...}} (ops/layers.py
    #                                 rotary_embedding); empty = rope_theta
    mamba_n_heads: int = 0
    mamba_d_head: int = 64
    mamba_d_state: int = 128
    mamba_n_groups: int = 1         # groups of heads sharing B and C
    mamba_d_conv: int = 4
    mamba_chunk_size: int = 256
    mamba_expand: int = 2           # n_heads * d_head = expand * hidden_size
    mamba_conv_bias: bool = True
    mamba_proj_bias: bool = False
    # the four muP multipliers of the Granite families, read by
    # models/granite_hybrid.py alone: 1 (None) where a model has none
    embedding_multiplier: float = 1.0   # x0 = embed[tokens] * this
    residual_multiplier: float = 1.0    # x + this * sublayer(norm(x))
    logits_scaling: float = 1.0         # logits / this
    attention_multiplier: float | None = None   # the softmax scale; None =
    #                                             head_dim ** -0.5
    # a looped stack (models/ouro.py): the num_layers layers run
    # total_ut_steps times a forward pass on the SAME weights, the final
    # norm after every pass, and every pass is an exit
    total_ut_steps: int = 1
    sandwich_norm: bool = False     # a second norm on each sublayer's
    #                                 OUTPUT, before the residual add
    exit_gate: bool = False         # a d -> 1 gate on every exit's state:
    #                                 the loss is the expected loss under
    #                                 the exit distribution the gates give
    exit_entropy_beta: float = 0.0  # ... less this x that distribution's
    #                                 entropy, a position
    # numerics
    param_dtype: Any = None   # set to jnp dtype in __post_init__
    loss_chunk: int = 0       # >0: fused chunked cross-entropy (tokens per
    #                           chunk): never materializes [B,S,V] logits;
    #                           under grad each chunk's dX and dW are made
    #                           in the forward scan, nothing is recomputed
    remat: bool = True
    # jax.checkpoint_policies name; "nothing_saveable" = full recompute
    remat_policy: str = "nothing_saveable"
    attn_impl: str = "reference"  # reference | flash

    def __post_init__(self):
        import jax.numpy as jnp
        if self.param_dtype is None:
            self.param_dtype = jnp.float32
        if self.num_kv_heads is None:
            self.num_kv_heads = self.num_heads
        self.kda_layers = tuple(self.kda_layers)
        self.full_attn_layers = tuple(self.full_attn_layers)
        self.layer_types = list(self.layer_types)   # as JSON has it

    @property
    def head_dim(self) -> int:
        return self.attn_head_dim or self.hidden_size // self.num_heads

    @property
    def effective_mlp_bias(self) -> bool:
        """mlp_bias falls back to use_bias — the single source of truth
        for init / forward / num_params (GPT-J splits them)."""
        return self.use_bias if self.mlp_bias is None else self.mlp_bias

    # ---- a stack of kinds (kda_layers / full_attn_layers) --------------
    @property
    def linear_attn_config(self) -> dict:
        """The published ``linear_attn_config`` group of the model as
        built (lists, as JSON has them)."""
        return {"full_attn_layers": list(self.full_attn_layers),
                "head_dim": self.kda_head_dim,
                "kda_layers": list(self.kda_layers),
                "num_heads": self.kda_num_heads,
                "short_conv_kernel_size": self.kda_conv_size}

    def layer_kinds(self) -> list[tuple[str, str]] | None:
        """(token mixer, channel mixer) of each layer, ``kda`` | ``mla``
        and ``dense`` | ``moe``; None for a model of one kind of layer."""
        if not (self.kda_layers or self.full_attn_layers):
            return None
        kinds = []
        for n in range(1, self.num_layers + 1):
            if (n in self.kda_layers) == (n in self.full_attn_layers):
                raise ValueError(
                    f"layer {n} is in both or neither of kda_layers "
                    f"{self.kda_layers} and full_attn_layers "
                    f"{self.full_attn_layers}")
            kinds.append(("kda" if n in self.kda_layers else "mla",
                          "dense" if n <= self.first_k_dense_replace
                          or self.num_experts <= 0 else "moe"))
        return kinds

    def _kind_params(self) -> dict:
        """Parameters of each kind of mixer, as models/kimi_linear.py
        builds them; ``expert`` is ONE routed expert, ``moe`` everything
        of a routed layer but its routed experts."""
        d = self.hidden_size
        h, dk, r = self.kda_num_heads, self.kda_head_dim, self.kda_gate_rank
        inner = h * dk
        kda = (3 * d * inner + 3 * self.kda_conv_size * inner   # q, k, v
               + d * r + r * inner + inner + h                   # decay
               + d * h                                           # beta
               + d * r + r * inner + inner                       # out gate
               + dk + inner * d)                                 # norm, wo
        nh = self.num_heads
        qk = self.qk_nope_head_dim + self.qk_rope_head_dim
        mla = (d * nh * qk + d * (self.kv_lora_rank + self.qk_rope_head_dim)
               + self.kv_lora_rank
               + self.kv_lora_rank * nh * (self.qk_nope_head_dim
                                           + self.v_head_dim)
               + nh * self.v_head_dim * d)
        fe = self.moe_intermediate_size or self.intermediate_size
        return {"kda": kda, "mla": mla,
                "dense": 3 * d * self.intermediate_size,
                "expert": 3 * d * fe,
                "moe": (d * self.num_experts + self.num_experts
                        + 3 * d * fe * self.moe_num_shared_experts)}

    # ---- Mamba-2 among attention layers (layer_types) ------------------
    def _hybrid_params(self) -> int:
        """Embedding, head, norms and the layers of ``layer_types``, as
        models/granite_hybrid.py builds them (every layer with a SwiGLU)."""
        d, v, h = self.hidden_size, self.vocab_size, self.mamba_n_heads
        inner = h * self.mamba_d_head
        conv = inner + 2 * self.mamba_n_groups * self.mamba_d_state
        per = {"mamba": (d * (inner + conv + h)          # z | xBC | dt
                         + (self.mamba_d_conv + self.mamba_conv_bias) * conv
                         + 3 * h + inner + inner * d),   # A, D, dt_bias, norm
               "attention": 2 * d * self.head_dim * (self.num_heads
                                                     + self.num_kv_heads)}
        n = v * d * (1 if self.tie_embeddings else 2) + d
        return n + sum(per[t] + 3 * d * self.intermediate_size + 2 * d
                       for t in self.layer_types)

    @property
    def held_experts(self) -> int:
        """Routed experts held here: all of them unless told a share."""
        return self.moe_held_experts or self.num_experts

    # ---- window and full attention layers, each routed (layer_types) ---
    @property
    def window_stack(self) -> bool:
        return "sliding_attention" in self.layer_types \
            or "full_attention" in self.layer_types

    def _window_stack_params(self, active: bool) -> int:
        """Embedding, untied head, norms and the layers of a window-and-
        full stack, as models/mellum.py builds them: grouped-query
        attention at ``head_dim`` and a router over ``num_experts`` in
        front of the ``held_experts`` held here. ``active``: a token's
        routed experts count as its ``moe_top_k`` times the share held."""
        d, v, hd = self.hidden_size, self.vocab_size, self.head_dim
        attn = 2 * d * hd * (self.num_heads + self.num_kv_heads)
        routed = (self.moe_top_k * self.held_experts / self.num_experts
                  if active else self.held_experts)
        layer = (attn + 2 * d + d * self.num_experts
                 + routed * 3 * d * self.moe_intermediate_size)
        n = v * d * (1 if self.tie_embeddings else 2) + d
        return int(n + len(self.layer_types) * layer)

    def _stack_params(self, kinds, active: bool) -> int:
        """Embedding, head, norms and the layers of a stack of kinds.
        ``active``: a token's routed experts count as the ``moe_top_k``
        it is routed to times the share of the experts held here (what
        this chip computes for it, under a balanced router)."""
        per = self._kind_params()
        d, v = self.hidden_size, self.vocab_size
        routed = (self.moe_top_k * self.held_experts / self.num_experts
                  if active else self.held_experts) if self.num_experts \
            else 0
        n = v * d + (0 if self.tie_embeddings else v * d) + d
        for mixer, channel in kinds:
            n += per[mixer] + 2 * d
            n += (per["dense"] if channel == "dense"
                  else per["moe"] + routed * per["expert"])
        return int(n)

    def num_params(self) -> int:
        """Analytic parameter count (embedding + layers + final norm),
        matching the trees the model's ``init`` builds exactly."""
        if self.window_stack:
            return self._window_stack_params(active=False)
        if self.layer_types:
            return self._hybrid_params()
        kinds = self.layer_kinds()
        if kinds is not None:
            return self._stack_params(kinds, active=False)
        d, f, v, L = (self.hidden_size, self.intermediate_size,
                      self.vocab_size, self.num_layers)
        nh_d = self.num_heads * self.head_dim
        kv = self.num_kv_heads * self.head_dim
        attn = d * nh_d + 2 * d * kv + nh_d * d  # wq, wk, wv, wo
        mlp = 3 * d * f if self.activation == "swiglu" else 2 * d * f
        if self.num_experts > 0:
            mlp = mlp * self.num_experts + d * self.num_experts  # + gate
            if self.moe_num_shared_experts > 0:
                # shared experts fused into one n-times-wider swiglu MLP
                # plus the sigmoid gate proj (d -> 1)
                mlp += 3 * d * f * self.moe_num_shared_experts + d
        n_norms = (1 if self.parallel_residual
                   and not self.parallel_dual_norm else 2)
        if self.sandwich_norm:
            n_norms += 2
        mlp_bias = self.effective_mlp_bias
        per_layer = attn + mlp + n_norms * d  # + ln scales
        if self.use_bias or self.attn_qkv_bias:
            per_layer += nh_d + 2 * kv      # qkv biases
        if self.use_bias:
            per_layer += d                  # wo bias
        if mlp_bias:
            per_layer += f + d              # w_up_b, w_down_b
            if self.activation == "swiglu":
                per_layer += f              # w_gate_b
        if self.norm_type == "layernorm":
            per_layer += n_norms * d        # ln biases
        embed = v * d + (0 if self.tie_embeddings else v * d)
        if not self.tie_embeddings and self.lm_head_bias:
            embed += v
        if self.embed_layernorm:
            embed += 2 * d
        pos = self.max_seq_len * d if self.position_embedding == "learned" else 0
        final_norm = d + (d if self.norm_type == "layernorm" else 0)
        gate = d + 1 if self.exit_gate else 0
        return embed + pos + L * per_layer + final_norm + gate

    def num_active_params(self) -> int:
        """Parameters a token actually computes with: dense models run
        everything; an MoE token runs only its top-k routed experts (the
        router projection and any shared experts always run). This is
        the MFU denominator — counting parked experts would credit the
        model with FLOPs it never executed."""
        if self.window_stack:
            return self._window_stack_params(active=True)
        kinds = self.layer_kinds()
        if kinds is not None:
            return self._stack_params(kinds, active=True)
        n = self.num_params()
        if self.num_experts <= 0:
            return n
        d, f = self.hidden_size, self.intermediate_size
        per_expert = 3 * d * f if self.activation == "swiglu" else 2 * d * f
        inactive = max(self.num_experts - self.moe_top_k, 0)
        return n - self.num_layers * inactive * per_expert

    def flops_per_token(self, seq_len: int, causal: bool = True) -> float:
        """Training FLOPs/token (fwd+bwd ~= 6*N_active + attention
        term), the standard MFU accounting. For MoE
        models N is :meth:`num_active_params` — top-k experts per
        token, not the full expert bank.

        ``causal=True`` (default — the PRIMARY number for every reported
        MFU) counts only the attention work a causal model performs: the
        average attended context is (s+1)/2, or bounded by the sliding
        window when one is configured. ``causal=False`` is the
        conventional full-attention accounting some frameworks report;
        at long sequence it flatters MFU ~2x and is kept only as a
        secondary figure.
        """
        n = self.num_active_params()
        s = seq_len
        if causal:
            w = self.sliding_window
            if w and w < s:
                # mean_i min(i+1, w): first w positions grow linearly,
                # the rest are window-bounded
                ctx = (w * (w + 1) / 2 + (s - w) * w) / s
            else:
                ctx = (s + 1) / 2
        else:
            ctx = s
        if self.window_stack:
            # a visible pair multiplies a key and a value of head_dim a
            # head (x3 training); the window bounds the first kind alone.
            # The embedding is a gather, not a matmul
            pair = 12 * self.num_heads * self.head_dim
            full = (s + 1) / 2 if causal else s
            n -= self.vocab_size * self.hidden_size
            return 6 * n + pair * sum(
                ctx if t == "sliding_attention" else full
                for t in self.layer_types)
        if self.layer_types:
            # an attention layer multiplies a key and a value of head_dim
            # a visible pair; a Mamba head writes and reads its [P, N]
            # state once a token (2 products of 2 P N FLOPs); x3 training.
            # A tied table is the head's matmul; an untied one's gather is
            # not a matmul
            attn = 12 * self.num_heads * self.head_dim * ctx
            ssd = 12 * self.mamba_n_heads * self.mamba_d_head \
                * self.mamba_d_state
            if not self.tie_embeddings:
                n -= self.vocab_size * self.hidden_size
            return 6 * n + sum(ssd if t == "mamba" else attn
                               for t in self.layer_types)
        kinds = self.layer_kinds()
        if kinds is not None:
            # latent attention multiplies a key of qk width and a value
            # of v width a visible pair (2 matmuls, x3 for training); a
            # KDA head reads, corrects and writes its [dk, dv] state once
            # a token (3 products of 2 dk dv FLOPs, x3 for training)
            mla = 6 * self.num_heads * ctx * (
                self.qk_nope_head_dim + self.qk_rope_head_dim
                + self.v_head_dim)
            kda = 18 * self.kda_num_heads * self.kda_head_dim ** 2
            # the embedding is a gather, not a matmul
            n -= self.vocab_size * self.hidden_size
            return 6 * n + sum(kda if mixer == "kda" else mla
                               for mixer, _ in kinds)
        attn_flops = 12 * self.num_layers * self.hidden_size * ctx
        # a looped stack runs everything but the embedding's gather once a
        # pass (the layers, the final norm, the head and the gate)
        again = (self.total_ut_steps - 1) * (
            n - (0 if self.tie_embeddings
                 else self.vocab_size * self.hidden_size))
        return 6 * (n + again) + self.total_ut_steps * attn_flops


class Model(Protocol):
    config: ModelConfig

    def init(self, rng: jax.Array) -> PyTree: ...

    def apply(self, params: PyTree, tokens: jax.Array, **kw) -> jax.Array: ...

    def loss(self, params: PyTree, batch: Any, **kw) -> jax.Array: ...

    def partition_rules(self) -> Rules: ...


_MODEL_REGISTRY: dict[str, Callable[..., Any]] = {}


def register_model(name: str):
    def deco(cls):
        _MODEL_REGISTRY[name] = cls
        return cls
    return deco


def get_model_class(name: str):
    if name not in _MODEL_REGISTRY:
        raise KeyError(
            f"unknown model {name!r}; available: {sorted(_MODEL_REGISTRY)}")
    return _MODEL_REGISTRY[name]
