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
    #                                      positions (the model has none, or
    #                                      a family rotates by its own tables)
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
    # numerics
    param_dtype: Any = None   # set to jnp dtype in __post_init__
    loss_chunk: int = 0       # >0: fused chunked cross-entropy (tokens per
    #                           chunk): never materializes [B,S,V] logits;
    #                           under grad each chunk's dX and dW are made
    #                           in the forward scan, nothing is recomputed
    remat: bool = True
    # jax.checkpoint_policies name, "save_attn_ffn" or "segments";
    # "nothing_saveable" = a layer recomputed whole but for what a kernel
    # declares kept (the flash kernel's output and row log-sum-exp:
    # models/transformer.py _remat_policy)
    remat_policy: str = "nothing_saveable"
    attn_impl: str = "reference"  # reference | flash

    def __post_init__(self):
        import jax.numpy as jnp
        if self.param_dtype is None:
            self.param_dtype = jnp.float32
        if self.num_kv_heads is None:
            self.num_kv_heads = self.num_heads

    @property
    def head_dim(self) -> int:
        return self.attn_head_dim or self.hidden_size // self.num_heads

    @property
    def effective_mlp_bias(self) -> bool:
        """mlp_bias falls back to use_bias — the single source of truth
        for init / forward / num_params (GPT-J splits them)."""
        return self.use_bias if self.mlp_bias is None else self.mlp_bias

    def num_params(self) -> int:
        """Analytic parameter count (embedding + layers + final norm),
        matching the trees the model's ``init`` builds exactly."""
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
        return embed + pos + L * per_layer + final_norm

    def num_active_params(self) -> int:
        """Parameters a token actually computes with: dense models run
        everything; an MoE token runs only its top-k routed experts (the
        router projection and any shared experts always run). This is
        the MFU denominator — counting parked experts would credit the
        model with FLOPs it never executed."""
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
        return (6 * self._matmul_params()
                + self._mixer_flops(seq_len, causal))

    # the two terms of flops_per_token a family may count its own way
    def _matmul_params(self) -> float:
        """N of the 6 N: the parameters a token is multiplied with in a
        forward pass. The standard accounting takes every active one, the
        embedding table among them."""
        return self.num_active_params()

    def _mixer_flops(self, seq_len: int, causal: bool) -> float:
        """What the token mixers add to the 6 N, a token: an attention
        layer multiplies a key and a value of the hidden width a visible
        pair (2 matmuls, x3 for training)."""
        return 12 * self.num_layers * self.hidden_size * mean_context(
            seq_len, causal, self.sliding_window)


def mean_context(seq_len: int, causal: bool, window: int | None = None):
    """Positions a token attends to, the mean over a sequence: all of them
    in the full accounting; under the causal one (s + 1) / 2, or what a
    window leaves of that."""
    s, w = seq_len, window
    if not causal:
        return s
    if w and w < s:
        # mean_i min(i+1, w): first w positions grow linearly, the rest
        # are window-bounded
        return (w * (w + 1) / 2 + (s - w) * w) / s
    return (s + 1) / 2


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
