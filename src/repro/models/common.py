"""Model config + parameter-definition machinery.

A model is described by a pytree of ``ParamDef`` (shape, dtype, init, logical
axes).  The same tree drives:
  * real initialization (smoke tests, the train example),
  * ``jax.ShapeDtypeStruct`` stand-ins (the multi-pod dry-run),
  * sharding specs (logical axes -> physical mesh axes via the rules table).

Logical axis vocabulary (see distributed/sharding.py for the physical rules):
  batch   - global batch                     -> ("pod","data") / ("data",)
  seq     - sequence (activations only)      -> "model" in seq-parallel attn
  embed   - d_model rows of weight matrices  -> "data"  (FSDP)
  heads   - attention head dim of weights    -> "model" (tensor parallel)
  kv      - kv-head dim                      -> "model" when divisible
  mlp     - FFN hidden dim                   -> "model"
  vocab   - vocabulary dim                   -> "model"
  experts - MoE expert dim                   -> "model" (expert parallel)
  layers  - scan-stacked layer dim           -> None (never sharded)
  conv/state/none - unsharded small dims
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                       # dense | moe | ssm | hybrid | audio | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: Optional[int] = None
    # nonlinearities (compiled into a repro.sfu.ActivationPlan — the paper's
    # knob; see sfu.compile_plan for the legacy-knob translation)
    activation: str = "silu"
    mlp_type: str = "swiglu"          # swiglu | geglu | mlp
    norm_type: str = "rmsnorm"        # rmsnorm | layernorm | nonparam_ln
    qkv_bias: bool = False
    act_impl: str = "exact"           # exact | jnp | kernel | fused (sfu.IMPLS)
    act_breakpoints: int = 32
    # explicit per-site plan pins: ((site_key, repro.sfu.ApproxSpec), ...),
    # applied last (last-match-wins) over the act_impl translation — e.g.
    # mamba2 pins ("ssm:silu", ApproxSpec(fn="silu", impl="exact")) because
    # SSM-input activations amplify approximation error through the
    # recurrence (EXPERIMENTS.md "SSM sensitivity" study).
    act_site_specs: tuple = ()
    pwl_softmax: bool = False         # PWL-exp softmax (paper Sec. V-B)
    # PWL table storage format ("f32" | "bf16" | "f16"): the paper's
    # multi-format tables (Sec. III); applies to every site compile_plan emits
    act_table_dtype: str = "f32"
    # backward implementation for fused-kernel sites ("fused" | "recompute"):
    # "fused" runs the Pallas backward kernels, which decode the per-segment
    # PWL *slope* in-kernel (the slope IS the activation derivative);
    # "recompute" is the pure-jnp rematerialization oracle — the escape
    # hatch if a fused backward misbehaves on some backend.  None defers to
    # the process default (fused; scoped via kernels.fused.use_impl_bwd).
    # build_train_step pins a non-None value for the whole train step.
    act_impl_bwd: Optional[str] = None
    # explicit repro.sfu.ActivationPlan — when set it IS the activation
    # resolution (the legacy act_impl/pwl_* knobs above are ignored);
    # when None, sfu.plan_for(cfg) translates the legacy knobs.
    act_plan: Any = None
    # attention pattern
    sliding_window: Optional[int] = None
    global_every: Optional[int] = None   # gemma3: 1 global per N layers
    rope_theta: float = 10000.0
    # MoE
    n_experts: int = 0
    n_active_experts: int = 0
    moe_d_ff: int = 0
    capacity_factor: float = 1.25
    # SSM (mamba2)
    ssm_state: int = 0
    ssm_head_dim: int = 64
    ssm_expand: int = 2
    ssm_conv_dim: int = 4
    ssm_chunk: int = 128
    attn_every: Optional[int] = None  # jamba: 1 attn layer per N (else mamba)
    moe_every: Optional[int] = None   # jamba: MoE FFN every N layers
    # encoder-decoder (whisper)
    is_encoder_decoder: bool = False
    n_encoder_layers: int = 0
    encoder_seq: int = 1500           # stub frame-embedding length
    # VLM
    n_vision_tokens: int = 0          # stub patch-embedding prefix length
    # numerics / structure
    dtype: Any = jnp.bfloat16
    remat: bool = True
    scan_layers: bool = True
    unroll_scans: bool = False  # dry-run probes: exact FLOP accounting
    causal_unroll: bool = True  # Perf H2: skip fully-masked causal kv blocks
    # Perf H3 small-model full-DP: None = auto from total params.  The dry-run
    # pins this from the FULL-depth config so shallow probes stay consistent.
    force_dp_only: object = None
    tie_embeddings: bool = False

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    @property
    def padded_vocab(self) -> int:
        """Vocab padded to a TP-friendly multiple (Megatron-style).  Logits
        over padded ids are masked to -inf in unembed(); targets never hit
        them.  vocab_size stays the logical vocabulary."""
        m = 256
        return -(-self.vocab_size // m) * m

    @property
    def layer_kinds(self) -> list[tuple[str, str]]:
        """Per-layer (mixer, ffn) kinds implementing the arch's interleave."""
        kinds = []
        for i in range(self.n_layers):
            if self.attn_every:  # jamba: attention in the middle of each block
                mixer = "attn" if i % self.attn_every == self.attn_every // 2 else "ssm"
            elif self.family == "ssm":
                mixer = "ssm"
            elif self.global_every:
                mixer = "attn_global" if (i + 1) % self.global_every == 0 else "attn_local"
            elif self.sliding_window:
                mixer = "attn_local"
            else:
                mixer = "attn"
            if self.moe_every:
                ffn = "moe" if i % self.moe_every == 1 else "dense"
            elif self.n_experts > 0:
                ffn = "moe"
            else:
                ffn = "dense"
            kinds.append((mixer, ffn))
        return kinds

    @property
    def period(self) -> int:
        """Smallest repeating period of layer kinds (scan unit)."""
        kinds = self.layer_kinds
        for p in range(1, len(kinds) + 1):
            if all(kinds[i] == kinds[i % p] for i in range(len(kinds))):
                if len(kinds) % p == 0:
                    return p
        return len(kinds)


@dataclasses.dataclass(frozen=True)
class ParamDef:
    shape: tuple[int, ...]
    logical_axes: tuple[Optional[str], ...]
    init: str = "normal"      # normal | zeros | ones | small_normal
    dtype: Any = jnp.float32  # master dtype (cast to cfg.dtype in forward)
    # inputs summed into each output ("normal" init draws std 1/sqrt(fan_in));
    # None means shape[-2], right for (.., in, out) matrices.  Attention
    # projections (D, H, dh) / (H, dh, D) set it: their shape[-2] is a head
    # count, which draws q/k ~sqrt(D/H) too large, saturates every softmax
    # row and makes the random model chaotic in its rounding.
    fan_in: Optional[int] = None

    def initializer(self, key, fan_in: Optional[int] = None):
        if self.init == "zeros":
            return jnp.zeros(self.shape, self.dtype)
        if self.init == "ones":
            return jnp.ones(self.shape, self.dtype)
        scale = 0.02 if self.init == "small_normal" else 1.0 / math.sqrt(
            fan_in or self.shape[0]
        )
        return (jax.random.normal(key, self.shape) * scale).astype(self.dtype)


def init_params(defs, rng) -> Any:
    """Materialize a ParamDef tree into real arrays (deterministic per-leaf)."""
    leaves, treedef = jax.tree_util.tree_flatten(
        defs, is_leaf=lambda x: isinstance(x, ParamDef)
    )
    keys = jax.random.split(rng, len(leaves))
    vals = []
    for k, d in zip(keys, leaves):
        fan_in = d.fan_in or (d.shape[-2] if len(d.shape) >= 2 else None)
        vals.append(d.initializer(k, fan_in))
    return jax.tree_util.tree_unflatten(treedef, vals)


def shape_structs(defs) -> Any:
    """ShapeDtypeStruct tree for .lower() — no allocation (dry-run path)."""
    return jax.tree_util.tree_map(
        lambda d: jax.ShapeDtypeStruct(d.shape, d.dtype),
        defs,
        is_leaf=lambda x: isinstance(x, ParamDef),
    )


def logical_specs(defs) -> Any:
    """Tree of logical-axis tuples matching the param tree."""
    return jax.tree_util.tree_map(
        lambda d: d.logical_axes, defs, is_leaf=lambda x: isinstance(x, ParamDef)
    )
