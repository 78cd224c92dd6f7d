"""Model / run configuration schema.

One :class:`ModelConfig` describes any of the assigned architecture
families (dense / moe / ssm / hybrid / encdec / vlm).  Parallelism and
step-shape knobs live in :class:`RunConfig` so the same model config can
be lowered for train / prefill / decode under different meshes.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    num_experts: int
    top_k: int
    capacity_factor: float = 1.25
    # tokens are routed within fixed-size groups (GShard-style) so the
    # dispatch einsum stays rectangular under SPMD
    group_size: int = 4096
    moe_every_n: int = 1          # 1 => every block is MoE
    shared_experts: int = 0


@dataclasses.dataclass(frozen=True)
class SSMConfig:
    state_dim: int = 128          # N
    head_dim: int = 64            # P
    expand: int = 2               # d_inner = expand * d_model
    n_groups: int = 1             # B/C groups (G)
    conv_width: int = 4
    chunk_size: int = 256         # SSD chunk length (Q)
    dt_min: float = 0.001
    dt_max: float = 0.1


@dataclasses.dataclass(frozen=True)
class EncDecConfig:
    encoder_layers: int = 6
    num_frames: int = 1500        # stub audio frontend output length


@dataclasses.dataclass(frozen=True)
class VLMConfig:
    num_patches: int = 576        # stub anyres vision frontend output length


@dataclasses.dataclass(frozen=True)
class HybridConfig:
    attn_every: int = 6           # shared attention block period (zamba2)


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                   # dense | moe | ssm | hybrid | encdec | vlm
    num_layers: int
    d_model: int
    num_heads: int                # 0 for attention-free
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: Optional[int] = None
    qk_norm: bool = False
    tie_embeddings: bool = False
    rope_theta: float = 10000.0
    norm_eps: float = 1e-6
    act: str = "silu"             # silu (swiglu) | gelu (plain mlp)
    norm: str = "rmsnorm"         # rmsnorm | layernorm
    pos_emb: str = "rope"         # rope | learned | sinusoidal | none
    moe: Optional[MoEConfig] = None
    ssm: Optional[SSMConfig] = None
    encdec: Optional[EncDecConfig] = None
    vlm: Optional[VLMConfig] = None
    hybrid: Optional[HybridConfig] = None
    # Granite-4.0-H style hybrid (family "hybrid" with this set): the kind
    # of each layer, "mamba" or "attention", each layer followed by its
    # own MLP; attention layers carry their own weights
    layer_types: Optional[Tuple[str, ...]] = None
    # muP multipliers (None / 1.0: the plain model).  The embedded tokens
    # are scaled by embedding_multiplier (TransformerLM: sqrt(d_model)
    # when None), each residual branch by residual_multiplier, the logits
    # divided by logits_scaling, and attention scores by
    # attention_multiplier (None: head_dim ** -0.5)
    embedding_multiplier: Optional[float] = None
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0
    attention_multiplier: Optional[float] = None
    max_seq_len: int = 131072
    dtype: str = "bfloat16"       # activations/weights compute dtype
    # sub-quadratic attention available? (long_500k eligibility)
    subquadratic: bool = False

    @property
    def resolved_head_dim(self) -> int:
        if self.head_dim is not None:
            return self.head_dim
        return self.d_model // max(self.num_heads, 1)

    @property
    def attention_layers(self) -> int:
        """How many layers hold attention (with a KV cache)."""
        if self.layer_types is not None:
            return self.layer_types.count("attention")
        if self.family == "hybrid":
            return self.num_layers // self.hybrid.attn_every
        return self.num_layers if self.num_heads else 0

    def param_count(self) -> int:
        """Approximate parameter count N (used for 6·N·D MODEL_FLOPS)."""
        d, v = self.d_model, self.vocab_size
        hd = self.resolved_head_dim
        total = v * d                      # embed
        if not self.tie_embeddings:
            total += v * d                 # lm head
        per_layer_attn = d * (self.num_heads * hd) + d * hd * self.num_kv_heads * 2 \
            + (self.num_heads * hd) * d if self.num_heads else 0
        if self.act == "silu":
            per_layer_mlp = 3 * d * self.d_ff
        else:
            per_layer_mlp = 2 * d * self.d_ff
        n_attn_layers = self.num_layers
        n_mlp_layers = self.num_layers
        if self.family == "ssm":
            cfg = self.ssm
            d_in = cfg.expand * d
            conv_dim = d_in + 2 * cfg.n_groups * cfg.state_dim
            nh = d_in // cfg.head_dim
            per_ssm = (d * (2 * d_in + 2 * cfg.n_groups * cfg.state_dim + nh)
                       + conv_dim * cfg.conv_width + 3 * nh + d_in
                       + d_in * d)
            return total + self.num_layers * per_ssm
        if self.family == "hybrid":
            cfg = self.ssm
            d_in = cfg.expand * d
            conv_dim = d_in + 2 * cfg.n_groups * cfg.state_dim
            nh = d_in // cfg.head_dim
            per_ssm = (d * (2 * d_in + 2 * cfg.n_groups * cfg.state_dim + nh)
                       + conv_dim * cfg.conv_width + 3 * nh + d_in
                       + d_in * d)
            if self.layer_types is not None:
                n_attn = self.attention_layers
                return (total + (self.num_layers - n_attn) * per_ssm
                        + n_attn * per_layer_attn
                        + self.num_layers * per_layer_mlp)
            shared_attn = per_layer_attn + per_layer_mlp
            return total + self.num_layers * per_ssm + shared_attn
        if self.moe is not None:
            per_layer_mlp = (3 * d * self.d_ff) * self.moe.num_experts \
                + d * self.moe.num_experts  # router
            if self.moe.shared_experts:
                per_layer_mlp += 3 * d * self.d_ff * self.moe.shared_experts
        total += n_attn_layers * per_layer_attn + n_mlp_layers * per_layer_mlp
        if self.family == "encdec":
            # encoder blocks + decoder cross-attention
            total += self.encdec.encoder_layers * (per_layer_attn + per_layer_mlp)
            total += self.num_layers * per_layer_attn  # cross attn
        return total

    def active_param_count(self) -> int:
        """Active params per token (MoE: only top_k experts count)."""
        if self.moe is None:
            return self.param_count()
        d = self.d_model
        full = self.param_count()
        all_experts = self.num_layers * 3 * d * self.d_ff * self.moe.num_experts
        active = self.num_layers * 3 * d * self.d_ff * (
            self.moe.top_k + self.moe.shared_experts)
        return full - all_experts + active


@dataclasses.dataclass(frozen=True)
class ParamLayout:
    """Init-time parameter layout — fusion legality decided at rest.

    The fused multi-op lowerings (kernels/fused.py) consume *concatenated*
    weight tensors: ``[wq|wk|wv]`` for the norm→q/k/v prologue and
    ``[wi|wg]`` for the norm→swiglu pair.  Concatenating per call is free
    at train/prefill scale but a net traffic loss at decode (rows = B: a
    weight-sized materialization to save a token-sized round trip), which
    is why PR 4 kept all seq-path fusions off the decode tick.  This plan
    moves the decision to where it is free: parameters are *persisted* in
    the fused layout at init, the hot loop only takes views.

    ``attn_qkv`` stores one ``wqkv = [wq|wk|wv]`` tensor per attention
    sublayer; ``mlp_swiglu`` stores one ``wig = [wi|wg]`` tensor per dense
    (and MoE shared-expert) swiglu MLP.  Either layout is *readable* by
    every consumer through the accessors in ``models/common.py`` —
    views/slices for unfused math, the whole tensor for fused kernels —
    so checkpoints in one layout load into models planned for the other
    (checkpoint/manager.py migrates at the flat-leaf level).
    """

    attn_qkv: bool = False
    mlp_swiglu: bool = False

    @classmethod
    def plan(cls, cfg: "ModelConfig", policy) -> "ParamLayout":
        """The ONE place the layout is decided, driven by the policy the
        model resolved: a fusing policy (``ExecutionPolicy.fuses()``)
        gets the concatenated layout wherever a fused lowering can
        consume it (rmsnorm prologues only — layernorm models keep the
        per-matrix layout)."""
        if not policy.fuses() or cfg.norm != "rmsnorm":
            return cls()
        return cls(attn_qkv=cfg.num_heads > 0,
                   mlp_swiglu=cfg.act == "silu")


#: the per-matrix layout every pre-ISSUE-5 checkpoint carries
LEGACY_LAYOUT = ParamLayout()


@dataclasses.dataclass(frozen=True)
class ParallelConfig:
    """How the model maps onto the mesh (DP/FSDP/TP/EP/SP)."""

    fsdp: bool = True              # shard params/opt-state over 'data'
    seq_shard_acts: bool = True    # saved residuals seq-sharded over 'model'
    # decode cache mesh layout: batch_heads | batch_seq | seq_all
    # (see parallel/sharding.py — batch_seq when kv heads don't divide
    # the model axis; seq_all for batch=1 long-context)
    cache_layout: str = "batch_heads"
    grad_accum: int = 1            # microbatch accumulation steps
    remat: str = "full"            # full | dots | none
    grad_compression: str = "none" # none | bf16 | int8_ef
    attn_chunk_q: int = 512
    attn_chunk_kv: int = 1024
    # §Perf lever: fold the causal triangle so masked-out blocks are never
    # computed (see models/attention.py)
    causal_folding: bool = False
    # §Perf lever: sharding-constrain pre-repeat K/V on the kv-head axis.
    # Baseline True (the naive layout); False removes the per-layer
    # involuntary replication all-gathers GSPMD inserts when
    # num_kv_heads < model-axis size (see EXPERIMENTS.md §Perf).
    constrain_kv_pre_repeat: bool = True
    # §Perf lever: constrain attention/MLP partial-sum outputs to the
    # seq-sharded layout *before* the residual add, so GSPMD lowers the
    # TP combine as reduce-scatter (half the wire of all-reduce) instead
    # of all-reduce + dynamic-slice.
    rs_outputs: bool = False
    # Beyond-paper serving lever: store the attention KV cache as int8
    # with per-(token, head) f32 scales — halves the decode memory term
    # (cache reads dominate long-context decode).  TransformerLM only.
    kv_cache_int8: bool = False
    # Route full-sequence attention through the Pallas flash kernel
    # (kernels/attention.py) instead of the jnp chunked path.  This is
    # the TPU execution path; CPU tests run it in interpret mode.  The
    # dry-run keeps the jnp path (compilable for the CPU placeholder
    # backend).
    use_pallas_attn: bool = False
    # Lowering policy for every registry-dispatched hot spot (norms,
    # reduce, attention kernel): an IsaMode value, "auto" (cheapest legal
    # variant for isa_dialect, per structural_cost), or None for the
    # seed-equivalent split — XLA library lowering for model norms, the
    # target-native variant on the Pallas attention path.
    isa_mode: Optional[str] = None
    isa_dialect: Optional[str] = None   # defaults to the framework TARGET
    # Fused-epilogue gate for the norm→projection and residual→norm hot
    # pairs (kernels/fused.py): True forces the fused lowerings, False
    # forces the unfused sequence, None (default) fuses exactly when the
    # policy mode is "auto" — the structural-cost ranking then picks the
    # variant whose hbm_bytes dropped by an activation round trip.
    fuse_epilogues: Optional[bool] = None
    # Weight-precision axis (ISSUE 7): "int8" retargets the hot fused
    # lowerings to their quantized twins (registry precision variants) —
    # int8 weights + per-channel scales dequantized in VMEM, so the
    # weight stream never rides HBM at f32 width.  None/"f32" keeps the
    # f32 rows.  Orthogonal to kv_cache_int8 (the cache axis).
    weight_precision: Optional[str] = None

    def execution_policy(self):
        """Resolve this config's ExecutionPolicy — the ONE place mode
        strings are decided; call sites only thread the result."""
        from repro.core.dialect import TARGET
        from repro.core.registry import ExecutionPolicy
        dialect = self.isa_dialect or TARGET.name
        if self.isa_mode is not None:
            return ExecutionPolicy(mode=self.isa_mode, dialect=dialect,
                                   kernel_mode=self.isa_mode,
                                   fuse=self.fuse_epilogues,
                                   precision=self.weight_precision)
        # Native lowerings are pinned to the framework TARGET; under a
        # foreign dialect the kernel path must degrade to a legal variant
        # ("auto") instead of requesting an unlowerable native kernel.
        kernel_mode = "native" if dialect == TARGET.name else "auto"
        return ExecutionPolicy(mode="library", dialect=dialect,
                               kernel_mode=kernel_mode,
                               fuse=self.fuse_epilogues,
                               precision=self.weight_precision)


@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    """One assigned input-shape cell."""

    name: str                      # train_4k | prefill_32k | decode_32k | long_500k
    kind: str                      # train | prefill | decode
    seq_len: int
    global_batch: int


SHAPES = {
    "train_4k": ShapeConfig("train_4k", "train", 4096, 256),
    "prefill_32k": ShapeConfig("prefill_32k", "prefill", 32768, 32),
    "decode_32k": ShapeConfig("decode_32k", "decode", 32768, 128),
    "long_500k": ShapeConfig("long_500k", "decode", 524288, 1),
}


def shape_applicable(model: ModelConfig, shape: ShapeConfig) -> bool:
    """long_500k only for sub-quadratic archs (DESIGN.md §5)."""
    if shape.name == "long_500k":
        return model.subquadratic
    return True
