"""Configuration dataclasses for the repro framework.

A run is fully described by (ModelConfig, ShapeConfig, MeshConfig,
TrainConfig) — together these form the portable part of the environment
manifest (core/manifest.py).  The host binding (device kind, real mesh) is
attached late, mirroring the paper's container-image / host-driver split.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Any


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


@dataclass(frozen=True)
class ModelConfig:
    """Architecture description.  One instance per assigned arch.

    ``family`` selects the block layout:
      dense   — decoder-only, attention+MLP blocks
      moe     — decoder-only, attention+MoE blocks
      ssm     — decoder-only, Mamba2 (SSD) blocks, attention-free
      hybrid  — Mamba2 blocks + a globally *shared* attention block every
                ``attn_every`` blocks (zamba2)
      encdec  — encoder-decoder (whisper); frontend stubbed
      vlm     — decoder-only with cross-attention blocks every
                ``cross_every`` layers attending to stubbed patch embeddings
    """

    name: str
    family: str  # dense | moe | ssm | hybrid | encdec | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0          # 0 -> d_model // n_heads
    # --- MoE ---
    n_experts: int = 0
    top_k: int = 0
    capacity_factor: float = 1.25
    router_aux_weight: float = 0.01
    # routing: "softmax" (top-k of the softmax, renormalised) or "sigmoid"
    # (top-k of sigmoid scores plus a per-expert selection bias, weights
    # the chosen scores, renormalised when ``route_norm``)
    router: str = "softmax"
    route_norm: bool = True
    route_scale: float = 1.0
    n_shared_experts: int = 0  # always-on experts beside the routed ones
    # the experts this chip holds of the routed ``n_experts`` (expert
    # parallelism): ``experts_held`` from ``expert_offset`` on; 0 = all
    experts_held: int = 0
    expert_offset: int = 0
    n_dense_layers: int = 0    # leading layers with a gated MLP, no experts
    d_ff_dense: int = 0        # their width (``d_ff`` is the experts')
    # --- SSM (Mamba2 / SSD) ---
    ssm_state: int = 0
    ssm_head_dim: int = 64
    ssm_expand: int = 2
    ssm_groups: int = 1
    conv_width: int = 4
    ssd_chunk: int = 256
    # --- hybrid (zamba2) ---
    attn_every: int = 6        # a shared attention block after every N-1 mamba blocks
    # --- vlm ---
    cross_every: int = 5       # one cross-attn block per `cross_every` self layers
    n_image_tokens: int = 1600
    # --- encdec (whisper) ---
    n_encoder_layers: int = 0
    n_audio_frames: int = 1500  # encoder sequence length for non-train shapes
    decoder_train_len: int = 448
    # --- common ---
    rope_theta: float = 500_000.0
    norm_eps: float = 1e-5
    tie_embeddings: bool = False
    qk_norm: bool = False      # qwen3-style per-head q/k RMSNorm
    # --- attention pattern (afmoe) ---
    sliding_window: int = 0    # keys q_pos - window < k_pos <= q_pos; 0 = none
    global_every: int = 0      # every N-th layer attends the whole context
    nope_global: bool = False  # whole-context layers take no RoPE
    attn_gate: bool = False    # attention output x sigmoid(x @ wg)
    sandwich_norm: bool = False  # RMSNorm after each branch, before its add
    embed_scale: bool = False  # embedding x sqrt(d_model)
    dtype: str = "bfloat16"
    # ref: citation string from the assignment table
    source: str = ""

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or (self.d_model // self.n_heads)

    @property
    def held_experts(self) -> int:
        return self.experts_held or self.n_experts

    def layer_windows(self) -> tuple[int, ...]:
        """Each layer's attention window (0: the whole context): with a
        ``sliding_window``, every ``global_every``-th layer is whole and
        the others windowed."""
        if not self.sliding_window:
            return (0,) * self.n_layers
        return tuple(
            0 if self.global_every and (i + 1) % self.global_every == 0
            else self.sliding_window for i in range(self.n_layers))

    @property
    def paged_only(self) -> bool:
        """True for a configuration only the paged serving step computes
        (windows, output gates, sandwich norms and the rest of afmoe's
        layer); the other paths raise rather than compute another model."""
        return bool(self.sliding_window or self.attn_gate
                    or self.sandwich_norm or self.embed_scale
                    or self.n_dense_layers or self.n_shared_experts
                    or self.router != "softmax"
                    or self.held_experts != self.n_experts)

    @property
    def padded_vocab(self) -> int:
        """Embedding tables padded to a multiple of 256 so the vocab dim is
        always shardable over a 16-wide model axis (Megatron-style)."""
        return _round_up(self.vocab_size, 256)

    @property
    def d_inner(self) -> int:
        return self.ssm_expand * self.d_model

    @property
    def n_ssm_heads(self) -> int:
        return self.d_inner // self.ssm_head_dim

    def param_count(self) -> int:
        """Analytic parameter count (used for 6·N·D model-FLOPs)."""
        from repro.models import stack  # local import to avoid cycles

        return stack.param_count(self)

    def active_param_count(self) -> int:
        """Parameters touched per token (MoE: top_k experts only)."""
        from repro.models import stack

        return stack.param_count(self, active_only=True)


@dataclass(frozen=True)
class ShapeConfig:
    """One input-shape cell from the assignment."""

    name: str       # train_4k | prefill_32k | decode_32k | long_500k
    kind: str       # train | prefill | decode
    seq_len: int
    global_batch: int

    @property
    def is_train(self) -> bool:
        return self.kind == "train"


SHAPES: dict[str, ShapeConfig] = {
    "train_4k": ShapeConfig("train_4k", "train", 4_096, 256),
    "prefill_32k": ShapeConfig("prefill_32k", "prefill", 32_768, 32),
    "decode_32k": ShapeConfig("decode_32k", "decode", 32_768, 128),
    "long_500k": ShapeConfig("long_500k", "decode", 524_288, 1),
}


@dataclass(frozen=True)
class MeshConfig:
    """Logical device mesh.  ``data`` carries batch + FSDP, ``model`` carries
    tensor/expert parallelism, ``pod`` (optional) is the cross-pod DP axis."""

    shape: tuple[int, ...] = (16, 16)
    axes: tuple[str, ...] = ("data", "model")

    @property
    def n_devices(self) -> int:
        n = 1
        for s in self.shape:
            n *= s
        return n

    @property
    def batch_axes(self) -> tuple[str, ...]:
        return tuple(a for a in self.axes if a in ("pod", "data"))

    @property
    def model_axes(self) -> tuple[str, ...]:
        return tuple(a for a in self.axes if a == "model")

    def axis_size(self, name: str) -> int:
        if name not in self.axes:
            return 1
        return self.shape[self.axes.index(name)]


SINGLE_POD = MeshConfig((16, 16), ("data", "model"))
MULTI_POD = MeshConfig((2, 16, 16), ("pod", "data", "model"))
# Reduced meshes for CPU-measured benchmarks / tests.
TINY_MESH = MeshConfig((1, 1), ("data", "model"))


@dataclass(frozen=True)
class TrainConfig:
    learning_rate: float = 3e-4
    warmup_steps: int = 100
    total_steps: int = 1000
    weight_decay: float = 0.1
    beta1: float = 0.9
    beta2: float = 0.95
    eps: float = 1e-8
    grad_clip: float = 1.0
    z_loss: float = 1e-4
    seed: int = 0
    # remat: 'none' | 'full' | 'selective' (save only block boundaries)
    remat: str = "full"
    # microbatching (gradient accumulation) — 0 disables
    microbatches: int = 0
    # gradient compression: 'none' | 'int8_ef'
    grad_compress: str = "none"


@dataclass(frozen=True)
class RunConfig:
    """Everything needed to lower one cell."""

    model: ModelConfig
    shape: ShapeConfig
    mesh: MeshConfig = SINGLE_POD
    train: TrainConfig = field(default_factory=TrainConfig)
    # sharding rule-set name (parallel/rules.py): 'baseline' is the
    # paper-faithful portable default, others are perf-pass variants.
    rules: str = "baseline"
    use_pallas: bool = False

    def cell_id(self) -> str:
        pods = "mp" if "pod" in self.mesh.axes else "sp"
        return f"{self.model.name}/{self.shape.name}/{pods}/{self.rules}"


def reduced(model: ModelConfig, **overrides: Any) -> ModelConfig:
    """A tiny same-family config for CPU smoke tests."""
    small: dict[str, Any] = dict(
        n_layers=min(model.n_layers, 4),
        d_model=128,
        n_heads=4,
        n_kv_heads=min(model.n_kv_heads, 2),
        head_dim=32,
        d_ff=256,
        vocab_size=512,
        n_experts=min(model.n_experts, 8) if model.n_experts else 0,
        top_k=min(model.top_k, 2) if model.top_k else 0,
        ssm_state=min(model.ssm_state, 16) if model.ssm_state else 0,
        ssm_head_dim=32,
        ssd_chunk=16,
        n_image_tokens=16,
        n_encoder_layers=2 if model.n_encoder_layers else 0,
        n_audio_frames=32,
        decoder_train_len=16,
        attn_every=2,
        cross_every=2,
        n_dense_layers=min(model.n_dense_layers, 1),
        d_ff_dense=256 if model.d_ff_dense else 0,
        experts_held=min(model.experts_held, 4) if model.experts_held else 0,
        expert_offset=0,
        sliding_window=min(model.sliding_window, 16),
        name=model.name + "-smoke",
    )
    small.update(overrides)
    return dataclasses.replace(model, **small)
