"""Config schema for the model zoo + CIM execution + parallelism.

One ``ModelConfig`` instance fully describes an architecture; the registry in
``configs/registry.py`` maps ``--arch <id>`` to a config. ``reduced()`` builds
the same-family shrunken config used by CPU smoke tests.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple


@dataclasses.dataclass(frozen=True)
class CIMModelConfig:
    """How the macro executes the model's linears (off = ideal digital)."""

    mode: str = "off"            # "off" | "qat" | "sim"
    policy: str = "paper_sac"    # SAC policy name (core/sac.py)
    act_clip_sigmas: float = 4.0  # activation scale = clip at k*rms (per-layer
                                  # Vref fit; abs-max if <= 0)
    use_kernel: bool = False      # route deployed sim-mode matmuls through
                                  # the fused-act-quant Pallas path
                                  # (ops.cim_matmul_deployed, DESIGN.md §12);
                                  # default jnp behavioural path on CPU


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    n_experts: int = 0           # the router's width: every expert of the layer
    top_k: int = 2
    n_shared: int = 0            # always-on shared experts (deepseek-v2: 2)
    capacity_factor: float = 1.25
    # the experts this device holds, [held_first, held_first + n_held) of
    # the router's n_experts (0: all of them). Under expert parallelism a
    # layer computes its held experts' part of the routed sum; the other
    # devices' parts are not added here (models/moe.py)
    n_held: int = 0
    held_first: int = 0
    norm_topk: bool = True       # renormalise the top-k gates to sum to 1
    routed_scale: float = 1.0    # the routed sum's factor (deepseek-v2)

    @property
    def held(self) -> int:
        return self.n_held or self.n_experts


@dataclasses.dataclass(frozen=True)
class SSMConfig:
    d_state: int = 128
    headdim: int = 64
    expand: int = 2
    conv_width: int = 4
    ngroups: int = 1
    chunk: int = 256


@dataclasses.dataclass(frozen=True)
class MLAConfig:
    q_lora: Optional[int] = 1536     # None: one query projection, no latent
    kv_lora: int = 512
    rope_head_dim: int = 64
    nope_head_dim: int = 128
    v_head_dim: int = 128


@dataclasses.dataclass(frozen=True)
class RopeScaling:
    """YaRN rope scaling (arXiv:2309.00071), fields named as the published
    ``rope_scaling`` keys. Sets the MLA rope frequencies and softmax scale
    (``layers.rope_freqs``, ``attention.mla_softmax_scale``)."""

    type: str = "yarn"
    factor: float = 1.0
    original_max_position_embeddings: int = 4096
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                  # dense|moe|ssm|hybrid|encdec|vlm|vit
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0            # 0 -> d_model // n_heads
    qkv_bias: bool = False       # qwen2
    use_rope: bool = True        # vit/whisper use absolute positions instead
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    tie_embeddings: bool = False

    moe: Optional[MoEConfig] = None
    ssm: Optional[SSMConfig] = None
    mla: Optional[MLAConfig] = None
    rope_scaling: Optional[RopeScaling] = None

    # moe: the first n_dense_layers blocks have a dense SwiGLU FFN of width
    # dense_d_ff in place of the expert layer (deepseek-v2:
    # first_k_dense_replace); n_layers counts them
    n_dense_layers: int = 0
    dense_d_ff: int = 0

    # hybrid (zamba2): repeating super-block of (attn_period-1) mamba layers
    # + 1 *shared-weight* attention layer.
    attn_period: int = 0

    # encoder-decoder (whisper): n_layers is the decoder depth.
    n_enc_layers: int = 0
    n_frames: int = 1500         # encoder memory length (stub frontend)

    # vlm (pixtral): first n_patches positions come from the (stub) vision
    # frontend as precomputed patch embeddings.
    n_patches: int = 0

    # vit (paper's CIFAR demo)
    image_size: int = 32
    patch_size: int = 4
    n_classes: int = 10

    max_seq_len: int = 8192
    dtype: str = "bfloat16"
    attn_impl: str = "einsum"     # "einsum" | "kernel": einsum is the dense
                                  # masked-softmax oracle; "kernel" routes
                                  # cached GQA attention through the Pallas
                                  # length-aware decode / flash prefill
                                  # kernels (O(len) decode, interpret-mode
                                  # validated on CPU)
    kv_cache_int8: bool = False   # quantized GQA cache (per-token/head scale):
                                  # halves serving HBM, the paper's quantized-
                                  # storage spirit applied to the cache
    fuse_layer: bool = False      # decode-shaped dense blocks run as ONE
                                  # Pallas program per layer (megakernel:
                                  # QKV + rope + length-aware attention +
                                  # O + SwiGLU chained in VMEM,
                                  # kernels/fused_step.py, DESIGN.md §15);
                                  # requires mode off, or sim with deployed
                                  # planes (in-kernel cim_matmul_fused math)
    remat: bool = True
    scan_layers: bool = True

    cim: CIMModelConfig = CIMModelConfig()

    @property
    def hd(self) -> int:
        if self.head_dim:
            return self.head_dim
        return self.d_model // self.n_heads if self.n_heads else 0

    @property
    def attention_free(self) -> bool:
        return self.family == "ssm"

    @property
    def subquadratic(self) -> bool:
        """Eligible for the long_500k shape (DESIGN.md §6)."""
        return self.family in ("ssm", "hybrid")

    def param_count(self) -> int:
        """Approximate parameter count (embeddings + blocks)."""
        d, f, v = self.d_model, self.d_ff, self.vocab_size
        hd = self.hd
        emb = v * d * (1 if self.tie_embeddings else 2)
        per_layer = 0
        if self.family in ("dense", "vlm", "encdec"):
            qkv = d * hd * (self.n_heads + 2 * self.n_kv_heads) + self.n_heads * hd * d
            mlp = 3 * d * f
            per_layer = qkv + mlp
            if self.family == "encdec":
                per_layer += qkv  # cross attention (approx)
        elif self.family == "moe":
            m = self.moe
            if self.mla is not None:
                a = self.mla
                qh = self.n_heads * (a.nope_head_dim + a.rope_head_dim)
                qkv = (
                    (d * qh if a.q_lora is None else d * a.q_lora + a.q_lora * qh)
                    + d * (a.kv_lora + a.rope_head_dim)
                    + a.kv_lora * self.n_heads * (a.nope_head_dim + a.v_head_dim)
                    + self.n_heads * a.v_head_dim * d
                )
            else:
                qkv = d * hd * (self.n_heads + 2 * self.n_kv_heads) + self.n_heads * hd * d
            per_layer = qkv + 3 * d * f * (m.held + m.n_shared) + d * m.n_experts
            lead = self.n_dense_layers * (qkv + 3 * d * self.dense_d_ff - per_layer)
            return emb + self.n_layers * per_layer + lead
        elif self.family == "ssm":
            s = self.ssm
            di = s.expand * d
            per_layer = d * (2 * di + 2 * s.ngroups * s.d_state + di // s.headdim) + di * d
        elif self.family == "hybrid":
            s = self.ssm
            di = s.expand * d
            mamba = d * (2 * di + 2 * s.ngroups * s.d_state + di // s.headdim) + di * d + 3 * d * f
            n_mamba = self.n_layers - self.n_layers // self.attn_period
            qkv = d * hd * (self.n_heads + 2 * self.n_kv_heads) + self.n_heads * hd * d
            return emb + n_mamba * mamba + qkv + 3 * d * f  # attn shared once
        elif self.family == "vit":
            per_layer = 4 * d * d + 2 * d * f
        n = self.n_layers + (self.n_enc_layers if self.family == "encdec" else 0)
        return emb + n * per_layer

    def reduced(self) -> "ModelConfig":
        """Same-family tiny config for CPU smoke tests."""
        small = dataclasses.replace(
            self,
            name=self.name + "-smoke",
            n_layers=min(self.n_layers, 2 if self.attn_period == 0 else 2 * max(self.attn_period, 1)),
            d_model=256,
            n_heads=4,
            n_kv_heads=min(self.n_kv_heads, 2) if self.n_kv_heads < self.n_heads else 4,
            head_dim=64,
            d_ff=512,
            vocab_size=512,
            n_enc_layers=min(self.n_enc_layers, 2),
            n_frames=32,
            n_patches=min(self.n_patches, 8) if self.n_patches else 0,
            max_seq_len=128,
            dtype="float32",
            # 128 positions lie far inside the context YaRN stretches from
            # (original_max_position_embeddings): plain rope
            rope_scaling=None,
        )
        if self.moe is not None:
            small = dataclasses.replace(
                small,
                moe=dataclasses.replace(self.moe, n_experts=min(self.moe.n_experts, 8),
                                        top_k=min(self.moe.top_k, 2)),
                d_ff=128,
            )
        if self.ssm is not None:
            small = dataclasses.replace(
                small,
                ssm=dataclasses.replace(self.ssm, d_state=16, headdim=32, chunk=32),
            )
        if self.mla is not None:
            small = dataclasses.replace(
                small,
                mla=MLAConfig(q_lora=64, kv_lora=64, rope_head_dim=16, nope_head_dim=32,
                              v_head_dim=32),
            )
        if self.attn_period:
            small = dataclasses.replace(small, attn_period=min(self.attn_period, 3),
                                        n_layers=6)
        return small


@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    """One assigned input-shape cell."""

    name: str
    seq_len: int
    global_batch: int
    kind: str  # "train" | "prefill" | "decode"


SHAPES: Tuple[ShapeConfig, ...] = (
    ShapeConfig("train_4k", 4096, 256, "train"),
    ShapeConfig("prefill_32k", 32768, 32, "prefill"),
    ShapeConfig("decode_32k", 32768, 128, "decode"),
    ShapeConfig("long_500k", 524288, 1, "decode"),
)


def get_shape(name: str) -> ShapeConfig:
    for s in SHAPES:
        if s.name == name:
            return s
    raise KeyError(name)
