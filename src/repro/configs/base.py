"""Architecture configuration system.

Every assigned architecture (plus the paper's own 3DGAN) is described by a
single frozen dataclass.  Configs are registered by id and selectable from
every launcher via ``--arch <id>``.
"""
from __future__ import annotations

import dataclasses
import importlib
from typing import Optional, Tuple

# ---------------------------------------------------------------------------
# Config dataclasses
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    """``n_experts`` counts the experts whose weights this device holds.

    With ``dispatch="grouped"`` (DeepSeek-V3 routing, arXiv:2412.19437:
    sigmoid scores, top-k of score + correction bias, normalised gates)
    the router scores ``router_experts`` experts (0: ``n_experts``), this
    device holds experts ``[expert_offset, expert_offset + n_experts)``
    of them and returns only their part of the layer plus the shared
    experts; no token is dropped.  ``dispatch="capacity"`` is the
    GShard-style softmax router with one-hot capacity dispatch that holds
    every expert and drops tokens past ``capacity_factor``."""
    n_experts: int
    top_k: int
    d_ff_expert: int
    capacity_factor: float = 1.25
    router_z_loss: float = 1e-3
    load_balance_loss: float = 1e-2
    dispatch: str = "capacity"       # capacity | grouped
    router_experts: int = 0          # grouped: experts the router scores
    expert_offset: int = 0           # grouped: first expert held here
    n_shared_experts: int = 0        # grouped: one SwiGLU of n * d_ff_expert
    routed_scale: float = 1.0        # grouped: routed_scaling_factor
    bias_update_rate: float = 0.0    # grouped: gamma of the correction bias
    seq_balance_loss: float = 0.0    # grouped: alpha of the sequence-wise loss

    @property
    def n_router(self) -> int:
        return self.router_experts or self.n_experts


@dataclasses.dataclass(frozen=True)
class MLAConfig:
    """Multi-head latent attention (DeepSeek-V2, arXiv:2405.04434), with
    the query uncompressed (q = h W_q)."""
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim


@dataclasses.dataclass(frozen=True)
class SSMConfig:
    state_dim: int = 64          # N (SSD state size per head)
    head_dim: int = 64           # P (channels per SSM head)
    expand: int = 2              # d_inner = expand * d_model
    chunk: int = 256             # chunked-scan block length
    conv_width: int = 4          # short causal conv width


@dataclasses.dataclass(frozen=True)
class ArchConfig:
    """Config for one architecture (transformer backbone semantics)."""

    arch_id: str
    family: str                  # dense | moe | ssm | hybrid | vlm | audio
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    source: str = ""             # citation (arXiv id / model card)

    # attention details
    d_head: int = 0              # 0 -> d_model // n_heads
    qkv_bias: bool = False
    rope_theta: float = 10_000.0
    mrope: bool = False          # qwen2-vl multimodal 3-axis rope
    mrope_sections: Tuple[int, int, int] = (16, 24, 24)  # t/h/w split of d_head/2
    sliding_window: int = 0      # 0 -> full causal attention

    # ffn details
    ffn_type: str = "swiglu"     # swiglu | gelu | relu2
    norm_type: str = "rmsnorm"   # rmsnorm | layernorm
    tie_embeddings: bool = False

    # family extensions
    moe: Optional[MoEConfig] = None
    ssm: Optional[SSMConfig] = None
    mla: Optional[MLAConfig] = None
    # leading layers that keep a dense FFN of width d_ff before the MoE
    # layers (DeepSeek's first_k_dense_replace)
    first_k_dense: int = 0

    # enc-dec (audio)
    is_encoder_decoder: bool = False
    n_encoder_layers: int = 0
    max_source_positions: int = 1500    # whisper: mel frames / 2
    max_target_positions: int = 448

    # hybrid (zamba2): one shared attention block applied every k layers
    shared_attn_every: int = 0

    # ssm/hybrid layer pattern ("m"=mamba2, "s"=slstm, "x"=mlstm, "a"=attn)
    layer_pattern: str = ""

    # serving
    decode_supported: bool = True
    subquadratic: bool = False   # can serve long_500k natively

    # kernel routing: replace the pure-JAX attention / SSD-scan training
    # paths with the Pallas kernels (tiles/chunks come from the shared
    # autotune registry; interpret-mode off-TPU, compiled on TPU)
    use_pallas_attn: bool = False
    use_pallas_ssm: bool = False

    def __post_init__(self):
        if self.d_head == 0:
            object.__setattr__(self, "d_head", self.d_model // self.n_heads)

    @property
    def q_dim(self) -> int:
        return self.n_heads * self.d_head

    @property
    def kv_dim(self) -> int:
        return self.n_kv_heads * self.d_head

    def param_count(self) -> int:
        """Analytic parameter count (embedding + blocks + head)."""
        c = self
        n = c.vocab * c.d_model                       # embedding
        if not self.tie_embeddings:
            n += c.vocab * c.d_model                  # lm head
        if c.first_k_dense:
            dense = dataclasses.replace(c, moe=None)
            n += _block_params(dense) * c.first_k_dense
        n += _block_params(c) * (c.n_layers - c.first_k_dense)
        if c.is_encoder_decoder:
            n += _block_params(c, cross=False) * c.n_encoder_layers
        return n

    def active_param_count(self) -> int:
        """Params touched per token (MoE: only top_k experts)."""
        if self.moe is None:
            return self.param_count()
        c, m = self, self.moe
        dense = self.param_count()
        expert_p = 3 * c.d_model * m.d_ff_expert
        inactive = (m.n_experts - m.top_k) * expert_p * (
            c.n_layers - c.first_k_dense)
        return dense - inactive


def _block_params(c: ArchConfig, cross: bool = False) -> int:
    """Approximate per-block parameter count."""
    attn = c.d_model * c.q_dim + 2 * c.d_model * c.kv_dim + c.q_dim * c.d_model
    if c.mla is not None:
        a = c.mla
        attn = (c.d_model * c.n_heads * a.qk_head_dim
                + c.d_model * (a.kv_lora_rank + a.qk_rope_head_dim)
                + a.kv_lora_rank
                + a.kv_lora_rank * c.n_heads * (a.qk_nope_head_dim
                                                + a.v_head_dim)
                + c.n_heads * a.v_head_dim * c.d_model)
    if c.family == "ssm":
        d_in = (c.ssm.expand if c.ssm else 2) * c.d_model
        return 2 * (c.d_model * 2 * d_in)           # rough: mlstm/slstm proj
    if c.moe is not None:
        ffn = c.moe.n_experts * 3 * c.d_model * c.moe.d_ff_expert
        ffn += c.d_model * c.moe.n_router           # router
        ffn += c.moe.n_shared_experts * 3 * c.d_model * c.moe.d_ff_expert
    elif c.ffn_type == "swiglu":
        ffn = 3 * c.d_model * c.d_ff
    else:
        ffn = 2 * c.d_model * c.d_ff
    if c.family == "hybrid" and c.ssm is not None:
        d_inner = c.ssm.expand * c.d_model
        attn = 2 * c.d_model * d_inner + d_inner * c.d_model
        ffn = 0
    if cross:
        attn *= 2
    return attn + ffn + 2 * c.d_model


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

ARCH_IDS = (
    "whisper-base",
    "dbrx-132b",
    "qwen2-vl-72b",
    "granite-20b",
    "nemotron-4-15b",
    "zamba2-1.2b",
    "olmoe-1b-7b",
    "xlstm-125m",
    "qwen2-1.5b",
    "phi4-mini-3.8b",
    "moonlight-16b-a3b",
    "calo3dgan",                 # the paper's own architecture
)

_MODULE_FOR = {a: a.replace("-", "_").replace(".", "_") for a in ARCH_IDS}


def get_config(arch_id: str) -> ArchConfig:
    if arch_id not in _MODULE_FOR:
        raise KeyError(f"unknown arch '{arch_id}'; known: {sorted(_MODULE_FOR)}")
    mod = importlib.import_module(f"repro.configs.{_MODULE_FOR[arch_id]}")
    return mod.config()


def reduced_config(arch_id: str) -> ArchConfig:
    """Reduced (smoke-test) variant of the same family: <=2 layers,
    d_model<=512, <=4 experts."""
    mod = importlib.import_module(f"repro.configs.{_MODULE_FOR[arch_id]}")
    if hasattr(mod, "reduced"):
        return mod.reduced()
    c = mod.config()
    kw = dict(
        n_layers=2,
        d_model=256,
        n_heads=4,
        n_kv_heads=min(c.n_kv_heads, 4) if c.n_kv_heads > 1 else 1,
        d_head=64,
        d_ff=512 if c.d_ff else 0,
        vocab=512,
        n_encoder_layers=2 if c.is_encoder_decoder else 0,
    )
    if c.mrope:
        kw["mrope_sections"] = (8, 12, 12)      # sums to d_head//2 = 32
    if c.moe is not None:
        kw["moe"] = dataclasses.replace(
            c.moe, n_experts=4, top_k=min(c.moe.top_k, 2), d_ff_expert=256)
    if c.ssm is not None:
        kw["ssm"] = dataclasses.replace(c.ssm, state_dim=32, head_dim=32, chunk=64)
    if c.layer_pattern:
        kw["layer_pattern"] = c.layer_pattern[:2]
    if c.shared_attn_every:
        kw["shared_attn_every"] = 2
    return dataclasses.replace(c, **kw)


# ---------------------------------------------------------------------------
# Input shapes (assigned)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class InputShape:
    name: str
    seq_len: int
    global_batch: int
    kind: str                    # train | prefill | decode


INPUT_SHAPES = {
    "train_4k": InputShape("train_4k", 4_096, 256, "train"),
    "prefill_32k": InputShape("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": InputShape("decode_32k", 32_768, 128, "decode"),
    "long_500k": InputShape("long_500k", 524_288, 1, "decode"),
}
