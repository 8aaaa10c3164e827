"""moonlight-16b-a3b [moe]: DeepSeek-V3 block — latent attention, 64
sigmoid-routed experts (top-6, bias-corrected selection) and 2 shared
experts after one dense layer.
[https://huggingface.co/moonshotai/Moonlight-16B-A3B/blob/main/config.json]

Assumed (not in the published config): the correction bias's update rate
gamma = 0.001 and the sequence-wise balance loss alpha = 1e-4 (the values
DeepSeek-V3 reports, arXiv:2412.19437); the optimizer (AdamW b1 0.9,
b2 0.95, wd 0.1 at a constant 3e-4, clip 1.0) — Moonlight itself was
trained with Muon (arXiv:2502.16982), which this repository lacks.
"""
import dataclasses

from repro.configs.base import ArchConfig, MLAConfig, MoEConfig

SOURCE = ("https://huggingface.co/moonshotai/Moonlight-16B-A3B/blob/main/"
          "config.json")
N_ROUTED = 64                   # n_routed_experts, the router's width
LR = 3e-4                       # assumed: constant AdamW rate


def _moe(n_held: int = N_ROUTED, offset: int = 0,
         router: int = N_ROUTED, d_ff_expert: int = 1408) -> MoEConfig:
    return MoEConfig(
        n_experts=n_held, top_k=6, d_ff_expert=d_ff_expert,
        dispatch="grouped", router_experts=router, expert_offset=offset,
        n_shared_experts=2, routed_scale=2.446, bias_update_rate=1e-3, seq_balance_loss=1e-4)


def config(n_layers: int = 27, n_held: int = N_ROUTED,
           vocab: int = 163_840) -> ArchConfig:
    """The published model; ``n_layers``, ``n_held`` and ``vocab`` give
    one chip's share of a deployment (the benchmark's
    ``moonlight-16b-a3b-ep8``: 5 layers, 8 of 64 experts, 20480 ids)."""
    return ArchConfig(
        arch_id="moonlight-16b-a3b",
        family="moe",
        n_layers=n_layers,
        d_model=2048,
        n_heads=16,
        n_kv_heads=16,
        d_ff=11_264,                # the dense layer's SwiGLU width
        vocab=vocab,
        source=SOURCE,
        d_head=192,                 # qk head dim (128 nope + 64 rope)
        rope_theta=50_000.0,
        ffn_type="swiglu",
        tie_embeddings=False,
        first_k_dense=1,
        decode_supported=False,     # no latent KV cache for serving yet
        mla=MLAConfig(kv_lora_rank=512, qk_nope_head_dim=128,
                      qk_rope_head_dim=64, v_head_dim=128),
        moe=_moe(n_held),
    )


def reduced(n_held: int = 4, offset: int = 0) -> ArchConfig:
    """CPU-test size: a dense layer then one MoE layer at d_model 256,
    8 routed experts of which ``n_held`` (from ``offset``) are held."""
    return dataclasses.replace(
        config(), n_layers=2, d_model=256, n_heads=4, n_kv_heads=4,
        d_ff=512, vocab=512, d_head=48,
        mla=MLAConfig(kv_lora_rank=64, qk_nope_head_dim=32,
                      qk_rope_head_dim=16, v_head_dim=32),
        moe=_moe(n_held, offset, router=8, d_ff_expert=128))
