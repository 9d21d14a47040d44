"""Zamba2-2.7B — Mamba2 backbone with two shared attention blocks
[arXiv:2411.15242; hf:Zyphra/Zamba2-2.7B].

54 Mamba2 layers (d_model 2560, d_inner 5120 = 80 heads of 64, state 64,
conv 4, one group).  Nine of them, at layers 6, 12, ..., 42, 47 and 51, are
hybrid: before the layer, shared block ``i mod 2`` runs on the
concatenation of the layer's input and the token embeddings (RMSNorm over
5120, attention from 5120 to 32 heads of 160 scaled by (160 / 2)^-1/2 with
no rotary embedding, RMSNorm, gated exact-GELU MLP of 10240 whose
gate/up projection carries a rank-128 LoRA of its own per invocation),
and a per-invocation 2560 x 2560 linear adds its output to that Mamba2
layer's input, not to the residual stream.  Tied embeddings.
"""

from ..models.config import ModelConfig, MAMBA2

CONFIG = ModelConfig(
    name="zamba2-2.7b",
    family="hybrid",
    n_layers=54,
    d_model=2560,
    n_heads=32,
    n_kv_heads=32,
    d_head=160,
    d_ff=10240,
    vocab=32000,
    pattern=((MAMBA2,),),
    hybrid_layers=(6, 12, 18, 24, 30, 36, 42, 47, 51),
    shared_blocks=2,
    adapter_rank=128,
    ssm_state=64,
    ssm_expand=2,
    ssm_heads=80,
    ssm_conv=4,
    ssm_chunk=256,
    tie_embeddings=True,
    source="arXiv:2411.15242; hf:Zyphra/Zamba2-2.7B",
)


def smoke() -> ModelConfig:
    """Every feature at a tiny width: two invocations of each block, and
    an irregular gap (3, then 2) like the published tail."""
    return CONFIG.scaled(
        n_layers=9, d_model=64, n_heads=4, n_kv_heads=4, d_head=32,
        d_ff=128, vocab=128, hybrid_layers=(1, 3, 6, 8), adapter_rank=8,
        ssm_state=16, ssm_heads=8, ssm_chunk=16)
