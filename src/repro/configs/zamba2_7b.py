"""zamba2-7b [hybrid]: Zyphra/Zamba2-7B-Instruct (arXiv:2411.15242), as its
``config.json`` states it: 81 Mamba2 layers of d_model 3584 (expand 2, 112
heads of 64, 2 groups, state 64, conv 4 with bias, chunk 256); at the 13
``hybrid_layer_ids`` one of two shared transformer blocks (ABAB) reads
concat(h, embedding) of width 7168: 32 heads of 224 with rope over the
whole head at theta 10000, and an exact-GELU gated MLP 3584 -> 2x14336 ->
3584 whose gate_up carries a rank-128 adapter per invocation; vocabulary
32000, tied head.

Attention runs in causal query blocks of 512 (``attn_impl="blocked"``),
each recomputed in the backward pass, so a 4096-token row's scores are
never held whole."""
from .base import ModelConfig, SSMConfig

HYBRID_LAYER_IDS = [6, 11, 17, 23, 29, 35, 41, 47, 53, 59, 65, 71, 77]

CONFIG = ModelConfig(
    name="zamba2-7b", family="hybrid", num_layers=81, d_model=3584,
    num_heads=32, num_kv_heads=32, head_dim=224, d_ff=14336,
    vocab_size=32000,
    ssm=SSMConfig(d_state=64, d_conv=4, expand=2, head_dim=64, n_groups=2,
                  chunk_size=256),
    hybrid_layer_ids=HYBRID_LAYER_IDS, num_mem_blocks=2, adapter_rank=128,
    tie_embeddings=True, act="gelu", attn_impl="blocked", attn_chunk=512,
)

# irregular hybrid positions, both shared blocks (block 0 twice), an
# empty run of Mamba layers between two invocations and none after the last
SMOKE = ModelConfig(
    name="zamba2-7b-smoke", family="hybrid", num_layers=6, d_model=64,
    num_heads=4, num_kv_heads=4, head_dim=32, d_ff=128, vocab_size=256,
    ssm=SSMConfig(d_state=16, d_conv=4, expand=2, head_dim=16, n_groups=2,
                  chunk_size=32),
    hybrid_layer_ids=[2, 4, 5], num_mem_blocks=2, adapter_rank=8,
    tie_embeddings=True, act="gelu", attn_impl="blocked", attn_chunk=16,
    remat="none",
)
