"""zamba2-2.7b [hybrid]: Zamba2's block (arXiv:2411.15242) at 54 Mamba2
layers of d_model 2560, state 64, and shared blocks of 32 heads (kv 32)
with an MLP of 10240, vocabulary 32000.

The layer pattern is ``Zamba2Config``'s default ``layers_block_type`` in
transformers (tied there to Zyphra/Zamba2-2.7B): hybrid layers 6, 12, ...,
42, 47, 51.  Assumed, as this repo has no ``config.json`` of the 2.7B:
two shared blocks alternating (ABAB, the paper's; the installed default
of ``num_mem_blocks`` is 1), heads of 2*2560/32 = 160, rope on the shared
attention as in the 7B, adapters of rank 128 on the MLP only, Mamba heads
of 64 in one group, chunk 256, tied head."""
from .base import ModelConfig, SSMConfig

CONFIG = ModelConfig(
    name="zamba2-2.7b", family="hybrid", num_layers=54, d_model=2560,
    num_heads=32, num_kv_heads=32, head_dim=160, d_ff=10240,
    vocab_size=32000,
    ssm=SSMConfig(d_state=64, d_conv=4, expand=2, head_dim=64, n_groups=1,
                  chunk_size=256),
    hybrid_layer_ids=[6, 12, 18, 24, 30, 36, 42, 47, 51], num_mem_blocks=2,
    adapter_rank=128, tie_embeddings=True, act="gelu", attn_impl="blocked",
    attn_chunk=512,
)

SMOKE = ModelConfig(
    name="zamba2-smoke", family="hybrid", num_layers=5, d_model=64,
    num_heads=4, num_kv_heads=4, head_dim=32, d_ff=128, vocab_size=256,
    ssm=SSMConfig(d_state=16, d_conv=4, expand=2, head_dim=16, n_groups=1,
                  chunk_size=32),
    hybrid_layer_ids=[1, 3], num_mem_blocks=2, adapter_rank=8,
    tie_embeddings=True, act="gelu", attn_impl="blocked", attn_chunk=16,
    remat="none",
)
