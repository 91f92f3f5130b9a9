"""Model / run configuration system.

One :class:`ModelConfig` describes any architecture in the assigned pool
(dense, MoE, SSM, hybrid, encoder-only, VLM backbone); one
:class:`ShapeConfig` describes a workload shape cell (train_4k, prefill_32k,
decode_32k, long_500k); one :class:`RunConfig` binds them to training
hyperparameters (the mesh is ``train(..., mesh=)``'s argument).
"""
from __future__ import annotations

from dataclasses import dataclass, field, replace


@dataclass(frozen=True)
class MoEConfig:
    num_experts: int = 8
    top_k: int = 2
    d_ff_expert: int = 2048
    num_shared_experts: int = 0
    d_ff_shared: int = 0
    capacity_factor: float = 1.25
    router_dtype: str = "float32"
    # router scores: "softmax" over the experts, or "sigmoid" renormalised
    # over the top k (DeepSeek-V3's config.json key of the same name)
    scoring_func: str = "softmax"


@dataclass(frozen=True)
class MLAConfig:
    """DeepSeek-V3 multi-head latent attention dims."""
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128


@dataclass(frozen=True)
class SSMConfig:
    """Mamba2 / SSD block dims."""
    d_state: int = 128
    d_conv: int = 4
    expand: int = 2
    head_dim: int = 64
    n_groups: int = 1
    chunk_size: int = 256

    def d_inner(self, d_model: int) -> int:
        return self.expand * d_model

    def n_heads(self, d_model: int) -> int:
        return self.d_inner(d_model) // self.head_dim


@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                    # dense | moe | ssm | hybrid | audio | vlm
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0              # 0 -> d_model // num_heads
    # attention features
    qkv_bias: bool = False
    attn_logit_softcap: float = 0.0
    final_logit_softcap: float = 0.0
    sliding_window: int = 0        # >0: SWA width (all layers)
    local_global_pattern: int = 0  # >0: alternate local/global every N layers
    causal: bool = True            # False -> encoder (bidirectional)
    rope_theta: float = 10000.0
    mrope_sections: tuple[int, ...] = ()   # Qwen2-VL M-RoPE (t, h, w) splits
    # substructures
    moe: MoEConfig | None = None
    mla: MLAConfig | None = None
    ssm: SSMConfig | None = None
    # hybrid (Zamba2, arXiv:2411.15242): every layer is a Mamba2 layer; at
    # each of ``hybrid_layer_ids`` the k-th invocation of shared block
    # k % num_mem_blocks runs first, with its own LoRA adapter (rank
    # ``adapter_rank``) on the MLP and its own output ``linear``
    hybrid_layer_ids: list[int] = field(default_factory=list)
    num_mem_blocks: int = 0
    adapter_rank: int = 0
    # not read by the model: a regular period of shared attention blocks,
    # compared by the benchmark's configuration files (0 for every model)
    hybrid_attn_every: int = 0
    # norm / misc
    rms_eps: float = 1e-5
    tie_embeddings: bool = False
    act: str = "silu"              # silu | gelu
    dtype: str = "bfloat16"
    # modality frontend: "none" means token ids; "stub" means the input is a
    # precomputed [B, S, d_model] embedding (audio frames / vision patches)
    frontend: str = "none"
    remat: str = "full"            # none | full (activation checkpointing)
    attn_impl: str = "chunked"     # dense | chunked | pallas
    attn_chunk: int = 1024
    scan_layers: bool = True       # False: python-unrolled layer stack
    layer_barriers: bool = False   # insert optimization_barrier between
    #                                layers (profiling-slicing boundaries)
    loss_vocab_chunk: int = 0      # >0: stream CE over vocab chunks (no
    #                                [B,S,V] f32 logits materialization)
    moe_ep_shardmap: bool = False  # explicit expert-parallel dispatch via
    #                                shard_map (see mlp.moe_forward_ep)

    def __post_init__(self):
        # a list, so that it equals the list a JSON configuration states
        ids = list(self.hybrid_layer_ids)
        object.__setattr__(self, "hybrid_layer_ids", ids)
        if self.family == "hybrid":
            assert self.num_mem_blocks >= 1, self.num_mem_blocks
            assert ids == sorted(set(ids)) and ids and \
                0 <= ids[0] and ids[-1] < self.num_layers, \
                (ids, self.num_layers)

    @property
    def resolved_head_dim(self) -> int:
        if self.head_dim:
            return self.head_dim
        return self.d_model // self.num_heads if self.num_heads else 0

    @property
    def is_encoder_only(self) -> bool:
        return not self.causal

    @property
    def attention_free(self) -> bool:
        return self.family == "ssm"

    def scaled(self, **kw) -> "ModelConfig":
        return replace(self, **kw)

    # ---- analytical parameter counts (for 6·N·D model flops) ----
    def param_count(self) -> tuple[int, int]:
        """(total_params, active_params). Active differs for MoE."""
        d, L, V = self.d_model, self.num_layers, self.vocab_size
        hd = self.resolved_head_dim
        emb = V * d * (1 if self.tie_embeddings else 2)
        total = active = emb
        if self.family in ("ssm", "hybrid"):
            s = self.ssm or SSMConfig()
            di = s.d_inner(d)
            nh = s.n_heads(d)
            conv_ch = di + 2 * s.n_groups * s.d_state
            per = (d * (2 * di + 2 * s.n_groups * s.d_state + nh)  # in_proj
                   + (s.d_conv + 1) * conv_ch                      # conv, bias
                   + di * d                                        # out_proj
                   + 3 * nh + di + d)          # A, dt bias, D; norms
            total += L * per + d                          # final norm
            if self.family == "hybrid":
                f, r, k = self.d_ff, self.adapter_rank, \
                    len(self.hybrid_layer_ids)
                shared = (2 * d * self.num_heads * hd             # q
                          + 2 * 2 * d * self.num_kv_heads * hd    # k, v
                          + self.num_heads * hd * d               # o
                          + 3 * d * f + 2 * d + d)                # mlp, norms
                invocation = d * d + d * r + r * 2 * f    # linear, adapter
                total += self.num_mem_blocks * shared + k * invocation
            return total, total
        kvh = self.num_kv_heads
        if self.mla is not None:
            m = self.mla
            qk_hd = m.qk_nope_head_dim + m.qk_rope_head_dim
            attn = (d * m.q_lora_rank + m.q_lora_rank * self.num_heads * qk_hd
                    + d * (m.kv_lora_rank + m.qk_rope_head_dim)
                    + m.kv_lora_rank * self.num_heads
                    * (m.qk_nope_head_dim + m.v_head_dim)
                    + self.num_heads * m.v_head_dim * d)
        else:
            attn = (d * self.num_heads * hd + 2 * d * kvh * hd
                    + self.num_heads * hd * d)
        if self.moe is not None:
            mo = self.moe
            ff_dense = 3 * d * mo.d_ff_shared * mo.num_shared_experts
            ff_all = 3 * d * mo.d_ff_expert * mo.num_experts + ff_dense
            ff_active = 3 * d * mo.d_ff_expert * mo.top_k + ff_dense
            router = d * mo.num_experts
            total += L * (attn + ff_all + router + 2 * d)
            active += L * (attn + ff_active + router + 2 * d)
        else:
            ff = 3 * d * self.d_ff
            total += L * (attn + ff + 2 * d)
            active += L * (attn + ff + 2 * d)
        return total, active


@dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str                      # "train" | "prefill" | "decode"


TRAIN_4K = ShapeConfig("train_4k", 4096, 256, "train")
PREFILL_32K = ShapeConfig("prefill_32k", 32768, 32, "prefill")
DECODE_32K = ShapeConfig("decode_32k", 32768, 128, "decode")
LONG_500K = ShapeConfig("long_500k", 524288, 1, "decode")
SHAPES = {s.name: s for s in (TRAIN_4K, PREFILL_32K, DECODE_32K, LONG_500K)}


@dataclass(frozen=True)
class RunConfig:
    model: ModelConfig
    shape: ShapeConfig
    learning_rate: float = 3e-4
    warmup_steps: int = 100        # linear learning-rate warmup
    weight_decay: float = 0.1
    optimizer: str = "adamw"       # adamw | adafactor
    grad_clip: float = 1.0
    microbatch: int = 0            # 0 = no gradient accumulation
    gradient_compression: bool = False
    seed: int = 0
