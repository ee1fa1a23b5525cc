"""Model / shape configuration schema and registry."""
from __future__ import annotations

import dataclasses
import importlib
from typing import Dict, Optional, Tuple


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                  # decoder | encdec | hybrid | xlstm
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    d_ff: int
    vocab_size: int
    # attention
    attention_type: str = "gqa"  # gqa | mla
    qkv_bias: bool = False
    window_size: Optional[int] = None        # SWA window (None = full attn)
    local_global_pattern: int = 0            # N local layers per 1 global
    qk_norm: bool = False
    rope_theta: float = 10_000.0
    # YaRN rotary scaling (MLA's rotary part; factor 0 = plain RoPE):
    # interpolated frequencies between the beta_fast / beta_slow
    # corrections, cos/sin scaled by mscale(mscale) / mscale(mscale_all_dim)
    # and the softmax by mscale(mscale_all_dim)**2
    rope_yarn_factor: float = 0.0
    rope_yarn_original_max_pos: int = 4096
    rope_yarn_beta_fast: float = 32.0
    rope_yarn_beta_slow: float = 1.0
    rope_yarn_mscale: float = 1.0
    rope_yarn_mscale_all_dim: float = 0.0
    logit_softcap: Optional[float] = None
    # MLA (minicpm3 / deepseek-v2)
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # MLP
    mlp_gated: bool = True
    act: str = "silu"
    # MoE
    num_experts: int = 0
    num_experts_per_tok: int = 0
    num_shared_experts: int = 0
    moe_d_ff: int = 0
    first_dense_layers: int = 0
    capacity_factor: float = 1.25
    # routing as DeepSeek-V2's config states it: softmax scores, then
    # top-k over all experts ("greedy") or over the experts of the
    # ``moe_topk_group`` best of ``moe_n_group`` groups, a group scored by
    # its best expert ("group_limited_greedy"); the k weights are
    # renormalised (``moe_norm_topk_prob``) or else scaled by
    # ``moe_routed_scale``
    moe_topk_method: str = "greedy"
    moe_n_group: int = 1
    moe_topk_group: int = 1
    moe_norm_topk_prob: bool = True
    moe_routed_scale: float = 1.0
    # expert share: this device holds routed experts [experts_offset,
    # experts_offset + experts_held) of the router's num_experts and
    # computes only their part of the layer (0 = it holds them all)
    experts_held: int = 0
    experts_offset: int = 0
    # SSM / hybrid
    ssm_state: int = 0
    ssm_headdim: int = 64
    ssm_expand: int = 2
    ssm_conv: int = 4
    ssm_chunk: int = 256         # SSD / mLSTM chunk length
    ssm_decay_bf16: bool = False # store intra-chunk decay matrices in bf16
    attn_every: int = 0          # zamba2: one shared attn block per N mamba
    lora_rank: int = 0           # zamba2 shared-block adapters
    slstm_every: int = 0         # xlstm: one sLSTM per N blocks
    # encoder-decoder (whisper)
    encoder_layers: int = 0
    encoder_frames: int = 1500
    # vlm (paligemma)
    num_prefix_tokens: int = 0
    # pipeline parallelism: preferred stage count for the layer stack.
    # 1 = no pipelining.  Deep configs (qwen2-72b, deepseek-v2-236b) opt
    # in; launch code decides whether the mesh actually carries a "stage"
    # axis (TrainPlan/make_train_step only pipeline when told to, so
    # smoke tests and stage-less meshes are unaffected by this field).
    pipeline_stages: int = 1
    # execution policy
    tie_embeddings: bool = True
    norm_eps: float = 1e-6
    param_dtype: str = "bfloat16"
    matmul_mode: str = "bf16"    # bf16 | bp8 | bp8_lowrank | bp8_fused | fp8
    # KV-cache storage format: "none" keeps bf16 k/v; "bp8" stores int8
    # Bent-Pyramid level codes + per-token/per-head f32 scales and decodes
    # through the fused Pallas attention kernel (GQA/MQA only, not MLA —
    # the latent cache is already compressed).
    kv_quant: str = "none"
    remat: bool = True
    scan_layers: bool = True
    attn_chunk: int = 1024       # KV chunk for memory-efficient attention

    @property
    def sub_quadratic(self) -> bool:
        """Eligible for long_500k (no full-attention over the whole seq in
        every layer): SSM/hybrid families, or SWA-dominant transformers."""
        if self.family in ("hybrid", "xlstm"):
            return True
        return self.window_size is not None

    @property
    def groups(self) -> Tuple[int, int]:
        """(n_groups, layers_per_group) for scan over heterogeneous stacks."""
        if self.local_global_pattern:
            per = self.local_global_pattern + 1
            assert self.num_layers % per == 0
            return self.num_layers // per, per
        if self.attn_every:
            assert self.num_layers % self.attn_every == 0
            return self.num_layers // self.attn_every, self.attn_every
        return self.num_layers, 1


@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    name: str
    kind: str            # train | prefill | decode
    seq_len: int
    global_batch: int


SHAPES: Dict[str, ShapeConfig] = {
    "train_4k": ShapeConfig("train_4k", "train", 4_096, 256),
    "prefill_32k": ShapeConfig("prefill_32k", "prefill", 32_768, 32),
    "decode_32k": ShapeConfig("decode_32k", "decode", 32_768, 128),
    "long_500k": ShapeConfig("long_500k", "decode", 524_288, 1),
}

ARCH_IDS = (
    "gemma3_12b", "h2o_danube_1p8b", "minicpm3_4b", "qwen2_72b",
    "granite_moe_1b", "deepseek_v2_236b", "whisper_base", "paligemma_3b",
    "zamba2_2p7b", "xlstm_1p3b",
)


def get_config(arch: str, smoke: bool = False) -> ModelConfig:
    arch = arch.replace("-", "_").replace(".", "p")
    mod = importlib.import_module(f"repro.configs.{arch}")
    return mod.smoke_config() if smoke else mod.config()


def shape_applicable(cfg: ModelConfig, shape: ShapeConfig,
                     seq_shards: int = 1) -> Tuple[bool, str]:
    """Whether an (arch, shape) cell is runnable; reason if not.

    Full-attention archs can't fit long_500k on a data×model×stage layout
    — unless the launcher brings sequence parallelism (``seq_shards`` > 1):
    ring attention over a "seq" mesh axis shards the half-million-token KV
    cache across the ring, which is exactly the regime that used to be
    skipped.  Sub-quadratic archs never needed the ring (their state is
    O(1) in sequence length).
    """
    if (shape.name == "long_500k" and not cfg.sub_quadratic
            and seq_shards <= 1):
        return False, ("pure full-attention arch: long_500k needs "
                       "sequence parallelism (seq_shards > 1) or "
                       "sub-quadratic attention")
    return True, ""
