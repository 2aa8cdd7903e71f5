"""Config system: model/arch configs, input shapes, and the registry.

Every assigned architecture gets one file in this package defining a
``ModelConfig``; ``get_config(arch_id)`` returns it and
``reduced_config(arch_id)`` returns a CPU-smoke-test-sized variant of the
same family (same code paths, tiny dims).
"""
from __future__ import annotations

import dataclasses
import importlib
from dataclasses import dataclass, field, replace
from typing import Any, Dict, Optional, Tuple

# ---------------------------------------------------------------------------
# Input shapes (assigned): name -> (seq_len, global_batch, kind)
#   kind: "train" lowers train_step; "decode" lowers serve_step (1 new token
#   against a KV cache of seq_len); "prefill" lowers a prefill forward.
# ---------------------------------------------------------------------------
SHAPES: Dict[str, Tuple[int, int, str]] = {
    "train_4k": (4_096, 256, "train"),
    "prefill_32k": (32_768, 32, "prefill"),
    "decode_32k": (32_768, 128, "decode"),
    "long_500k": (524_288, 1, "decode"),
}


@dataclass(frozen=True)
class MoEConfig:
    n_experts: int = 0                 # routed experts: the router's outputs
    top_k: int = 1
    d_ff_expert: int = 0
    n_shared_experts: int = 0          # shared (always-on) experts, run as
                                       # one MLP of n_shared * d_ff_expert
    router_jitter: float = 0.0
    expert_parallel: bool = False      # EP all-to-all instead of expert-dim TP
    capacity_factor: float = 1.25      # tokens/expert cap multiplier (the
                                       # training path; serving is dropless)
    norm_topk_prob: bool = True        # renormalise the top-k gates to sum 1
    experts_held: int = 0              # routed experts this chip holds
                                       # (0 = all): the chip's share under
                                       # expert parallelism
    first_held: int = 0                # index of the first held expert

    @property
    def held(self) -> int:
        return self.experts_held or self.n_experts


@dataclass(frozen=True)
class SSMConfig:
    version: int = 1                   # 1 = mamba1 selective scan, 2 = mamba2 SSD
    d_state: int = 16
    d_conv: int = 4
    expand: int = 2
    dt_rank: int = 0                   # 0 -> ceil(d_model/16)
    head_dim: int = 64                 # mamba2 head dim
    chunk: int = 256                   # mamba2 SSD chunk length


@dataclass(frozen=True)
class YarnConfig:
    """YaRN rotary scaling (arXiv:2309.00071) as DeepSeek-V2 publishes it:
    ``inv_freq`` mixes the base frequencies and the ones slowed by
    ``factor`` along a linear ramp between the correction dims of
    ``beta_fast`` and ``beta_slow`` rotations over
    ``original_max_position``; the softmax scale gains
    ``(0.1 * mscale_all_dim * ln(factor) + 1)^2``. The published
    ``mscale`` equals ``mscale_all_dim``, which makes the cos/sin scale 1."""
    factor: float = 40.0
    original_max_position: int = 4096
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale_all_dim: float = 0.707


@dataclass(frozen=True)
class MLAConfig:
    q_lora_rank: int = 768             # 0: a direct q projection (no LoRA)
    kv_lora_rank: int = 256
    qk_nope_head_dim: int = 64
    qk_rope_head_dim: int = 32
    v_head_dim: int = 64
    yarn: Optional[YarnConfig] = None  # YaRN on the rope dims; None = plain



@dataclass(frozen=True)
class ClusterKVConfig:
    """The paper's technique as an attention backend (see core/clusterkv.py)."""
    enabled: bool = False
    embed_dim: int = 3                 # PCA embedding dim (paper: d = 1..3)
    block_q: int = 128                 # query tile (MXU aligned)
    block_k: int = 128                 # key tile
    blocks_per_query: int = 16         # top-B key blocks kept per query block
    local_window_blocks: int = 1       # always-kept local diagonal blocks
    decode_clusters: int = 16          # top-c clusters gathered at decode
    use_pallas: bool = False           # kernels/block_attention for the tiles
                                       # (interpret-mode on CPU; Mosaic on TPU)
    decode_backend: str = "auto"       # plan-decode attend: "xla" | "pallas"
                                       # | "auto" (cost-model pick)


@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                        # dense | moe | ssm | hybrid | encdec | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    d_head: int = 0                    # 0 -> d_model // n_heads
    qkv_bias: bool = False
    rope_theta: float = 10_000.0
    swa_window: int = 0                # sliding-window attention; 0 = full
    tie_embeddings: bool = False
    norm_eps: float = 1e-5
    # family extensions
    moe: Optional[MoEConfig] = None
    ssm: Optional[SSMConfig] = None
    mla: Optional[MLAConfig] = None
    # leading layers whose FFN is a dense MLP of d_ff (DeepSeek's
    # first_k_dense_replace); the rest take the MoE FFN
    first_k_dense: int = 0
    # hybrid (zamba2-like): shared attention block every `shared_attn_every`
    shared_attn_every: int = 0
    # enc-dec (whisper-like)
    n_enc_layers: int = 0
    # vlm/audio stub frontends: inputs are precomputed embeddings
    embedding_inputs: bool = False
    # attention backend: "dense" | "clusterkv"
    clusterkv: ClusterKVConfig = field(default_factory=ClusterKVConfig)
    # training knobs
    optimizer: str = "adamw"           # adamw | adafactor
    remat: bool = True
    remat_policy: str = "full"         # full | dots (save matmul outputs)
    loss_chunk: int = 0                # 0 = unchunked CE; else tokens/chunk
    dtype: str = "bfloat16"            # compute dtype
    param_dtype: str = "float32"       # master param dtype (bf16 for 100B+)
    # sub-quadratic long-context backend for long_500k ("swa"|"clusterkv"|"ssm"|"skip")
    long_context: str = "clusterkv"

    @property
    def head_dim(self) -> int:
        return self.d_head or (self.d_model // self.n_heads)

    def with_(self, **kw: Any) -> "ModelConfig":
        return replace(self, **kw)


ARCH_IDS = [
    "llava-next-34b",
    "qwen2-0.5b",
    "minicpm3-4b",
    "h2o-danube-3-4b",
    "mistral-large-123b",
    "falcon-mamba-7b",
    "whisper-medium",
    "llama4-maverick-400b-a17b",
    "granite-moe-3b-a800m",
    "zamba2-1.2b",
]

_MOD_FOR: Dict[str, str] = {a: a.replace("-", "_").replace(".", "_") for a in ARCH_IDS}


def get_config(arch_id: str) -> ModelConfig:
    if arch_id not in _MOD_FOR:
        raise KeyError(f"unknown arch {arch_id!r}; known: {ARCH_IDS}")
    mod = importlib.import_module(f"repro.configs.{_MOD_FOR[arch_id]}")
    return mod.CONFIG


def reduced_config(arch_id: str) -> ModelConfig:
    """Tiny same-family config for CPU smoke tests."""
    mod = importlib.import_module(f"repro.configs.{_MOD_FOR[arch_id]}")
    return mod.REDUCED


def cells(arch_id: str):
    """Yield the (shape_name, seq, batch, kind) cells assigned to this arch."""
    cfg = get_config(arch_id)
    for name, (seq, batch, kind) in SHAPES.items():
        if name == "long_500k" and cfg.long_context == "skip":
            continue
        yield name, seq, batch, kind


def all_cells():
    for a in ARCH_IDS:
        for c in cells(a):
            yield (a,) + c
