"""Model configuration schema, the assigned input-shape sets, and the registry.

Every assigned architecture is a ``ModelConfig`` in its own module
(``repro/configs/<id>.py``); ``get_config(name)`` resolves ``--arch`` flags.
``reduced()`` derives the smoke-test configuration of the same family (small
widths/layers/vocab, same structure) used by per-arch CPU smoke tests — the
full configs are exercised only through the AOT dry-run.
"""

from __future__ import annotations

import dataclasses
import importlib
from dataclasses import dataclass, field, replace


@dataclass(frozen=True)
class ShapeCell:
    """One assigned input-shape cell."""

    name: str
    seq_len: int
    global_batch: int
    kind: str               # "train" | "prefill" | "decode"


SHAPES: dict[str, ShapeCell] = {
    "train_4k": ShapeCell("train_4k", 4_096, 256, "train"),
    "prefill_32k": ShapeCell("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": ShapeCell("decode_32k", 32_768, 128, "decode"),
    "long_500k": ShapeCell("long_500k", 524_288, 1, "decode"),
}

# long_500k requires sub-quadratic sequence mixing; per the assignment it runs
# only for SSM/hybrid archs and is recorded as a documented skip elsewhere.
SUBQUADRATIC_FAMILIES = ("ssm", "hybrid")


@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                     # dense | moe | audio | vlm | hybrid | ssm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 128
    qkv_bias: bool = False
    rope_theta: float = 10_000.0    # 0: absolute sinusoidal positions
    position_embedding: str = "rope"    # rope | nope (no positions at all)
    norm_eps: float = 1e-5
    tie_embeddings: bool = False
    act: str = "silu"               # mlp activation (gated unless act=="gelu")
    # Granite-style scales; each is applied only where it is set
    embedding_multiplier: float = 1.0   # embeddings multiplied by this
    residual_multiplier: float = 1.0    # sublayer outputs, before the add
    attention_multiplier: float = 0.0   # score scale (0: 1/sqrt(head_dim))
    logits_scaling: float = 1.0         # logits divided by this

    # MoE
    n_experts: int = 0
    n_shared_experts: int = 0
    top_k: int = 0
    n_dense_layers: int = 0         # leading dense layers (DeepSeek=3, Kimi=1)
    moe_d_ff: int = 0               # per-expert hidden (d_ff = dense-layer hidden)
    shared_d_ff: int = 0            # shared-expert hidden (0: moe_d_ff ×
                                    # n_shared_experts)
    n_experts_held: int = 0         # experts 0..n-1 held on this chip, routed
                                    # over all n_experts (0: all of them)
    capacity_factor: float = 1.25
    router_aux_weight: float = 0.001

    # MLA (DeepSeek-V3)
    use_mla: bool = False
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_dim: int = 0
    qk_rope_dim: int = 0
    v_head_dim: int = 0
    mtp: bool = False               # multi-token-prediction module

    # hybrid (RecurrentGemma)
    block_pattern: tuple[str, ...] = ()   # repeating unit, e.g. ("rec","rec","attn")
    lru_width: int = 0
    window: int = 0                 # local-attention window

    # per-layer pattern (Granite 4.0-H): "mamba" | "attention" for each
    # layer, every layer followed by the held-expert FFN
    layer_types: tuple[str, ...] = ()

    # SSM (Mamba-2)
    ssm_state: int = 0
    ssm_expand: int = 2
    ssm_headdim: int = 64
    ssm_ngroups: int = 1
    conv_kernel: int = 4
    ssd_chunk: int = 256

    # encoder-decoder (Whisper) — backbone only, conv frontend is a stub
    enc_layers: int = 0
    enc_seq: int = 0                # precomputed frame embeddings
    # vision-language (Phi-3-vision) — CLIP frontend is a stub
    num_patches: int = 0            # precomputed patch embeddings

    # numerics / compilation
    dtype: str = "bfloat16"         # activation/compute dtype
    param_dtype: str = "float32"
    scan_layers: bool = True
    remat: str = "full"             # none | full | dots
    expert_dtype: str = ""          # storage dtype for expert stacks
                                    # ("" → param_dtype; fp8 for serving)
    attn_q_chunk: int = 0           # blockwise attention over query chunks
                                    # (0 = full scores) — the paper's tiling
                                    # transformation applied to attention;
                                    # bounds the O(S²) working set

    citation: str = ""

    # ------------------------------------------------------------------

    @property
    def is_subquadratic(self) -> bool:
        return self.family in SUBQUADRATIC_FAMILIES

    def n_params(self) -> int:
        """Total parameter count (embedding + blocks + head)."""
        from repro.models.model import count_params_from_specs
        return count_params_from_specs(self)

    def n_active_params(self) -> int:
        from repro.models.model import count_params_from_specs
        return count_params_from_specs(self, active_only=True)

    def reduced(self) -> "ModelConfig":
        """Smoke-test configuration: same family/structure, tiny sizes."""
        pat = self.block_pattern
        kw = dict(
            n_layers=min(self.n_layers, 2 * max(1, len(pat)) if pat else 2),
            d_model=128,
            n_heads=4,
            n_kv_heads=min(self.n_kv_heads, 4) if self.n_kv_heads else self.n_kv_heads,
            head_dim=32,
            d_ff=256,
            vocab_size=512,
            dtype="float32",
            param_dtype="float32",
            scan_layers=False,
            remat="none",
        )
        if self.n_experts:
            kw.update(n_experts=8, top_k=min(self.top_k, 2), moe_d_ff=64,
                      n_dense_layers=min(self.n_dense_layers, 1))
            if self.shared_d_ff:
                kw.update(shared_d_ff=96)
        if self.layer_types:
            # the shortest prefix that holds every kind of layer, and a
            # share of the experts, as one chip of a deployment holds
            n = 1 + max(self.layer_types.index(t) for t in set(self.layer_types))
            kw.update(n_layers=n, layer_types=self.layer_types[:n],
                      n_experts_held=3)
        if self.use_mla:
            kw.update(q_lora_rank=64, kv_lora_rank=32, qk_nope_dim=32,
                      qk_rope_dim=16, v_head_dim=32)
        if self.lru_width:
            kw.update(lru_width=128, window=32)
        if self.ssm_state:
            kw.update(ssm_state=16, ssm_headdim=16, ssd_chunk=16)
        if self.enc_layers:
            kw.update(enc_layers=2, enc_seq=16)
        if self.num_patches:
            kw.update(num_patches=4)
        return replace(self, **kw)


_REGISTRY = [
    "qwen1_5_32b", "internlm2_1_8b", "qwen1_5_110b", "glm4_9b",
    "kimi_k2_1t_a32b", "deepseek_v3_671b", "whisper_base",
    "phi_3_vision_4_2b", "recurrentgemma_2b", "mamba2_130m",
    "granite_4_0_h_small",
]


def arch_ids() -> list[str]:
    return list(_REGISTRY)


def get_config(name: str) -> ModelConfig:
    key = name.replace("-", "_").replace(".", "_")
    if key not in _REGISTRY:
        raise KeyError(f"unknown arch {name!r}; known: {_REGISTRY}")
    mod = importlib.import_module(f"repro.configs.{key}")
    return mod.CONFIG


def shape_cells(cfg: ModelConfig) -> dict[str, ShapeCell | None]:
    """All four cells; value None means a documented skip for this arch."""
    out: dict[str, ShapeCell | None] = {}
    for n, cell in SHAPES.items():
        if n == "long_500k" and not cfg.is_subquadratic:
            out[n] = None      # quadratic attention: per-assignment skip
        else:
            out[n] = cell
    return out
