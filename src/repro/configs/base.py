"""Architecture / run configuration dataclasses.

Every assigned architecture gets one module in this package exporting
``CONFIG``; the registry in ``__init__`` maps ``--arch <id>`` to it.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    """A routed-expert FFN: a softmax router over `n_experts`, greedy top-k,
    each chosen expert's SwiGLU weighted by its gate; `n_shared_experts`
    SwiGLUs of width `d_ff_expert` every token passes through."""
    n_experts: int
    top_k: int
    d_ff_expert: int
    n_shared_experts: int = 0
    # Gates are the top-k softmax probabilities, renormalised to sum to one
    # only where `norm_topk_prob` is set, then times `routed_scaling`.
    norm_topk_prob: bool = False
    routed_scaling: float = 1.0
    # Weight of the per-sequence balance loss (DeepSeek-V2's `seq_aux`).
    aux_loss_alpha: float = 0.001


@dataclasses.dataclass(frozen=True)
class YarnConfig:
    """YaRN scaling of RoPE (DeepSeek-V2's `rope_scaling`, type "yarn"):
    the frequencies and the softmax scale's mscale (`layers.yarn_freqs`,
    `layers.yarn_mscale`)."""
    factor: float = 40.0
    original_max_position: int = 4096
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 0.707
    mscale_all_dim: float = 0.707


@dataclasses.dataclass(frozen=True)
class MLAConfig:
    """DeepSeek-style Multi-head Latent Attention."""
    kv_lora_rank: int = 512
    qk_rope_dim: int = 64
    qk_nope_dim: int = 128
    v_head_dim: int = 128


@dataclasses.dataclass(frozen=True)
class SSMConfig:
    state_size: int = 64          # N (per-channel state) for Mamba2
    head_dim: int = 64            # P
    expand: int = 2
    conv_width: int = 4
    chunk_size: int = 128
    kind: str = "mamba2"          # "mamba2" | "rwkv6"


@dataclasses.dataclass(frozen=True)
class ArchConfig:
    name: str
    family: str                   # dense | moe | ssm | hybrid | encdec | vlm | audio
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: Optional[int] = None
    qkv_bias: bool = False
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    tie_embeddings: bool = False
    moe: Optional[MoEConfig] = None
    mla: Optional[MLAConfig] = None
    ssm: Optional[SSMConfig] = None
    # hybrid: apply one shared attention block every `shared_attn_every` layers
    shared_attn_every: int = 0
    # enc-dec
    n_encoder_layers: int = 0
    # sliding-window attention (0 = full attention); decode keeps a ring
    # buffer of the window.
    sliding_window: int = 0
    # Leading dense layers of a MoE decoder (DeepSeek's
    # `first_k_dense_replace`), with their SwiGLU width (0: `d_ff`).
    first_k_dense: int = 0
    dense_d_ff: int = 0
    # YaRN scaling of MLA's rope dims (None: plain RoPE).
    yarn: Optional[YarnConfig] = None
    # Routed experts this device holds, [0, experts_held) of the router's
    # `moe.n_experts` (0: all of them); the device computes its own experts'
    # part of the layer (one chip's share of expert parallelism).
    experts_held: int = 0
    # dtype of the params
    param_dtype: str = "bfloat16"
    # activation checkpointing of each scanned layer (False: none, so every
    # scan activation is stored, ~18 TB/device for qwen2-72b train_4k).
    # The dense/MoE/MLA decoder keeps its projection outputs for the
    # backward where they fit in a quarter of the device's memory
    # (`transformer.KEEP_PROJ_SHARE`), so the backward recomputes the norms,
    # RoPE, SwiGLU and attention but no projection GEMM; otherwise, and
    # where the device reports no memory (CPU), it recomputes
    # the whole layer, ~1.33x the FLOPs. Other families always recompute.
    remat: bool = True
    source: str = ""              # citation

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim if self.head_dim else self.d_model // self.n_heads

    @property
    def resolved_dense_d_ff(self) -> int:
        return self.dense_d_ff or self.d_ff

    @property
    def resolved_experts_held(self) -> int:
        return self.experts_held or self.moe.n_experts

    @property
    def is_attention_free(self) -> bool:
        return self.family == "ssm"

    def reduced(self) -> "ArchConfig":
        """A smoke-test-sized variant of the same family (<=2 layers, d<=512)."""
        kw = dataclasses.asdict(self)
        kw["n_layers"] = min(2, self.n_layers)
        d = min(256, self.d_model)
        heads = min(4, self.n_heads)
        kv = max(1, min(self.n_kv_heads, heads))
        # keep heads % kv == 0
        while heads % kv:
            kv -= 1
        kw.update(d_model=d, n_heads=heads, n_kv_heads=kv,
                  d_ff=min(512, self.d_ff), vocab_size=min(1024, self.vocab_size),
                  head_dim=d // heads, param_dtype="float32")
        if self.moe is not None:
            kw["moe"] = dataclasses.replace(
                self.moe, n_experts=min(4, self.moe.n_experts),
                top_k=min(2, self.moe.top_k),
                d_ff_expert=min(128, self.moe.d_ff_expert),
                n_shared_experts=min(1, self.moe.n_shared_experts))
        else:
            kw["moe"] = None
        kw["experts_held"] = 0
        kw["first_k_dense"] = min(1, self.first_k_dense)
        kw["dense_d_ff"] = min(512, self.dense_d_ff)
        kw["yarn"] = self.yarn
        if self.mla is not None:
            kw["mla"] = MLAConfig(kv_lora_rank=64, qk_rope_dim=16,
                                  qk_nope_dim=32, v_head_dim=32)
            kw["head_dim"] = None
        else:
            kw["mla"] = None
        if self.ssm is not None:
            kw["ssm"] = MLAConfig  # placeholder replaced below
            kw["ssm"] = SSMConfig(state_size=min(16, self.ssm.state_size),
                                  head_dim=min(32, self.ssm.head_dim),
                                  expand=2, conv_width=4, chunk_size=32,
                                  kind=self.ssm.kind)
        else:
            kw["ssm"] = None
        if self.shared_attn_every:
            kw["shared_attn_every"] = 1
        if self.n_encoder_layers:
            kw["n_encoder_layers"] = min(2, self.n_encoder_layers)
        if self.sliding_window:
            kw["sliding_window"] = 64
        return ArchConfig(**kw)


# Valid FedConfig string knobs. Mirrored (not imported) from repro.core
# .distances / repro.optim so configs stays dependency-free; both modules
# raise on unknown names themselves, this just fails at construction time.
DISTANCE_MEASURES = ("l2", "l1", "cosine", "squared_l2")
OPTIMIZERS = ("sgd", "momentum", "adam", "adamw")


@dataclasses.dataclass(frozen=True)
class FedConfig:
    """FedELMY hyper-parameters (paper Alg. 1 notation)."""
    n_clients: int = 10
    pool_size: int = 5            # S
    e_local: int = 200            # E_local (steps in our step-based trainer)
    e_warmup: int = 30            # E_w
    alpha: float = 0.06           # d1 scale
    beta: float = 1.0             # d2 scale
    learning_rate: float = 5e-5
    weight_decay: float = 1e-4
    optimizer: str = "adam"
    distance_measure: str = "l2"  # l2 | l1 | cosine | squared_l2
    use_d1: bool = True
    use_d2: bool = True
    use_pool: bool = True         # ablation: pool vs single model
    log_scale_distances: bool = True
    moment_form: bool = False     # legacy alias for pool_backend="moment"
    # Pool representation, resolved against the repro.api backend registry
    # ("stacked" | "moment" | "lowrank" | any registered extension). None
    # derives it from the legacy `moment_form` flag.
    pool_backend: Optional[str] = None
    # Rank ceiling for pool_backend="lowrank": each matrix leaf's pool delta
    # is truncated to rank min(pool_rank, d_in, d_out). Ignored elsewhere.
    pool_rank: int = 8
    seed: int = 0

    def __post_init__(self):
        if self.distance_measure not in DISTANCE_MEASURES:
            raise ValueError(
                f"unknown distance_measure {self.distance_measure!r}; "
                f"expected one of {DISTANCE_MEASURES}")
        if self.optimizer not in OPTIMIZERS:
            raise ValueError(
                f"unknown optimizer {self.optimizer!r}; "
                f"expected one of {OPTIMIZERS}")
        if self.moment_form and self.pool_backend not in (None, "moment"):
            raise ValueError(
                f"moment_form=True conflicts with "
                f"pool_backend={self.pool_backend!r}; drop moment_form and "
                f"set pool_backend explicitly")
        if self.pool_rank < 1:
            raise ValueError(f"pool_rank must be >= 1, got {self.pool_rank}")
        if self.resolved_pool_backend == "lowrank" and \
                self.distance_measure not in ("l2", "squared_l2"):
            raise ValueError(
                "the low-rank delta pool computes distances from factor "
                "Grams, which is exact for l2/squared_l2 only; got "
                f"{self.distance_measure!r}. Use pool_backend='stacked' "
                "for l1/cosine.")
        if self.resolved_pool_backend == "moment" and \
                self.distance_measure != "squared_l2":
            raise ValueError(
                "the moment-form pool keeps only (μ, q) statistics and "
                "supports distance_measure='squared_l2' exactly; got "
                f"{self.distance_measure!r}. Use pool_backend='stacked' for "
                "l2/l1/cosine, or set distance_measure='squared_l2'.")

    @property
    def resolved_pool_backend(self) -> str:
        """Backend name for the repro.api pool registry."""
        if self.pool_backend is not None:
            return self.pool_backend
        return "moment" if self.moment_form else "stacked"
