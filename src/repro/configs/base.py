"""Configuration dataclasses for the repro framework.

Every model in the zoo (the paper's DDPM U-Net and the 10 assigned
architectures) is described by a frozen dataclass config.  Configs are pure
data: hashable, comparable, and serializable — they are used as static args
to jitted step builders.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Optional, Tuple

# ---------------------------------------------------------------------------
# Layer kinds for the unified decoder stack.
# ---------------------------------------------------------------------------
ATTN_GLOBAL = 0      # full causal attention
ATTN_LOCAL = 1       # sliding-window causal attention
RECURRENT = 2        # RG-LRU recurrent block (recurrentgemma)
RWKV = 3             # RWKV6 time-mix block

LAYER_KIND_NAMES = {
    ATTN_GLOBAL: "attn_global",
    ATTN_LOCAL: "attn_local",
    RECURRENT: "rglru",
    RWKV: "rwkv6",
}


@dataclass(frozen=True)
class MoEConfig:
    """Mixture-of-experts sub-config."""
    num_experts: int
    experts_per_token: int
    d_expert: int                       # per-expert ffn hidden dim
    num_shared_experts: int = 0         # deepseek-style always-on shared expert(s)
    d_shared: int = 0                   # hidden dim of the shared expert
    router_aux_loss: float = 0.0        # load-balance aux loss coefficient
    capacity_factor: float = 1.25       # dense-dispatch capacity
    first_dense_layers: int = 0         # leading layers that use dense FFN (deepseek=3)


@dataclass(frozen=True)
class MLAConfig:
    """DeepSeek-V3 multi-head latent attention sub-config."""
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128


@dataclass(frozen=True)
class ModelConfig:
    """Unified model configuration.

    ``arch_type`` selects the top-level model family:
      - "decoder":   causal decoder-only LM (dense / MoE / SSM / hybrid)
      - "encdec":    whisper-style encoder-decoder (audio frontend stubbed)
      - "vlm":       vision-language (ViT frontend stubbed, decoder LM backbone)
      - "unet":      DDPM U-Net (the paper's own model)
    """
    name: str
    arch_type: str                       # decoder | encdec | vlm | unet
    source: str = ""                     # citation (arXiv id / hf card)

    # --- transformer backbone ----------------------------------------------
    num_layers: int = 0
    d_model: int = 0
    num_heads: int = 0
    num_kv_heads: int = 0
    head_dim: int = 0                    # 0 -> d_model // num_heads
    d_ff: int = 0
    vocab_size: int = 0
    max_seq_len: int = 8192
    # layer pattern: tuple of layer kinds, cycled over num_layers.
    layer_pattern: Tuple[int, ...] = (ATTN_GLOBAL,)
    sliding_window: int = 4096           # window for ATTN_LOCAL layers
    rope_theta: float = 10000.0
    use_qkv_bias: bool = False
    use_attn_out_bias: bool = False
    use_ffn_bias: bool = False
    tie_embeddings: bool = False
    norm_eps: float = 1e-6
    activation: str = "silu"             # silu (swiglu) | gelu (plain mlp)
    glu: bool = True                     # gated linear unit FFN
    logit_softcap: float = 0.0           # gemma2 final logit soft-capping
    attn_softcap: float = 0.0            # gemma2 attention logit soft-capping
    parallel_block: bool = False         # command-r parallel attn+ffn block
    # --- MoE / MLA -----------------------------------------------------------
    moe: Optional[MoEConfig] = None
    mla: Optional[MLAConfig] = None
    # --- recurrent (RG-LRU / RWKV) ------------------------------------------
    lru_width: int = 0                   # RG-LRU recurrence width (0 -> d_model)
    conv1d_width: int = 4                # temporal conv in recurrent block
    # --- enc-dec (whisper) ---------------------------------------------------
    num_encoder_layers: int = 0
    encoder_seq_len: int = 1500          # whisper mel-frame count after conv stub
    # --- vlm -----------------------------------------------------------------
    num_image_tokens: int = 0            # patch-embedding count from the ViT stub
    # --- unet ----------------------------------------------------------------
    image_size: int = 32
    in_channels: int = 3
    base_channels: int = 128
    channel_mults: Tuple[int, ...] = (1, 2, 2, 2)
    num_res_blocks: int = 2
    attn_resolutions: Tuple[int, ...] = (16,)
    num_classes: int = 0                 # 0 = unconditional
    dropout: float = 0.1
    diffusion_steps: int = 1000

    # --- numerics ------------------------------------------------------------
    dtype: str = "bfloat16"              # activation dtype
    param_dtype: str = "bfloat16"        # parameter dtype (fp32 master in opt)
    # --- compute backend -----------------------------------------------------
    # repro.models.ops dispatch: "xla" | "pallas" | "ref"; "" resolves
    # via $FEDPHD_BACKEND (trainers bake the resolved name in at
    # construction, so jit caches and checkpoints pin a concrete
    # backend).  Part of the frozen config on purpose: the backend is a
    # static argument of every compiled step/round program.
    backend: str = ""
    # --- compute precision ---------------------------------------------------
    # repro.models.ops precision axis: "fp32" | "bf16"; "" resolves via
    # $FEDPHD_PRECISION (trainers bake the resolved name in, same as
    # backend).  bf16 casts float params inside the loss closure — the
    # master weights, Adam moments, and aggregation stay fp32.  Frozen
    # for the same reason as ``backend``: it is a static argument of
    # every compiled step/round program.
    precision: str = ""
    # --- rematerialization ---------------------------------------------------
    # "" keeps every activation the backward pass reads (no remat);
    # "dots_with_no_batch_dims_saveable" runs the local step's loss
    # (repro.fl.client.make_loss_fn) under jax.checkpoint with that
    # policy: GEMM outputs are kept, the elementwise ops and the im2col
    # patches are recomputed in the backward pass.  Changes memory and
    # time, not the mathematics; a static argument of every step.
    remat_policy: str = ""

    def __post_init__(self):
        if self.arch_type != "unet":
            if self.head_dim == 0 and self.num_heads:
                object.__setattr__(self, "head_dim", self.d_model // self.num_heads)
            if self.num_kv_heads == 0:
                object.__setattr__(self, "num_kv_heads", self.num_heads)
            if self.lru_width == 0:
                object.__setattr__(self, "lru_width", self.d_model)

    # -- helpers --------------------------------------------------------------
    def layer_kinds(self) -> Tuple[int, ...]:
        """Per-layer kind, pattern cycled to num_layers."""
        pat = self.layer_pattern
        return tuple(pat[i % len(pat)] for i in range(self.num_layers))

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)

    @property
    def q_per_kv(self) -> int:
        return max(1, self.num_heads // max(1, self.num_kv_heads))

    def param_count(self) -> int:
        """Analytic parameter count (matches models.model.init shapes)."""
        from repro.metrics.flops import count_params_analytic
        return count_params_analytic(self)


@dataclass(frozen=True)
class InputShape:
    """One assigned (global) input shape."""
    name: str
    seq_len: int
    global_batch: int
    mode: str                            # "train" | "prefill" | "decode"


TRAIN_4K = InputShape("train_4k", 4096, 256, "train")
PREFILL_32K = InputShape("prefill_32k", 32768, 32, "prefill")
DECODE_32K = InputShape("decode_32k", 32768, 128, "decode")
LONG_500K = InputShape("long_500k", 524288, 1, "decode")

INPUT_SHAPES = {s.name: s for s in (TRAIN_4K, PREFILL_32K, DECODE_32K, LONG_500K)}


@dataclass(frozen=True)
class ShardingRules:
    """Logical-axis -> mesh-axis mapping for one model.

    Each entry is a tuple of mesh axis names (or None) per logical axis.
    ``fsdp_axes`` lists mesh axes over which parameters are additionally
    sharded on their largest dimension (ZeRO-3 style).
    """
    batch: Tuple[str, ...] = ("pod", "data")
    heads: Tuple[str, ...] = ("model",)
    ffn: Tuple[str, ...] = ("model",)
    experts: Tuple[str, ...] = ("model",)
    vocab: Tuple[str, ...] = ("model",)
    fsdp_axes: Tuple[str, ...] = ("data",)
    shard_kv_cache_seq: bool = False     # shard the KV cache along sequence
    moe_ep: bool = False                 # shard_map expert parallelism:
                                         # experts over the EP axes, d_expert
                                         # unsharded (weights fully local)


# -- JSON (de)serialization --------------------------------------------------
# Configs are frozen dataclasses of scalars and tuples; JSON turns the
# tuples into lists, so round-tripping needs explicit coercion.  Used by
# repro.experiment (declarative specs) and checkpoint manifests (the
# post-prune ModelConfig differs from the one the run started with).

_MODEL_TUPLE_FIELDS = ("layer_pattern", "channel_mults", "attn_resolutions")


def config_to_dict(cfg: "ModelConfig") -> dict:
    return dataclasses.asdict(cfg)


def config_from_dict(d: dict) -> "ModelConfig":
    d = dict(d)
    if d.get("moe"):
        d["moe"] = MoEConfig(**d["moe"])
    if d.get("mla"):
        d["mla"] = MLAConfig(**d["mla"])
    for k in _MODEL_TUPLE_FIELDS:
        if d.get(k) is not None:
            d[k] = tuple(d[k])
    return ModelConfig(**d)


def fl_to_dict(fl: "FLConfig") -> dict:
    return dataclasses.asdict(fl)


def fl_from_dict(d: dict) -> "FLConfig":
    return FLConfig(**d)


@dataclass(frozen=True)
class FLConfig:
    """Federated-learning / FedPhD hyper-parameters (paper §V-A)."""
    num_clients: int = 20                # N
    num_edges: int = 2                   # N_e
    participation: float = 1.0           # kappa
    local_epochs: int = 1                # E
    edge_agg_every: int = 1              # r_e
    cloud_agg_every: int = 5             # r_g
    rounds: int = 100                    # R
    sparse_rounds: int = 20              # R_s
    # SH-score weighting (eqs 22/24/25)
    sh_a: float = 15000.0
    sh_b: float = 0.0
    # pruning
    prune_ratio: float = 0.44            # s_p
    prune_mode: str = "group_norm"       # "group_norm" | "oneshot_random" | "oneshot_l2"
    lambda0: float = 1e-4                # group-lasso base scale (eq 17)
    # baseline knobs
    fedprox_mu: float = 1.0
    moon_mu: float = 1.0
    moon_tau: float = 0.5
    seed: int = 0
