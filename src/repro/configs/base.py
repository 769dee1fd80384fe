"""Architecture configuration system.

Each assigned architecture gets one module in this package defining an
``ArchConfig`` with the exact published dimensions, registered under its id.
``reduced()`` derives the CPU smoke-test config (same family, tiny dims).
``input_specs()`` produces ShapeDtypeStruct stand-ins for every model input
of a given (arch × shape-id) cell — the dry-run's zero-allocation inputs.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp

# ---------------------------------------------------------------------------
# Shape grid assigned to the LM family (see system spec).
# ---------------------------------------------------------------------------
SHAPES: dict[str, dict[str, Any]] = {
    "train_4k": dict(kind="train", seq_len=4096, global_batch=256),
    "prefill_32k": dict(kind="prefill", seq_len=32768, global_batch=32),
    "decode_32k": dict(kind="decode", seq_len=32768, global_batch=128),
    "long_500k": dict(kind="decode", seq_len=524288, global_batch=1),
}


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    num_experts: int
    top_k: int
    expert_d_ff: int
    num_shared: int = 0
    shared_d_ff: int = 0
    capacity_factor: float = 1.25
    router_dtype: Any = jnp.float32
    # Expert-parallel axes (in sharding priority order) and config-time expert
    # padding so every mesh in use divides the expert axis (e.g. 40e → 64 on a
    # 16-way "model" axis; padding experts are masked in the router).
    ep_axes: tuple[str, ...] = ("model",)
    padded_experts: int = 0
    # The experts this chip holds, [first_expert, first_expert +
    # experts_held), when an expert-parallel deployment divides them over
    # chips (0 = all of them). The router still scores all ``num_experts``
    # and takes its ``top_k``; only the held experts add to the output.
    experts_held: int = 0
    first_expert: int = 0

    @property
    def held(self) -> int:
        return self.experts_held or self.padded_experts or self.num_experts


@dataclasses.dataclass(frozen=True)
class MLAConfig:
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128


@dataclasses.dataclass(frozen=True)
class SSMConfig:
    state_size: int
    head_dim: int = 64
    expand: int = 2
    conv_width: int = 4
    chunk_size: int = 256

    def d_inner(self, d_model: int) -> int:
        return self.expand * d_model

    def num_heads(self, d_model: int) -> int:
        return self.d_inner(d_model) // self.head_dim


@dataclasses.dataclass(frozen=True)
class ArchConfig:
    name: str
    family: str  # dense | moe | ssm | hybrid | hybrid_moe | vlm | audio
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0  # 0 → d_model // num_heads
    qkv_bias: bool = False
    rope_theta: float = 10_000.0
    norm_eps: float = 1e-5
    tie_embeddings: bool = False
    # Family extensions ----------------------------------------------------
    moe: MoEConfig | None = None
    first_k_dense: int = 0  # deepseek: leading dense layers before MoE stack
    mla: MLAConfig | None = None
    mtp: bool = False  # deepseek multi-token-prediction head
    ssm: SSMConfig | None = None
    attn_every: int = 0  # hybrid: shared attention applied every k-th layer
    # hybrid_moe: the layers whose mixer is attention (the rest are Mamba-2)
    attn_layers: tuple[int, ...] = ()
    rope: bool = True  # False: attention takes no position embedding (NoPE)
    # Granite's multipliers (hybrid_moe): the embedding is scaled by
    # embedding_multiplier, attention scores by attention_multiplier (0 =
    # 1/sqrt(head_dim)), each residual branch by residual_multiplier, and the
    # logits divided by logits_scaling
    embedding_multiplier: float = 1.0
    attention_multiplier: float = 0.0
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0
    encoder_layers: int = 0  # enc-dec (whisper)
    encoder_seq: int = 0  # frames from the stubbed conv frontend
    frontend: str | None = None  # "audio" | "vision" stub (precomputed embeds)
    frontend_seq: int = 0  # prepended embedding positions (vlm)
    # Execution knobs (generator design-point axes) -------------------------
    dtype: Any = jnp.bfloat16
    activation: str = "silu"  # mlp nonlinearity family
    activation_impl: str = "exact"  # exact | pwl | lut | hard (paper RQ1 axis)
    attention_impl: str = "auto"  # auto | naive | chunked
    attn_chunk: int = 1024
    remat: str = "full"  # none | full | dots
    optimizer: str = "adamw"  # adamw | adafactor (671B needs adafactor)
    logits_chunk: int = 0  # 0 = sharded-vocab CE, >0 = seq-chunked CE
    scan_layers: bool = True
    cache_update: str = "dus"  # dus | onehot (sharded-seq-safe decode write)
    kv_dtype: Any = None  # None → dtype; jnp.float8_e4m3fn halves KV reads
    # "int8" routes attention/MLP projection einsums through the
    # per-output-channel int8 matmul path (models/quant.py): weights are
    # quantized once at engine init, activations per row at each call.
    # None = full-precision weights. Serving/inference only.
    quant: str | None = None

    # -- derived -----------------------------------------------------------
    @property
    def resolved_head_dim(self) -> int:
        if self.num_heads == 0:  # attention-free (pure SSM)
            return self.head_dim
        return self.head_dim or self.d_model // self.num_heads

    @property
    def attention_layers(self) -> tuple[int, ...]:
        """hybrid_moe: the attention layers among the first ``num_layers``."""
        return tuple(i for i in self.attn_layers if i < self.num_layers)

    @property
    def padded_vocab(self) -> int:
        """Embedding-table vocab rounded up to a multiple of 256 so the vocab
        axis TP-shards on any mesh; padded logits are masked at the CE /
        sampling sites (true ``vocab_size`` is unchanged)."""
        return ((self.vocab_size + 255) // 256) * 256

    def supports(self, shape_id: str) -> tuple[bool, str]:
        """Applicability of a shape cell to this arch (skips per DESIGN.md)."""
        if shape_id == "long_500k" and self.family not in ("ssm", "hybrid"):
            return False, "full-attention arch: 500k context needs sub-quadratic attention"
        return True, ""

    def param_count(self) -> int:
        from repro.models.model import param_defs
        from repro.models.params import count_params

        return count_params(param_defs(self))

    def active_param_count(self) -> int:
        """Activated params per token (MoE discounts inactive experts —
        including config-time padding experts, which never activate)."""
        total = self.param_count()
        if self.moe is None:
            return total
        m = self.moe
        epad = m.padded_experts or m.num_experts
        expert_p = 3 * self.d_model * m.expert_d_ff  # gate/up/down
        n_moe_layers = self.num_layers - self.first_k_dense
        # a share of the experts holds top_k * held / num_experts active ones
        # per token on average
        active = m.top_k if m.held == epad else m.top_k * m.held / m.num_experts
        inactive = n_moe_layers * (m.held - active) * expert_p
        return int(total - inactive)


_REGISTRY: dict[str, Callable[[], ArchConfig]] = {}
_REDUCED: dict[str, Callable[[], ArchConfig]] = {}


def register(name: str, full: Callable[[], ArchConfig], reduced: Callable[[], ArchConfig]):
    _REGISTRY[name] = full
    _REDUCED[name] = reduced


def get_config(name: str) -> ArchConfig:
    if name not in _REGISTRY:
        raise KeyError(f"unknown arch {name!r}; known: {sorted(_REGISTRY)}")
    return _REGISTRY[name]()


def get_reduced_config(name: str) -> ArchConfig:
    return _REDUCED[name]()


def list_archs() -> list[str]:
    return sorted(_REGISTRY)


# ---------------------------------------------------------------------------
# Input specs (ShapeDtypeStruct stand-ins, shardable, zero allocation)
# ---------------------------------------------------------------------------
def input_specs(cfg: ArchConfig, shape_id: str, mesh=None) -> dict[str, Any]:
    """Abstract inputs for one (arch × shape) cell.

    train  → {tokens, labels [, frontend_embeds]}
    prefill→ {tokens [, frontend_embeds]}
    decode → {token, pos, cache} — cache specs come from serving.kv_cache.
    """
    from repro.serving.kv_cache import cache_defs
    from repro.models.params import abstract_params
    from repro.sharding.rules import active_rules, batch_spec, spec_for
    from jax.sharding import NamedSharding, PartitionSpec as P

    shape = SHAPES[shape_id]
    b, s = shape["global_batch"], shape["seq_len"]
    i32 = jnp.int32

    def tok(shp):
        if mesh is None:
            return jax.ShapeDtypeStruct(shp, i32)
        sp = batch_spec(shp[0], mesh, extra_dims=len(shp) - 1)
        return jax.ShapeDtypeStruct(shp, i32, sharding=NamedSharding(mesh, sp))

    def emb(shp):
        if mesh is None:
            return jax.ShapeDtypeStruct(shp, cfg.dtype)
        sp = batch_spec(shp[0], mesh, extra_dims=len(shp) - 1)
        return jax.ShapeDtypeStruct(shp, cfg.dtype, sharding=NamedSharding(mesh, sp))

    out: dict[str, Any] = {}
    kind = shape["kind"]
    if kind in ("train", "prefill"):
        out["tokens"] = tok((b, s))
        if kind == "train":
            out["labels"] = tok((b, s))
        if cfg.frontend == "vision":
            out["frontend_embeds"] = emb((b, cfg.frontend_seq, cfg.d_model))
        if cfg.frontend == "audio":
            out["frontend_embeds"] = emb((b, cfg.encoder_seq, cfg.d_model))
    else:  # decode: one new token against a seq_len KV cache
        out["token"] = tok((b, 1))
        if mesh is None:
            out["pos"] = jax.ShapeDtypeStruct((), i32)
        else:
            out["pos"] = jax.ShapeDtypeStruct(
                (), i32, sharding=NamedSharding(mesh, P())
            )
        defs = cache_defs(cfg, batch=b, max_len=s)
        rules = active_rules()
        if mesh is None:
            out["cache"] = abstract_params(defs)
        else:
            out["cache"] = abstract_params(
                defs, lambda d: NamedSharding(mesh, _cache_spec(d, b, mesh, rules))
            )
    return out


def _cache_spec(d, batch: int, mesh, rules):
    """KV-cache sharding: batch dim over DP axes (if divisible), seq over TP."""
    from repro.sharding.rules import batch_axes, spec_for
    from jax.sharding import PartitionSpec as P

    base = spec_for(d, mesh, rules)
    axes = batch_axes(mesh)
    size = 1
    for a in axes:
        size *= mesh.shape[a]
    entries = list(base)
    for i, (dim, logical) in enumerate(zip(d.shape, d.logical)):
        if logical == "batch" and dim % size == 0 and size > 1:
            entries[i] = axes if len(axes) > 1 else axes[0]
    return P(*entries)
