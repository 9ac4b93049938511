"""Config system: model architectures, input shapes, hardware profiles.

Every assigned architecture gets one module in this package defining a
``ModelConfig`` named ``CONFIG`` built with the exact numbers from its source
paper / model card (cited in the module docstring).  ``registry()`` collects
them; ``--arch <id>`` in the launchers resolves through it.

Layer structure is expressed as a *period pattern*: a short list of
``LayerSpec`` that repeats down the stack (e.g. jamba's 8-layer
mamba/attention interleave, gemma3's 5 local + 1 global).  The transformer
stack scans over whole periods, keeping HLO size O(period) instead of
O(layers), which matters for the 512-device dry-run compiles.
"""

from __future__ import annotations

import dataclasses
import importlib
import pkgutil
from dataclasses import dataclass, field, replace
from typing import Optional


# ---------------------------------------------------------------------------
# layer / block specs
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class AttentionSpec:
    """Self-attention mixer variant for one layer."""
    kind: str = "full"          # "full" | "window" | "chunked"  (chunked = llama4 iRoPE local)
    window: int = 0             # window size for "window", chunk size for "chunked"
    rope: bool = True
    qk_norm: bool = False


@dataclass(frozen=True)
class SSMSpec:
    """Mamba-2 (SSD) mixer."""
    state_dim: int = 128
    head_dim: int = 64
    expand: int = 2
    conv_width: int = 4
    chunk: int = 64             # SSD intra-chunk block length


@dataclass(frozen=True)
class LayerSpec:
    """One layer of the repeating period: a mixer plus an FFN kind."""
    mixer: str = "attn"         # "attn" | "mamba"
    ffn: str = "dense"          # "dense" | "moe" | "none"
    attn: AttentionSpec = AttentionSpec()
    ssm: SSMSpec = SSMSpec()


@dataclass(frozen=True)
class MoEConfig:
    num_experts: int = 8
    top_k: int = 2
    d_ff_expert: int = 0            # expert hidden size (g_e in the paper)
    num_shared_experts: int = 0     # always-on shared expert(s) (llama4/deepseek style)
    router_aux_coef: float = 0.01   # Switch-style auxiliary load-balance loss weight
    loss_free_bias: bool = False    # DeepSeek auxiliary-loss-free bias balancing
    bias_update_rate: float = 0.001
    # MemFine knobs ---------------------------------------------------------
    strategy: str = "auto"          # "auto" | "ep_shardmap" | "tp_gspmd" | "dense"
    capacity_mode: str = "dropless" # "dropless" (worst-case static buffers) | "capacity"
    capacity_factor: float = 1.25   # only used by capacity_mode="capacity" baselines


@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                     # dense | moe | ssm | hybrid | audio | vlm
    source: str                     # citation for the numbers
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0               # 0 -> d_model // num_heads
    norm: str = "rmsnorm"           # rmsnorm | layernorm
    pattern: tuple[LayerSpec, ...] = (LayerSpec(),)
    prefix: tuple[LayerSpec, ...] = ()   # unrolled leading layers (e.g.
                                         # DeepSeek's d_l dense layers); the
                                         # pattern then scans over the rest
    moe: Optional[MoEConfig] = None
    tie_embeddings: bool = False
    rope_theta: float = 10000.0
    dtype: str = "bfloat16"
    # encoder-decoder (whisper) --------------------------------------------
    encoder_layers: int = 0
    encoder_seq: int = 0            # fixed encoder frame count (audio stub)
    # multimodal stubs ------------------------------------------------------
    num_patch_tokens: int = 0       # VLM: leading positions fed by patch embeddings
    learned_pos: int = 0            # learned position-embedding table size (whisper)
    # long-context eligibility (see docs/DESIGN.md §4)
    subquadratic: bool = False
    # MemFine scheduling ----------------------------------------------------
    remat_policy: str = "memfine"   # "none" | "full" | "memfine"
    moe_chunks: int = 1             # FCDA chunk count c (MACT overrides dynamically)
    # 2-layer representative pattern for the smoke tests (None -> derived)
    smoke_pattern: Optional[tuple[LayerSpec, ...]] = None

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or self.d_model // self.num_heads

    @property
    def padded_vocab(self) -> int:
        """Embedding/logits vocab rounded up to a multiple of 256 so the vocab
        dim always shards over a 16-wide axis (Megatron-style padding; the
        real ``vocab_size`` stays the label space)."""
        return -(-self.vocab_size // 256) * 256

    def layer_specs(self) -> tuple[LayerSpec, ...]:
        """Full per-layer spec list (prefix, then pattern cycled)."""
        p = self.pattern
        body = self.num_layers - len(self.prefix)
        return self.prefix + tuple(p[i % len(p)] for i in range(body))

    @property
    def num_periods(self) -> int:
        return (self.num_layers - len(self.prefix)) // len(self.pattern)

    @property
    def remainder_layers(self) -> int:
        return (self.num_layers - len(self.prefix)) % len(self.pattern)

    def reduced(self, *, d_model: int = 256, max_experts: int = 4) -> "ModelConfig":
        """Smoke-test variant: 2 layers, d_model<=512, <=4 experts, same family.

        The 2-layer pattern is ``smoke_pattern`` if given, else the first two
        distinct-mixer layers of the full pattern (so a hybrid keeps one mamba
        and one attention layer, an MoE arch keeps an MoE layer, etc.).
        """
        if self.smoke_pattern is not None:
            pat = self.smoke_pattern
        else:
            reps: list[LayerSpec] = []
            for ls in self.layer_specs():
                if not any(r.mixer == ls.mixer and r.ffn == ls.ffn for r in reps):
                    reps.append(ls)
                if len(reps) == 2:
                    break
            pat = tuple(reps) if len(reps) == 2 else (reps[0], reps[0])
        n_layers = 2
        heads = 4
        kv = max(1, min(self.num_kv_heads, 2))
        moe = None
        if self.moe is not None:
            moe = replace(
                self.moe,
                num_experts=min(self.moe.num_experts, max_experts),
                top_k=min(self.moe.top_k, 2),
                d_ff_expert=d_model * 2,
                num_shared_experts=min(self.moe.num_shared_experts, 1),
            )
        ssm_small = SSMSpec(state_dim=16, head_dim=32, expand=2, conv_width=4, chunk=16)
        pat = tuple(replace(ls, ssm=ssm_small,
                            attn=replace(ls.attn, window=min(ls.attn.window, 64) if ls.attn.window else 0))
                    for ls in pat)
        return replace(
            self,
            name=self.name + "-smoke",
            prefix=(),
            num_layers=n_layers,
            d_model=d_model,
            num_heads=heads,
            num_kv_heads=kv,
            head_dim=d_model // heads,
            d_ff=d_model * 3,
            vocab_size=512,
            pattern=pat,
            moe=moe,
            encoder_layers=min(self.encoder_layers, 2),
            encoder_seq=min(self.encoder_seq, 32),
            num_patch_tokens=min(self.num_patch_tokens, 8),
            dtype="float32",
        )


# ---------------------------------------------------------------------------
# input shapes (assigned)
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class InputShape:
    name: str
    seq_len: int
    global_batch: int
    mode: str                       # "train" | "prefill" | "decode"


SHAPES: dict[str, InputShape] = {
    "train_4k":    InputShape("train_4k",    4_096,   256, "train"),
    "prefill_32k": InputShape("prefill_32k", 32_768,   32, "prefill"),
    "decode_32k":  InputShape("decode_32k",  32_768,  128, "decode"),
    "long_500k":   InputShape("long_500k",  524_288,    1, "decode"),
}


# ---------------------------------------------------------------------------
# hardware profiles (for the memory model / MACT / roofline)
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class HardwareProfile:
    name: str
    hbm_bytes: float
    peak_flops: float               # bf16
    hbm_bw: float                   # bytes/s
    ici_bw: float                   # bytes/s per link
    alpha: float = 0.9              # usable-memory fraction (paper's alpha)


TPU_V5E = HardwareProfile("tpu-v5e", 16e9, 197e12, 819e9, 50e9)
GPU_64G = HardwareProfile("gpu-64g", 64e9, 197e12, 819e9, 50e9)   # paper's 64 GB devices

#: The profile each ``jax.Device.device_kind`` plans against.  The CPU
#: backend runs the tests and the rehearsals of chip runs, so it plans
#: against the v5e it rehearses for.
DEVICE_PROFILES = {"TPU v5 lite": TPU_V5E, "cpu": TPU_V5E}


def device_profile(kind: Optional[str] = None) -> HardwareProfile:
    """The hardware profile of ``kind`` (default: the first local device's
    ``device_kind``).  A kind with no profile is an error, never a default."""
    if kind is None:
        import jax
        kind = jax.devices()[0].device_kind
    if kind not in DEVICE_PROFILES:
        raise ValueError(f"no hardware profile for device kind {kind!r}; "
                         f"known: {sorted(DEVICE_PROFILES)}")
    return DEVICE_PROFILES[kind]


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

_SKIP_MODULES = {"base", "__init__"}


def registry() -> dict[str, ModelConfig]:
    """Import every config module in this package and collect CONFIG objects."""
    import repro.configs as pkg
    out: dict[str, ModelConfig] = {}
    for info in pkgutil.iter_modules(pkg.__path__):
        if info.name in _SKIP_MODULES:
            continue
        mod = importlib.import_module(f"repro.configs.{info.name}")
        cfg = getattr(mod, "CONFIG", None)
        if cfg is not None:
            out[cfg.name] = cfg
        extra = getattr(mod, "CONFIGS", ())
        for c in extra:
            out[c.name] = c
    return out


def get_config(name: str) -> ModelConfig:
    reg = registry()
    if name not in reg:
        raise KeyError(f"unknown arch {name!r}; available: {sorted(reg)}")
    return reg[name]


def long_context_eligible(cfg: ModelConfig) -> bool:
    """long_500k runs only for sub-quadratic archs (docs/DESIGN.md §4)."""
    return cfg.subquadratic


def decode_eligible(cfg: ModelConfig) -> bool:
    return True  # all assigned archs have a decoder; encoder-only would return False
