"""Dry-run machinery: abstract inputs, lowering, HLO analysis, roofline terms.

Used by launch/dryrun.py (CLI) and benchmarks/roofline.py.  Everything here
operates on ShapeDtypeStructs — no device allocation ever happens; the
``.lower().compile()`` succeeding per (arch x shape x mesh) is the deliverable.

Conventions:
  * ``cost_analysis()``/``memory_analysis()`` of the SPMD-partitioned module
    are PER DEVICE (verified on this backend); the roofline divides by
    per-chip peaks directly.
  * collective bytes = sum of output-shape bytes of every all-reduce /
    all-gather / reduce-scatter / all-to-all / collective-permute in the
    optimized HLO, per device.
"""

from __future__ import annotations

import dataclasses
import functools
import re
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import (InputShape, ModelConfig, SHAPES, TPU_V5E,
                                get_config, long_context_eligible)
from repro.core.mact import MACTController
from repro.core.memory_model import Parallelism
from repro.core.moe import DistContext
from repro.data.pipeline import make_batch_specs
from repro.distributed import sharding as shd
from repro.models import transformer
from repro.training.step import init_train_state, make_train_step
from repro.serving.engine import make_serve_step

DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "u64": 8,
               "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
               "pred": 1, "c64": 8, "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(shape_str: str) -> int:
    """Bytes of one HLO shape string, e.g. 'bf16[8,128]' or a tuple thereof."""
    total = 0
    for m in _SHAPE_RE.finditer(shape_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> dict:
    """Per-collective-kind output bytes (per device) from optimized HLO."""
    out = {k: 0 for k in _COLLECTIVES}
    counts = {k: 0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        line = line.strip()
        m = re.match(r"(?:ROOT )?%?[\w.\-]+ = (.+?) (all-reduce|all-gather|"
                     r"reduce-scatter|all-to-all|collective-permute)"
                     r"(?:-start|-done)?\(", line)
        if not m:
            continue
        kind = m.group(2)
        if "-done" in line.split("(")[0]:
            continue  # avoid double counting start/done pairs
        out[kind] += _shape_bytes(m.group(1))
        counts[kind] += 1
    return {"bytes": out, "counts": counts,
            "total_bytes": int(sum(out.values()))}


# ---------------------------------------------------------------------------
# context / abstract inputs per (arch, shape, mesh)
# ---------------------------------------------------------------------------

def mesh_dims(mesh: Mesh) -> dict:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def build_context(cfg: ModelConfig, shape: InputShape, mesh: Mesh, *,
                  chunks: Optional[int] = None, use_pallas: bool = False,
                  strategy: str = "auto",
                  flags: Optional[dict] = None) -> tuple[ModelConfig, DistContext]:
    """``flags`` are the beyond-paper optimization knobs (docs/DESIGN.md §Perf):
      seq_shard_acts   — shard inter-layer activations (B,S,d) on S over
                         'model' (sequence parallelism; cuts stored-x memory
                         and turns TP all-reduces into RS/AG pairs)
      prefill_chunks   — apply FCDA chunking to the MoE in *inference prefill*
                         (the paper only chunks training)
    """
    flags = flags or {}
    B = shape.global_batch
    if chunks is None:
        chunks = choose_chunks(cfg, shape, mesh)
    if shape.mode == "prefill":
        chunks = int(flags.get("prefill_chunks", 1))
    elif shape.mode != "train":
        chunks = 1
    seq_ax = "model" if flags.get("seq_shard_acts") and \
        shape.seq_len % shd.axis_size(mesh, "model") == 0 else None
    ctx = DistContext(
        mesh=mesh,
        ep_axis="model",
        moe_chunks=chunks,
        remat_chunks=True,
        use_pallas=use_pallas or bool(flags.get("pallas_interpret")),
        moe_strategy=strategy,
        moe_ragged=bool(flags.get("moe_ragged")),
        moe_fused=bool(flags.get("moe_fused")),
        pallas_interpret=bool(flags.get("pallas_interpret")),
        **shd.context_shardings(mesh, cfg, B, seq_ax),
    )
    return cfg, ctx


def choose_chunks(cfg: ModelConfig, shape: InputShape, mesh: Mesh) -> int:
    """MACT cold-start chunk choice for the paper-faithful baseline (worst
    case s' -> e*s*k against the TPU v5e profile)."""
    if cfg.moe is None or shape.mode != "train":
        return 1
    dims = mesh_dims(mesh)
    model_ax = dims.get("model", 1)
    batch_div = dims.get("data", 1) * dims.get("pod", 1)
    b = max(1, shape.global_batch // batch_div)
    if cfg.moe.num_experts % model_ax == 0:
        par = Parallelism(e=model_ax, b=b)      # ep_shardmap strategy
    else:
        par = Parallelism(t=model_ax, e=1, b=b) # tp_gspmd fallback
    mact = MACTController(cfg, par, TPU_V5E, seq_len=shape.seq_len)
    return mact.choose()


def _with_shardings(tree_sds, tree_shardings):
    return jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        tree_sds, tree_shardings)


def _zero1_shardings(p_shard, state_sds, mesh: Mesh):
    """ZeRO-1-style optimizer-state sharding: extend each param's spec with
    the data axes on the first unsharded, divisible dim (mu/nu are only
    touched at the optimizer step, so the extra gather cost is per-step)."""
    ba = shd.batch_axes(mesh)
    n = shd.axis_size(mesh, ba)

    def extend(sharding, leaf):
        spec = list(sharding.spec) + [None] * (len(leaf.shape) - len(sharding.spec))
        for i, (s, d) in enumerate(zip(spec, leaf.shape)):
            if s is None and d % n == 0 and d >= n:
                spec[i] = ba
                break
        return NamedSharding(mesh, P(*spec))

    return jax.tree.map(extend, p_shard, state_sds.params)


def abstract_train_args(cfg: ModelConfig, shape: InputShape, mesh: Mesh,
                        dtype=jnp.bfloat16, flags: Optional[dict] = None):
    flags = flags or {}
    state_sds = jax.eval_shape(
        lambda k: init_train_state(k, cfg, dtype=dtype),
        jax.ShapeDtypeStruct((2,), jnp.uint32))
    p_shard = shd.param_shardings(state_sds.params, mesh, cfg)
    opt_shard = (_zero1_shardings(p_shard, state_sds, mesh)
                 if flags.get("opt_shard_data") else p_shard)
    state_shardings = type(state_sds)(
        params=p_shard,
        opt=type(state_sds.opt)(
            step=NamedSharding(mesh, P()),
            mu=opt_shard, nu=opt_shard),
        step=NamedSharding(mesh, P()),
    )
    state_abs = _with_shardings(state_sds, state_shardings)

    batch_sds = make_batch_specs(cfg, shape, dtype=jnp.bfloat16)
    B = shape.global_batch
    batch_shardings = {
        k: NamedSharding(mesh, shd.batch_pspec(mesh, B) if v.ndim == 2
                         else P(shd.guarded(mesh, B, shd.batch_axes(mesh)),
                                None, None))
        for k, v in batch_sds.items()}
    batch_abs = _with_shardings(batch_sds, batch_shardings)
    return state_abs, batch_abs


def abstract_params(cfg: ModelConfig, mesh: Mesh, dtype=jnp.bfloat16):
    p_sds = jax.eval_shape(
        lambda k: transformer.init_params(k, cfg, dtype=dtype),
        jax.ShapeDtypeStruct((2,), jnp.uint32))
    return _with_shardings(p_sds, shd.param_shardings(p_sds, mesh, cfg))


def abstract_cache(cfg: ModelConfig, shape: InputShape, mesh: Mesh,
                   params_abs, dtype=jnp.bfloat16):
    B, S = shape.global_batch, shape.seq_len
    enc_abs = None
    if cfg.encoder_layers:
        enc_abs = jax.ShapeDtypeStruct((B, cfg.encoder_seq, cfg.d_model), dtype)
    cache_sds = jax.eval_shape(
        functools.partial(transformer.init_cache, cfg=cfg, batch_size=B,
                          seq_len=S, dtype=dtype),
        params=jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype),
                            params_abs),
        enc_out=enc_abs)
    cache_shardings = jax.tree.map(
        lambda s: NamedSharding(mesh, shd.cache_pspec(mesh, s.shape, B)),
        cache_sds)
    return _with_shardings(cache_sds, cache_shardings)


# ---------------------------------------------------------------------------
# lowering per mode
# ---------------------------------------------------------------------------

def lower_combo(arch: str, shape_name: str, mesh: Mesh, *,
                chunks: Optional[int] = None, strategy: str = "auto",
                dtype=jnp.bfloat16, extra_cfg: Optional[dict] = None,
                flags: Optional[dict] = None):
    """Lower the step for one (arch, shape) on ``mesh``; returns (lowered, meta)."""
    cfg = get_config(arch)
    if extra_cfg:
        cfg = dataclasses.replace(cfg, **extra_cfg)
    shape = SHAPES[shape_name]
    if shape.name == "long_500k" and not long_context_eligible(cfg):
        raise SkipCombo(f"{arch} is full-attention — long_500k skipped "
                        f"(docs/DESIGN.md §4)")
    cfg, ctx = build_context(cfg, shape, mesh, chunks=chunks, strategy=strategy,
                             flags=flags)
    meta = {"arch": arch, "shape": shape_name, "mode": shape.mode,
            "mesh_dims": dict(mesh_dims(mesh)), "chunks": ctx.moe_chunks,
            "flags": dict(flags or {}),
            "dtype": str(dtype.__name__ if hasattr(dtype, '__name__') else dtype)}

    with jax.set_mesh(mesh):
        if shape.mode == "train":
            state_abs, batch_abs = abstract_train_args(cfg, shape, mesh, dtype,
                                                       flags=flags)
            step = make_train_step(cfg, ctx, lr=1e-4)
            lowered = jax.jit(step).lower(state_abs, batch_abs)
        elif shape.mode == "prefill":
            params_abs = abstract_params(cfg, mesh, dtype)
            batch_sds = make_batch_specs(cfg, shape, dtype=dtype)
            batch_sds.pop("labels")
            B = shape.global_batch
            batch_abs = _with_shardings(batch_sds, {
                k: NamedSharding(mesh, shd.batch_pspec(mesh, B) if v.ndim == 2
                                 else P(shd.guarded(mesh, B, shd.batch_axes(mesh)),
                                        None, None))
                for k, v in batch_sds.items()})

            def prefill_step(params, batch):
                logits, _ = transformer.forward(params, cfg, ctx, batch)
                return logits

            lowered = jax.jit(prefill_step).lower(params_abs, batch_abs)
        else:  # decode
            params_abs = abstract_params(cfg, mesh, dtype)
            cache_abs = abstract_cache(cfg, shape, mesh, params_abs, dtype)
            B = shape.global_batch
            tok_abs = jax.ShapeDtypeStruct(
                (B, 1), jnp.int32,
                sharding=NamedSharding(mesh, shd.batch_pspec(mesh, B)))
            step = make_serve_step(cfg, ctx)
            lowered = jax.jit(step).lower(params_abs, cache_abs, tok_abs)
    return lowered, meta


class SkipCombo(Exception):
    pass


# ---------------------------------------------------------------------------
# analysis
# ---------------------------------------------------------------------------

def analyse(lowered, compiled, hw=TPU_V5E, chips: int = 1) -> dict:
    from repro.launch import hlo_analysis
    ma = compiled.memory_analysis()
    ca = compiled.cost_analysis() or {}
    txt = compiled.as_text()
    coll = collective_bytes(txt)
    # scan-aware re-derivation: cost_analysis counts while bodies ONCE, which
    # under-reports layer-scanned models by the trip count (docs/DESIGN.md §7)
    scan = hlo_analysis.analyse_module(txt)
    flops = float(scan["flops"]) or float(ca.get("flops", 0.0))
    bytes_acc = float(scan["hbm_bytes"]) or float(ca.get("bytes accessed", 0.0))
    coll_total = float(scan["collective_total"]) or coll["total_bytes"]
    t_compute = flops / hw.peak_flops
    t_memory = bytes_acc / hw.hbm_bw
    t_coll = coll_total / hw.ici_bw
    dominant = max(("compute", t_compute), ("memory", t_memory),
                   ("collective", t_coll), key=lambda kv: kv[1])[0]
    return {
        "memory": {
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "alias_bytes": ma.alias_size_in_bytes,
            "peak_device_gb": (ma.argument_size_in_bytes
                               + ma.temp_size_in_bytes) / 1e9,
            "fits_v5e": (ma.argument_size_in_bytes + ma.temp_size_in_bytes)
                        < hw.alpha * hw.hbm_bytes,
        },
        "cost": {"flops_per_device": flops, "bytes_per_device": bytes_acc,
                 "xla_cost_analysis_flops": float(ca.get("flops", 0.0)),
                 "xla_cost_analysis_bytes": float(ca.get("bytes accessed", 0.0))},
        "collectives": {**coll, "scan_aware": scan["collective_bytes"],
                        "total_bytes": coll_total},
        "roofline": {
            "t_compute_s": t_compute,
            "t_memory_s": t_memory,
            "t_collective_s": t_coll,
            "dominant": dominant,
        },
    }
