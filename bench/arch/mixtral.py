"""Mixtral (Hugging Face ``model_type`` "mixtral"; arXiv:2401.04088) for the
benchmark: the program's model for a configuration file, the plain
references and their view of the benchmark-made weights, and the operations
and bytes the published model needs.

Counting: only the work the model itself requires is counted: the two routed
experts of each token (top-k, not every expert and not a padded capacity),
the router, the attention projections, causal attention scores over the
context each token actually sees (capped by the sliding window), and the LM
head only where a logit is needed.  Recomputation, capacity padding and the
dense copies a program may make are not model work, so a program that does
less of them can only read higher, and a share of a peak built on these
counts cannot pass 100% unless the timing leaves out work.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from bench import flops
from bench.models import mixtral as serve_reference
from bench.models import mixtral_train as train_reference

#: a program layer's leaf -> the training reference layer's key
LAYER_LEAVES = {("norm1", "scale"): "norm1", ("mixer", "wq"): "wq",
                ("mixer", "wk"): "wk", ("mixer", "wv"): "wv",
                ("mixer", "wo"): "wo", ("norm2", "scale"): "norm2",
                ("ffn", "router", "w"): "router", ("ffn", "w1"): "w1",
                ("ffn", "w3"): "w3", ("ffn", "w2"): "w2"}


def dims(c: dict) -> serve_reference.Dims:
    return serve_reference.Dims.from_config(c)


# -- the program --------------------------------------------------------------

def program_config(c: dict):
    """The program's ``ModelConfig`` for configuration file ``c``: the
    program's own architecture entry with every size taken from the file."""
    from repro.configs import get_config
    base = get_config(c["bench"]["program_arch"])
    if base.norm != "rmsnorm" or base.tie_embeddings != c[
            "tie_word_embeddings"]:
        raise ValueError(f"{base.name}: norm or embedding tie differs from "
                         "the configuration file")
    (spec,) = base.pattern
    attn = dataclasses.replace(spec.attn, window=c.get("sliding_window") or 0,
                               kind="window" if c.get("sliding_window")
                               else "full")
    moe = dataclasses.replace(base.moe, num_experts=c["num_local_experts"],
                              top_k=c["num_experts_per_tok"],
                              d_ff_expert=c["intermediate_size"])
    return dataclasses.replace(
        base, num_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"],
        head_dim=c.get("head_dim") or 0, d_ff=c["intermediate_size"],
        vocab_size=c["vocab_size"], rope_theta=float(c["rope_theta"]),
        pattern=(dataclasses.replace(spec, attn=attn),), moe=moe,
        dtype=c["torch_dtype"])


def train_program_config(c: dict):
    """The program's ``ModelConfig`` for training configuration ``c``: the
    serving mapping of the file's sizes, with float32 master weights, the
    file's remat policy, aux coefficient and expert strategy."""
    tr = c["bench"]["training"]
    cfg = program_config(c)
    moe = dataclasses.replace(cfg.moe,
                              router_aux_coef=float(c["router_aux_loss_coef"]),
                              strategy=tr["moe_strategy"])
    return dataclasses.replace(cfg, dtype=tr["master_dtype"],
                               remat_policy=tr["remat_policy"], moe=moe)


# -- the references' view of the weights --------------------------------------

def _layers(params: dict, layer) -> list:
    """``layer(p, at)`` of each decoder layer of the program's tree: scanned
    periods stacked on a leading axis (``at`` the index), then the unrolled
    remainder (``at`` None)."""
    out = []
    if params["periods"] is not None:
        (stack,) = params["periods"]
        for i in range(stack["norm1"]["scale"].shape[0]):
            out.append(layer(stack, i))
    out += [layer(p, None) for p in params["rem"]]
    return out


def reference_weights(params: dict) -> serve_reference.Weights:
    """The serving reference's view of the benchmark-made weights (the
    expert stacks stay whole, indexed by ``at``)."""
    def layer(p, at):
        pick = (lambda a: a[at]) if at is not None else (lambda a: a)
        return serve_reference.Layer(
            norm1=pick(p["norm1"]["scale"]), wq=pick(p["mixer"]["wq"]),
            wk=pick(p["mixer"]["wk"]), wv=pick(p["mixer"]["wv"]),
            wo=pick(p["mixer"]["wo"]), norm2=pick(p["norm2"]["scale"]),
            router=pick(p["ffn"]["router"]["w"]), w1=p["ffn"]["w1"],
            w3=p["ffn"]["w3"], w2=p["ffn"]["w2"],
            at=() if at is None else (at,))

    return serve_reference.Weights(
        embed=params["embed"], head=params["head"],
        final_norm=params["final_norm"]["scale"],
        layers=_layers(params, layer))


def _get(tree, parts):
    for p in parts:
        tree = tree[p]
    return tree


def reference_tree(params: dict) -> dict:
    """The training reference's weights from the program's tree: scanned
    periods unstacked, then the unrolled remainder."""
    def layer(p, at):
        pick = (lambda a: a[at]) if at is not None else (lambda a: a)
        return {key: pick(_get(p, parts))
                for parts, key in LAYER_LEAVES.items()}

    return {"embed": params["embed"], "head": params["head"],
            "final_norm": params["final_norm"]["scale"],
            "layers": _layers(params, layer)}


def reference_leaves(prog_leaf: str, n_periods: int) -> list:
    """The training reference's ``flat`` names of a program leaf: a leaf
    stacked over scanned periods holds one per period; a leaf the reference
    has no use for (the router's bias, unused without loss-free balancing)
    has none."""
    parts = prog_leaf.split("/")
    if parts[0] in ("embed", "head", "final_norm"):
        return [parts[0]]
    key = LAYER_LEAVES.get(tuple(parts[2:]))
    if key is None:
        return []
    if parts[0] == "periods":
        return [f"layers/{i}/{key}" for i in range(n_periods)]
    if parts[0] == "rem":
        return [f"layers/{n_periods + int(parts[1])}/{key}"]
    raise ValueError(f"no reference leaf for {prog_leaf!r}")


def train_reference_weights(make_params, devices):
    """The seed's weights again (``make_params``: the program's tree), made
    straight into the training reference's placement on ``devices``."""
    import jax
    from jax.sharding import Mesh
    mesh = Mesh(np.asarray(devices), ("x",))
    sp = train_reference.specs(mesh)
    shard = {"embed": sp["embed"], "head": sp["head"],
             "final_norm": sp["final_norm"]}

    def build():
        return reference_tree(make_params())

    shapes = jax.eval_shape(build)
    shard["layers"] = [sp["layer"]] * len(shapes["layers"])
    return jax.jit(build, out_shardings=shard)()


def reference_args(c: dict, devices: int, chunks: list) -> dict:
    """Keyword arguments of ``train_reference.train_steps`` for the
    configuration: the EP split, the chunk count of each step, the aux
    coefficient and AdamW's hyperparameters."""
    h = c["bench"]["training"]["adamw"]
    lr = float(c["bench"]["training"]["trainer"]["lr"])
    return {"hp": (float(h["b1"]), float(h["b2"]), float(h["eps"]),
                   float(h["weight_decay"]), float(h["clip_norm"]), lr),
            "devices": devices, "chunks": list(chunks),
            "coef": float(c["router_aux_loss_coef"]),
            "cap_multiple": int(c["bench"]["check"]["cap_multiple"])}


# -- operations and bytes -----------------------------------------------------

def _dims(cfg: dict) -> dict:
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    kv = cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or d // h
    return {"d": d, "h": h, "kv": kv, "hd": hd,
            "f": cfg["intermediate_size"], "e": cfg["num_local_experts"],
            "k": cfg["num_experts_per_tok"], "v": cfg["vocab_size"],
            "layers": cfg["num_hidden_layers"],
            "window": cfg.get("sliding_window") or 0}


def layer_flops_per_token(cfg: dict, context: int) -> float:
    """Forward FLOPs of one decoder layer for one token that attends to
    ``context`` positions (itself included; the window caps it)."""
    m = _dims(cfg)
    if m["window"]:
        context = min(context, m["window"])
    proj = 2 * m["d"] * (2 * m["h"] * m["hd"] + 2 * m["kv"] * m["hd"])
    scores = 2 * 2 * m["h"] * m["hd"] * context        # QK^T and PV
    router = 2 * m["d"] * m["e"]
    experts = m["k"] * 3 * 2 * m["d"] * m["f"]          # SwiGLU: w1, w3, w2
    return float(proj + scores + router + experts)


def head_flops(cfg: dict) -> float:
    """LM-head FLOPs for one token whose logits are needed."""
    m = _dims(cfg)
    return float(2 * m["d"] * m["v"])


def prefill_flops(cfg: dict, start: int, stop: int, logits: int = 0) -> float:
    """Forward FLOPs of prompt positions ``[start, stop)`` (position p sees
    p + 1 keys) through every layer, plus the head for ``logits`` tokens."""
    m = _dims(cfg)
    n = stop - start
    per_layer = (layer_flops_per_token(cfg, 0) * n
                 + 2 * 2 * m["h"] * m["hd"]
                 * flops.context_sum(start, stop, m["window"]))
    return float(m["layers"] * per_layer + logits * head_flops(cfg))


def weight_bytes(cfg: dict, bytes_per_param: float = 2.0,
                 experts_read: int | None = None) -> float:
    """Bytes of the decoder's weights (attention, router, experts, norms) plus
    the LM head; ``experts_read`` experts per layer (default all of them).
    The embedding table is excluded: a step reads only its rows."""
    m = _dims(cfg)
    e = m["e"] if experts_read is None else experts_read
    attn = m["d"] * (2 * m["h"] * m["hd"] + 2 * m["kv"] * m["hd"])
    experts = e * 3 * m["d"] * m["f"]
    router = m["d"] * m["e"]
    norms = 2 * m["d"]
    head = m["d"] * m["v"]
    return float(bytes_per_param * (m["layers"] * (attn + experts + router
                                                   + norms) + head + m["d"]))
