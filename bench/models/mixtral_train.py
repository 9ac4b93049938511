"""Plain float32 reference of a Mixtral training step (arXiv:2401.04088, the
Hugging Face ``MixtralForCausalLM`` equations, as in ``bench/models/
mixtral.py``), with its loss, its gradients and AdamW, written in
``jax.numpy`` at matmul precision HIGHEST.  It imports nothing of the
program under test.

Loss of a batch of B sequences of S tokens:

  loss = mean over all B*S positions of CE(logits, next token)
         + coef * (1 / n_moe) * sum over layers of aux_l
  aux_l = mean over the token groups g of  E / k * sum_e f_ge * P_ge
  f_ge  = share of g's tokens that route to e (top-k), no gradient
  P_ge  = mean over g's tokens of the router's softmax probability of e

Departures from Mixtral's whole-batch term, each the program's own
definition: the Switch term is taken per group and averaged, and divided by
k.  A group is one FCDA chunk of one EP device's tokens: the sequence axis
is split over ``devices`` peers, each peer's tokens run batch-major, and
each peer splits them into ``chunks`` equal chunks (``chunk_groups``).

The gradient is accumulated sequence by sequence.  The routing (top-k) and
f depend on the whole group, so a first pass runs the forward layer by
layer and fixes them; the second pass differentiates each sequence's share
of the loss with the top-k fixed and the aux term as a per-token weighting
of the router probabilities (exact: f carries no gradient).  Each expert
computes only the rows routed to it, gathered into an (E, cap, d) buffer
whose expert axis lies on the device that holds that expert's weights.

``quant`` makes the same step in int8 or fp8 (the serving reference's
``qdq``): every operand of every projection rounded, float32 accumulation,
and the rounding passed straight through in the backward pass.  That is the
control.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench.models.mixtral import Dims, _rms, _rope, _split, qdq

HIGHEST = jax.lax.Precision.HIGHEST
QUERY_BLOCK = 1024
EXPERT_KEYS = ("w1", "w3", "w2")
LAYER_KEYS = ("norm1", "wq", "wk", "wv", "wo", "norm2", "router") \
    + EXPERT_KEYS


# -- placement: no shard_map, only where each array lives --------------------

def specs(mesh) -> dict:
    """Where each weight lives on ``mesh`` (one axis, ``x``): experts by
    expert, attention by head, the head by vocabulary, the rest whole."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    def ns(*s):
        return NamedSharding(mesh, P(*s))
    layer = {"norm1": ns(), "wq": ns(None, "x"), "wk": ns(None, "x"),
             "wv": ns(None, "x"), "wo": ns("x", None), "norm2": ns(),
             "router": ns(), "w1": ns("x", None, None),
             "w3": ns("x", None, None), "w2": ns("x", None, None)}
    return {"embed": ns(), "head": ns(None, "x"), "final_norm": ns(),
            "layer": layer}


def place(params: dict, mesh) -> dict:
    """``params`` in float32, each leaf placed by ``specs``."""
    sp = specs(mesh)
    out = {k: jax.device_put(jnp.asarray(params[k], jnp.float32), sp[k])
           for k in ("embed", "head", "final_norm")}
    out["layers"] = [{k: jax.device_put(jnp.asarray(lp[k], jnp.float32),
                                        sp["layer"][k]) for k in LAYER_KEYS}
                     for lp in params["layers"]]
    return out


def flat(params: dict) -> dict:
    """``{"embed": .., "layers/0/wq": .., ...}``: the names the comparison
    uses."""
    out = {k: params[k] for k in ("embed", "head", "final_norm")}
    for i, lp in enumerate(params["layers"]):
        out.update({f"layers/{i}/{k}": v for k, v in lp.items()})
    return out


# -- the equations -------------------------------------------------------------

def _q(x, fmt, axis):
    """``x`` rounded to ``fmt``, the rounding passed straight through in the
    backward pass."""
    if fmt is None:
        return x
    return x + jax.lax.stop_gradient(qdq(x, fmt, axis) - x)


def _mm(x, w, quant):
    wq, aq = _split(quant)
    return jnp.dot(_q(x, aq, -1), _q(w, wq, -2), precision=HIGHEST)


def _attention(lp, x, dm: Dims, quant):
    """x (S, d) -> x + Attn(RMSNorm(x)), causal, GQA grouped by KV head."""
    S = x.shape[0]
    pos = jnp.arange(S)
    G = dm.h // dm.kv
    hn = _rms(x, lp["norm1"], dm.eps)
    q = _rope(_mm(hn, lp["wq"], quant).reshape(S, dm.h, dm.hd), pos,
              dm.theta).reshape(S, dm.kv, G, dm.hd)
    k = _rope(_mm(hn, lp["wk"], quant).reshape(S, dm.kv, dm.hd), pos,
              dm.theta)
    v = _mm(hn, lp["wv"], quant).reshape(S, dm.kv, dm.hd)
    qb = min(QUERY_BLOCK, S)

    @jax.checkpoint
    def block(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * qb, qb)
        qp = i * qb + jnp.arange(qb)
        s = jnp.einsum("qkgd,tkd->kgqt", qi, k,
                       precision=HIGHEST) * dm.hd ** -0.5
        m = pos[None, :] <= qp[:, None]
        if dm.window:
            m &= pos[None, :] > qp[:, None] - dm.window
        p = jax.nn.softmax(jnp.where(m, s, -jnp.inf), axis=-1)
        return jnp.einsum("kgqt,tkd->qkgd", p, v, precision=HIGHEST)

    o = jax.lax.map(block, jnp.arange(S // qb)).reshape(S, dm.h * dm.hd)
    return x + _mm(o, lp["wo"], quant)


def _probs(lp, h, dm: Dims):
    """RMSNorm(h) and the router's softmax (float32, never rounded: the
    router is not a projection the control rounds)."""
    xn = _rms(h, lp["norm2"], dm.eps)
    return xn, jax.nn.softmax(jnp.dot(xn, lp["router"], precision=HIGHEST),
                              -1)


def _experts(lp, xn, probs, topk, idx, keep, dm: Dims, quant):
    """sum over the top-k experts e of g_e * SwiGLU_e(xn): each expert over
    the rows ``idx[e]`` routed to it (``keep`` 0 on padding rows)."""
    top = jnp.take_along_axis(probs, topk, -1)
    top = top / jnp.sum(top, -1, keepdims=True)
    gates = jnp.sum(jax.nn.one_hot(topk, dm.e) * top[..., None], 1)  # (S, E)
    g = jnp.take_along_axis(gates.T, idx, 1) * keep                 # (E, cap)
    wq, aq = _split(quant)
    xe = _q(xn[idx], aq, -1)                                         # (E, cap, d)
    w1, w3, w2 = (_q(lp[k], wq, -2) for k in EXPERT_KEYS)
    h = (jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, w1, precision=HIGHEST))
         * jnp.einsum("ecd,edf->ecf", xe, w3, precision=HIGHEST))
    o = jnp.einsum("ecf,efd->ecd", _q(h, aq, -1), w2, precision=HIGHEST)
    o = o * g[..., None]
    return jnp.zeros_like(xn).at[idx.reshape(-1)].add(
        o.reshape(-1, xn.shape[-1]))


def _logits(params, x, dm: Dims, quant):
    xn = _rms(x, params["final_norm"], dm.eps)
    return _mm(xn, params["head"], quant)[:, :dm.v]


@functools.partial(jax.jit, static_argnames=("dm", "quant"))
def _embed(embed, tokens, dm: Dims, quant):
    return _q(jnp.take(embed, tokens, axis=0), _split(quant)[0], -1)


@functools.partial(jax.jit, static_argnames=("dm", "quant"))
def _attn_route(lp, x, dm: Dims, quant):
    """Pass 1, one layer: (the residual after attention, RMSNorm of it, the
    router's probabilities, its top-k)."""
    h = _attention(lp, x, dm, quant)
    xn, probs = _probs(lp, h, dm)
    return h, xn, probs, jax.lax.top_k(probs, dm.k)[1]


@functools.partial(jax.jit, static_argnames=("dm", "quant"))
def _expert_residual(lp, h, xn, probs, topk, idx, keep, dm: Dims, quant):
    return h + _experts(lp, xn, probs, topk, idx, keep, dm, quant)


def _seq_loss(params, tokens, labels, routes, auxw, n_tokens, dm: Dims,
              quant):
    """One sequence's share of the batch loss, with the routing fixed."""
    x = _q(jnp.take(params["embed"], tokens, axis=0), _split(quant)[0], -1)
    aux = 0.0
    for lp, (topk, idx, keep), w in zip(params["layers"], routes, auxw):
        h = _attention(lp, x, dm, quant)
        xn, probs = _probs(lp, h, dm)
        aux = aux + jnp.sum(w * probs)
        x = h + _experts(lp, xn, probs, topk, idx, keep, dm, quant)
    logits = _logits(params, x, dm, quant)
    gold = jnp.take_along_axis(logits, labels[:, None], -1)[:, 0]
    ce = jnp.sum(jax.nn.logsumexp(logits, -1) - gold) / n_tokens
    return ce + aux


@functools.partial(jax.jit, static_argnames=("dm", "quant"),
                   donate_argnums=(0,))
def _accumulate(acc, params, tokens, labels, routes, auxw, n_tokens,
                dm: Dims, quant):
    """(loss, grads) of the batch so far, plus this sequence's share."""
    loss, g = jax.value_and_grad(_seq_loss)(params, tokens, labels, routes,
                                            auxw, n_tokens, dm, quant)
    return jax.tree.map(jnp.add, acc, (loss, g))


def _zeros(params):
    """(0, zeros like ``params``), each leaf where its parameter lives (not
    under ``jit``, whose zeros would all land on one device)."""
    return jnp.float32(0), jax.tree.map(jnp.zeros_like, params)


@jax.jit
def leaf_norms(tree):
    """The float32 Frobenius norm of every leaf."""
    return jax.tree.map(lambda a: jnp.sqrt(jnp.sum(jnp.square(
        a.astype(jnp.float32)))), tree)


@jax.jit
def _diff_norms(a, b):
    return jax.tree.map(lambda x, y: jnp.sqrt(jnp.sum(jnp.square(x - y))),
                        a, b)


@functools.partial(jax.jit, static_argnames=("hp",), donate_argnums=(0, 2))
def adamw(params, grads, opt, step, hp: tuple):
    """One AdamW step with global-norm clipping (``hp``: b1, b2, eps,
    weight decay, clip norm, lr).  Returns (params, (mu, nu), the clipped
    gradient)."""
    b1, b2, eps, wd, clip, lr = hp
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                         for g in jax.tree.leaves(grads)))
    g = jax.tree.map(lambda x: x * jnp.minimum(1.0, clip / jnp.maximum(
        gnorm, 1e-9)), grads)
    mu = jax.tree.map(lambda m, x: b1 * m + (1 - b1) * x, opt[0], g)
    nu = jax.tree.map(lambda v, x: b2 * v + (1 - b2) * x * x, opt[1], g)
    bc1, bc2 = 1 - b1 ** step, 1 - b2 ** step
    new = jax.tree.map(
        lambda p, m, v: p - lr * ((m / bc1) / (jnp.sqrt(v / bc2) + eps)
                                  + wd * p), params, mu, nu)
    return new, (mu, nu), g


# -- routing data of pass 1 ----------------------------------------------------

def chunk_groups(batch: int, seq: int, devices: int, chunks: int
                 ) -> np.ndarray:
    """(batch, seq) group id of each token: the sequence axis split over
    ``devices`` peers, each peer's tokens batch-major, cut into ``chunks``
    equal chunks; group = peer * chunks + chunk."""
    per = seq // devices
    if seq % devices or (batch * per) % chunks:
        raise ValueError(f"{batch}x{seq} tokens do not split over {devices} "
                         f"devices and {chunks} chunks")
    b, p = np.meshgrid(np.arange(batch), np.arange(seq), indexing="ij")
    local = b * per + p % per
    return (p // per) * chunks + local // (batch * per // chunks)


def aux_weights(topk: np.ndarray, groups: np.ndarray, dm: Dims, coef: float,
                n_moe: int) -> np.ndarray:
    """(B, S, E) weights w such that sum(w * probs) over every token is one
    layer's aux term, times coef / n_moe (f fixed from ``topk``)."""
    n = int(groups.max()) + 1
    hit = np.zeros(groups.shape + (dm.e,), np.float64)
    np.put_along_axis(hit, topk, 1.0, axis=-1)
    size = np.bincount(groups.ravel(), minlength=n).astype(np.float64)
    f = np.zeros((n, dm.e))
    np.add.at(f, groups.ravel(), hit.reshape(-1, dm.e))
    f /= size[:, None]
    w = coef * dm.e * f[groups] / (dm.k * n * n_moe * size[groups][..., None])
    return w.astype(np.float32)


def expert_rows(topk: np.ndarray, e: int, cap: int,
                keep: Optional[np.ndarray] = None):
    """(idx (E, cap) int32, keep (E, cap) float32): the rows of one sequence
    routed to each expert, padded with row 0 at weight 0.  ``keep`` (S, E)
    bool, when given, drops the (row, expert) pairs it marks False."""
    hit = np.zeros((topk.shape[0], e), bool)
    np.put_along_axis(hit, topk, True, axis=-1)
    if keep is not None:
        hit &= keep
    idx = np.zeros((e, cap), np.int32)
    ok = np.zeros((e, cap), np.float32)
    for j in range(e):
        rows = np.nonzero(hit[:, j])[0]
        idx[j, :len(rows)] = rows
        ok[j, :len(rows)] = 1.0
    return idx, ok


def _cap(topk: np.ndarray, dm: Dims, multiple: int) -> int:
    """Rows per expert buffer for a batch's (B, S, k) routing: the most any
    sequence routes to one expert, rounded up to ``multiple`` (so that few
    buffer shapes compile), at most S."""
    most = max(np.bincount(t.ravel(), minlength=dm.e).max() for t in topk)
    return int(min(-(-most // multiple) * multiple, topk.shape[1]))


def local_pairs(topk: np.ndarray, dm: Dims, devices: int) -> np.ndarray:
    """(S, E) bool: the expert lies on the device that holds the token
    (the sequence axis split over ``devices``).  The fault that leaves the
    exchange between chips out keeps only these pairs."""
    S = topk.shape[0]
    tok_dev = np.arange(S) // (S // devices)
    exp_dev = np.arange(dm.e) // (dm.e // devices)
    return tok_dev[:, None] == exp_dev[None, :]


# -- a step and three --------------------------------------------------------

def batch_loss_grads(params, tokens: np.ndarray, labels: np.ndarray,
                     dm: Dims, *, devices: int, chunks: int, coef: float,
                     cap_multiple: int, quant: Optional[str] = None,
                     local_only: bool = False):
    """(loss, grads) of one batch, sequence by sequence."""
    B, S = tokens.shape
    n_moe = len(params["layers"])
    groups = chunk_groups(B, S, devices, chunks)
    # pass 1: each layer's routing over the whole batch
    xs = [_embed(params["embed"], jnp.asarray(t), dm, quant) for t in tokens]
    topks = []
    for li, lp in enumerate(params["layers"]):
        if li + 1 == n_moe:          # the last layer: only its routing
            topks.append(np.stack([np.asarray(
                _attn_route(lp, x, dm, quant)[3]) for x in xs]))
            break
        outs = [_attn_route(lp, x, dm, quant) for x in xs]
        tk = np.stack([np.asarray(o[3]) for o in outs])
        topks.append(tk)
        cap = _cap(tk, dm, cap_multiple)
        nxt = []
        for (h, xn, probs, k_), t in zip(outs, tk):
            idx, keep = expert_rows(t, dm.e, cap, local_pairs(
                t, dm, devices) if local_only else None)
            nxt.append(_expert_residual(lp, h, xn, probs, k_, idx, keep, dm,
                                        quant))
        xs = nxt
    del xs
    auxw = [aux_weights(tk, groups, dm, coef, n_moe) for tk in topks]
    caps = [_cap(tk, dm, cap_multiple) for tk in topks]
    # pass 2: each sequence's share of the loss and its gradient
    acc = _zeros(params)
    for b in range(B):
        routes = []
        for tk, cap in zip(topks, caps):
            idx, keep = expert_rows(tk[b], dm.e, cap, local_pairs(
                tk[b], dm, devices) if local_only else None)
            routes.append((jnp.asarray(tk[b]), jnp.asarray(idx),
                           jnp.asarray(keep)))
        acc = _accumulate(acc, params, jnp.asarray(tokens[b]),
                          jnp.asarray(labels[b]), routes,
                          [jnp.asarray(w[b]) for w in auxw],
                          jnp.float32(B * S), dm, quant)
    return acc


def train_steps(params, batches: list, dm: Dims, hp: tuple, *, devices: int,
                chunks: list, coef: float, cap_multiple: int,
                quant: Optional[str] = None, local_only: bool = False,
                keep_grad1: bool = False) -> dict:
    """Run len(batches) AdamW steps from ``params`` (placed; not kept).
    ``chunks[i]`` is the chunk count of step i.  Returns, by ``flat`` name:
    ``losses`` (one per step), ``grad0`` (the first gradient's leaf norms),
    ``grad1`` (the same, clipped, as the optimizer takes it) and ``change``
    (the leaf norms of the parameters' change over all the steps); with
    ``keep_grad1`` also ``grad1_full``, the clipped first gradient itself on
    the host."""
    p0 = params
    p = jax.tree.map(jnp.copy, params)
    opt = (jax.tree.map(jnp.zeros_like, p), jax.tree.map(jnp.zeros_like, p))
    out = {"losses": []}
    for i, (tokens, labels) in enumerate(batches):
        loss, g = batch_loss_grads(p, tokens, labels, dm, devices=devices,
                                   chunks=chunks[i], coef=coef,
                                   cap_multiple=cap_multiple, quant=quant,
                                   local_only=local_only)
        out["losses"].append(float(loss))
        if i == 0:
            out["grad0"] = _host(leaf_norms(flat(g)))
        p, opt, gc = adamw(p, g, opt, jnp.float32(i + 1), hp)
        if i == 0:
            out["grad1"] = _host(leaf_norms(flat(gc)))
            if keep_grad1:
                out["grad1_full"] = {k: np.asarray(v) for k, v in
                                     jax.device_get(flat(gc)).items()}
        del g, gc
    out["change"] = _host(_diff_norms(flat(p), flat(p0)))
    return out


def _host(tree: dict) -> dict:
    return {k: float(v) for k, v in tree.items()}
