"""Plain float32 reference of Mixtral (arXiv:2401.04088; the Hugging Face
``MixtralForCausalLM`` equations), written in ``jax.numpy`` at matmul
precision HIGHEST.  It imports nothing of the program under test.

Equations per decoder layer, on the residual stream x:
  h = x + Attn(RMSNorm(x))      # GQA, RoPE (rotate-half), causal, window W
  x = h + sum_{e in top-k} g_e * SwiGLU_e(RMSNorm(h))
  g = softmax(router(RMSNorm(h))) restricted to its top-k, renormalised
and logits = RMSNorm(x) @ head.  RMSNorm(x) = x / sqrt(mean(x^2) + eps) * s.

The reference runs in blocks so that it fits beside the weights on one
chip: attention one sequence at a time and one query block at a time, and
each expert only over the rows routed to it, in fixed-size row blocks, with
its weights converted to float32 one expert at a time.

``quant`` makes the same computation in int8 or fp8 (e4m3): every operand of
every projection, the weight (one scale per output channel) and the
activation (one scale per row) alike, is rounded to it, with float32
accumulation.  That is the control: the precision below the configuration's
bfloat16, which the comparison must fail.  ``int8-weights`` and
``fp8-weights`` round the weights alone (weight-only quantisation).
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
QUERY_BLOCK = 512
ROW_BLOCK = 2048


@dataclass(frozen=True)
class Dims:
    d: int
    h: int
    kv: int
    hd: int
    e: int
    k: int
    f: int
    v: int
    window: int
    theta: float
    eps: float

    @classmethod
    def from_config(cls, c: dict) -> "Dims":
        return cls(d=c["hidden_size"], h=c["num_attention_heads"],
                   kv=c["num_key_value_heads"],
                   hd=c.get("head_dim") or c["hidden_size"]
                   // c["num_attention_heads"],
                   e=c["num_local_experts"], k=c["num_experts_per_tok"],
                   f=c["intermediate_size"], v=c["vocab_size"],
                   window=c.get("sliding_window") or 0,
                   theta=float(c["rope_theta"]), eps=float(c["rms_norm_eps"]))


@dataclass
class Layer:
    """One layer's weights.  The expert stacks are ``(..., E, in, out)``;
    ``at`` indexes their leading axes (e.g. the layer within a stack)."""
    norm1: jax.Array
    wq: jax.Array
    wk: jax.Array
    wv: jax.Array
    wo: jax.Array
    norm2: jax.Array
    router: jax.Array
    w1: jax.Array
    w3: jax.Array
    w2: jax.Array
    at: tuple = ()


@dataclass
class Weights:
    embed: jax.Array            # (V', d); rows past the vocabulary unused
    head: jax.Array             # (d, V')
    final_norm: jax.Array       # (d,)
    layers: list


def qdq(w: jax.Array, quant: Optional[str], axis: int = -2) -> jax.Array:
    """``w`` in float32, or rounded to ``quant`` with one scale per slice
    along ``axis`` (the input axis: one scale per output channel)."""
    w = w.astype(jnp.float32)
    if quant is None:
        return w
    amax = jnp.maximum(jnp.max(jnp.abs(w), axis=axis, keepdims=True), 1e-30)
    if quant == "int8":
        s = amax / 127.0
        return jnp.clip(jnp.round(w / s), -127, 127) * s
    if quant == "fp8":
        s = amax / 448.0
        return (w / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    raise ValueError(f"unknown quantisation {quant!r}")


def _split(quant: Optional[str]):
    """``quant`` -> (the format of the weights, that of the activations)."""
    if quant is not None and quant.endswith("-weights"):
        return quant[:-len("-weights")], None
    return quant, quant


def _mm(x, w, quant):
    """x @ w with the operands in ``quant`` (float32 accumulation)."""
    wq, aq = _split(quant)
    return jnp.dot(qdq(x, aq, axis=-1), qdq(w, wq), precision=HIGHEST)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, pos, theta):
    """x: (L, heads, hd); rotate-half RoPE."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos[:, None].astype(jnp.float32) * inv[None]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


@functools.partial(jax.jit, static_argnames=("dm", "quant"))
def _embed(embed, tokens, dm: Dims, quant):
    return qdq(jnp.take(embed, tokens, axis=0), _split(quant)[0], axis=-1)


@functools.partial(jax.jit, static_argnames=("dm", "quant"))
def _attention(x, norm1, wq, wk, wv, wo, dm: Dims, quant):
    """x: (n, L, d) -> x + Attn(RMSNorm(x)), one sequence at a time."""
    L = x.shape[1]
    pos = jnp.arange(L)
    G = dm.h // dm.kv

    def one(xs):
        hn = _rms(xs, norm1.astype(jnp.float32), dm.eps)
        q = _rope(_mm(hn, wq, quant).reshape(L, dm.h, dm.hd), pos, dm.theta)
        k = _rope(_mm(hn, wk, quant).reshape(L, dm.kv, dm.hd), pos, dm.theta)
        v = _mm(hn, wv, quant).reshape(L, dm.kv, dm.hd)
        k = jnp.repeat(k, G, axis=1)
        v = jnp.repeat(v, G, axis=1)

        def block(i):
            qb = jax.lax.dynamic_slice_in_dim(q, i * QUERY_BLOCK, QUERY_BLOCK)
            qp = i * QUERY_BLOCK + jnp.arange(QUERY_BLOCK)
            s = jnp.einsum("qhd,khd->hqk", qb, k,
                           precision=HIGHEST) * dm.hd ** -0.5
            m = pos[None, :] <= qp[:, None]
            if dm.window:
                m &= pos[None, :] > qp[:, None] - dm.window
            s = jnp.where(m[None], s, -jnp.inf)
            p = jax.nn.softmax(s, axis=-1)
            return jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST)

        o = jax.lax.map(block, jnp.arange(L // QUERY_BLOCK))
        return xs + _mm(o.reshape(L, dm.h * dm.hd), wo, quant)

    return jax.lax.map(one, x)


@functools.partial(jax.jit, static_argnames=("dm",))
def _route(x, norm2, router, dm: Dims):
    """-> (RMSNorm(x), dense (.., E) gates: renormalised top-k probs)."""
    xn = _rms(x, norm2.astype(jnp.float32), dm.eps)
    probs = jax.nn.softmax(
        jnp.dot(xn, router.astype(jnp.float32), precision=HIGHEST), -1)
    top, idx = jax.lax.top_k(probs, dm.k)
    top = top / jnp.sum(top, -1, keepdims=True)
    gates = jnp.sum(jax.nn.one_hot(idx, dm.e) * top[..., None], axis=-2)
    return xn, gates


@functools.partial(jax.jit, static_argnames=("quant",), donate_argnums=(1,))
def _expert_block(xn, y, rows, gate, w1, w3, w2, at, e, quant):
    """y[rows] += gate * SwiGLU_e(xn[rows]) for one block of rows.  The
    expert stacks are indexed by ``at`` (their leading axes) and then ``e``."""
    def sel(w):
        for i in range(at.shape[0]):
            w = jax.lax.dynamic_index_in_dim(w, at[i], 0, keepdims=False)
        return jax.lax.dynamic_index_in_dim(w, e, 0, keepdims=False)
    xr = jnp.take(xn, rows, axis=0)
    h = jax.nn.silu(_mm(xr, sel(w1), quant)) * _mm(xr, sel(w3), quant)
    out = _mm(h, sel(w2), quant) * gate[:, None]
    return y.at[rows].add(out)


@functools.partial(jax.jit, static_argnames=("dm", "quant"))
def _logits(x, final_norm, head, dm: Dims, quant):
    xn = _rms(x, final_norm.astype(jnp.float32), dm.eps)
    return _mm(xn, head, quant)[:, :dm.v]


def forward_rows(w: Weights, dm: Dims, seqs: list, want: list,
                 quant: Optional[str] = None) -> list:
    """Logits (float32, host) of sequence ``i`` at positions ``want[i]``.

    ``seqs``: token arrays.  All are padded to one length (a multiple of the
    query block), which causal attention never looks past."""
    n = len(seqs)
    L = max(len(s) for s in seqs)
    L = -(-L // QUERY_BLOCK) * QUERY_BLOCK
    toks = np.zeros((n, L), np.int32)
    for i, s in enumerate(seqs):
        toks[i, :len(s)] = s
    x = _embed(w.embed, jnp.asarray(toks), dm, quant)
    valid = np.concatenate([i * L + np.arange(len(s))
                            for i, s in enumerate(seqs)])
    for lay in w.layers:
        x = _attention(x, lay.norm1, lay.wq, lay.wk, lay.wv, lay.wo, dm,
                       quant)
        xn, gates = _route(x.reshape(n * L, dm.d), lay.norm2, lay.router, dm)
        g = np.asarray(gates)
        y = jnp.zeros((n * L, dm.d), jnp.float32)
        at = jnp.asarray(lay.at, jnp.int32)
        for e in range(dm.e):
            rows = valid[g[valid, e] > 0]
            pad = -len(rows) % ROW_BLOCK
            rows = np.concatenate([rows, np.zeros(pad, np.int64)])
            gate = np.concatenate([g[rows[:len(rows) - pad], e],
                                   np.zeros(pad, np.float32)])
            for b in range(0, len(rows), ROW_BLOCK):
                y = _expert_block(
                    xn, y, jnp.asarray(rows[b:b + ROW_BLOCK], jnp.int32),
                    jnp.asarray(gate[b:b + ROW_BLOCK], jnp.float32),
                    lay.w1, lay.w3, lay.w2, at, jnp.int32(e), quant)
        x = x + y.reshape(n, L, dm.d)
    out = []
    for i, pos in enumerate(want):
        rows = x[i, jnp.asarray(np.asarray(pos, np.int32))]
        out.append(np.asarray(_logits(rows, w.final_norm, w.head, dm, quant)))
    return out


def _positions(prompts: list, outs: list):
    """The sequences the reference reads (each prompt with all but its last
    served token) and, in each, the positions that predicted a served
    token."""
    seqs = [np.concatenate([p, np.asarray(o[:-1], np.int32)])
            for p, o in zip(prompts, outs)]
    want = [np.arange(len(p) - 1, len(p) - 1 + len(o))
            for p, o in zip(prompts, outs)]
    return seqs, want


def served_logits(w: Weights, dm: Dims, prompts: list, outs: list) -> list:
    """The float32 reference's logits at every served position."""
    return forward_rows(w, dm, *_positions(prompts, outs))


def served_gaps(w: Weights, dm: Dims, prompts: list, outs: list,
                quant: Optional[str] = None, ref: Optional[list] = None
                ) -> list:
    """For each request (prompt, served tokens): the gap by which each
    served token's reference logit lies below the reference's best at that
    position.  With ``quant`` the reference is the float32 one and the
    served token is the one the quantised model puts first (the control).
    ``ref``: ``served_logits``, when already computed."""
    seqs, want = _positions(prompts, outs)
    if ref is None:
        ref = forward_rows(w, dm, seqs, want)
    if quant is None:
        toks = [np.asarray(o) for o in outs]
    else:
        ctl = forward_rows(w, dm, seqs, want, quant)
        toks = [np.argmax(c, axis=-1) for c in ctl]
    return [r.max(-1) - r[np.arange(len(t)), t] for r, t in zip(ref, toks)]
