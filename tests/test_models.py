"""Model-substrate unit tests: attention variants, SSD, caches, enc-dec."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.configs.base import AttentionSpec, SSMSpec
from repro.core.moe import DistContext
from repro.models import ssm as ssm_mod
from repro.models import transformer
from repro.models.attention import attention, decode_attention, repeat_kv

CTX = DistContext()


def _qkv(S=64, B=2, H=4, KH=2, hd=8, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (B, S, H, hd))
    k = jax.random.normal(ks[1], (B, S, KH, hd))
    v = jax.random.normal(ks[2], (B, S, KH, hd))
    return q, k, v


def _naive(q, k, v, causal=True, window=0, chunk=0):
    B, S, H, hd = q.shape
    k = repeat_kv(k, H)
    v = repeat_kv(v, H)
    s = jnp.einsum("bqhd,bshd->bhqs", q, k) / np.sqrt(hd)
    qpos = jnp.arange(S)[:, None]
    kpos = jnp.arange(S)[None, :]
    m = jnp.ones((S, S), bool)
    if causal:
        m &= kpos <= qpos
    if window:
        m &= kpos > qpos - window
    if chunk:
        m &= (kpos // chunk) == (qpos // chunk)
    s = jnp.where(m[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqs,bshd->bqhd", p, v)


def test_full_causal_matches_naive():
    q, k, v = _qkv()
    out = attention(q, k, v, AttentionSpec(kind="full"), block_q=16)
    np.testing.assert_allclose(np.asarray(out), np.asarray(_naive(q, k, v)),
                               atol=1e-5)


@pytest.mark.parametrize("window", [8, 16, 48])
def test_window_matches_naive(window):
    q, k, v = _qkv()
    out = attention(q, k, v, AttentionSpec(kind="window", window=window),
                    block_q=16)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(_naive(q, k, v, window=window)), atol=1e-5)


def test_chunked_matches_naive():
    q, k, v = _qkv()
    out = attention(q, k, v, AttentionSpec(kind="chunked", window=16),
                    block_q=8)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(_naive(q, k, v, chunk=16)), atol=1e-5)


def test_non_causal_cross():
    q, k, v = _qkv()
    out = attention(q, k, v, AttentionSpec(kind="full"), causal=False)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(_naive(q, k, v, causal=False)), atol=1e-5)


def test_block_size_invariance():
    q, k, v = _qkv()
    a = attention(q, k, v, AttentionSpec(kind="full"), block_q=8)
    b = attention(q, k, v, AttentionSpec(kind="full"), block_q=64)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_decode_attention_matches_last_row():
    q, k, v = _qkv(S=32)
    full = attention(q, k, v, AttentionSpec(kind="full"), block_q=8)
    dec = decode_attention(q[:, -1:], k, v,
                           jnp.full((2,), 32, jnp.int32),
                           AttentionSpec(kind="full"))
    np.testing.assert_allclose(np.asarray(dec[:, 0]), np.asarray(full[:, -1]),
                               atol=1e-5)


# ---------------------------------------------------------------------------
# SSD
# ---------------------------------------------------------------------------

SPEC = SSMSpec(state_dim=16, head_dim=8, expand=2, conv_width=4, chunk=8)


def test_ssd_chunk_invariance():
    params = ssm_mod.init_ssm(jax.random.PRNGKey(0), 32, SPEC)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 32)) * 0.5
    y1 = ssm_mod.apply_ssm(params, x, SPEC)
    y2 = ssm_mod.apply_ssm(params, x, dataclasses.replace(SPEC, chunk=16))
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=1e-5)


def test_ssd_decode_consistency():
    params = ssm_mod.init_ssm(jax.random.PRNGKey(0), 32, SPEC)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 33, 32)) * 0.5
    y_full, st_full = ssm_mod.apply_ssm(params, x, SPEC, return_state=True)
    _, st = ssm_mod.apply_ssm(params, x[:, :-1], SPEC, return_state=True)
    y_dec, st2 = ssm_mod.decode_ssm(params, x[:, -1:], st, SPEC)
    np.testing.assert_allclose(np.asarray(y_dec[:, 0]),
                               np.asarray(y_full[:, -1]), atol=1e-4)
    np.testing.assert_allclose(np.asarray(st2.ssm), np.asarray(st_full.ssm),
                               atol=1e-4)


def test_ssd_matches_naive_recurrence():
    """SSD chunked algorithm == step-by-step recurrence."""
    params = ssm_mod.init_ssm(jax.random.PRNGKey(0), 16, SPEC)
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 12, 16)) * 0.5
    y_ssd = ssm_mod.apply_ssm(params, x, SPEC)
    state = ssm_mod.init_state(1, 16, SPEC, x.dtype)
    ys = []
    for t in range(12):
        yt, state = ssm_mod.decode_ssm(params, x[:, t:t + 1], state, SPEC)
        ys.append(yt)
    y_rec = jnp.concatenate(ys, axis=1)
    np.testing.assert_allclose(np.asarray(y_ssd), np.asarray(y_rec), atol=1e-4)


# ---------------------------------------------------------------------------
# full-model decode == forward (incl. period-scan path), all families
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch", ["llama3.2-3b", "mixtral-8x7b",
                                  "jamba-1.5-large-398b", "mamba2-130m",
                                  "gemma3-27b", "whisper-small",
                                  "internvl2-76b"])
def test_decode_matches_forward(arch):
    cfg = dataclasses.replace(registry()[arch].reduced(), num_layers=4)
    params = transformer.init_params(jax.random.PRNGKey(0), cfg)
    B, S = 2, 32
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab_size)
    batch = {"tokens": toks}
    enc_out = None
    if cfg.encoder_layers:
        batch["frames"] = jax.random.normal(jax.random.PRNGKey(2),
                                            (B, cfg.encoder_seq, cfg.d_model))
        enc_out = transformer.encode(params, cfg, batch["frames"], CTX)
    if cfg.num_patch_tokens:
        batch["patches"] = jax.random.normal(
            jax.random.PRNGKey(3), (B, cfg.num_patch_tokens, cfg.d_model))
    full, _ = transformer.forward(params, cfg, CTX, batch)
    cache = transformer.init_cache(params, cfg, B, S + cfg.num_patch_tokens,
                                   jnp.float32, enc_out=enc_out)
    step = jax.jit(lambda c, t: transformer.decode_step(params, cfg, CTX, c, t))
    if cfg.num_patch_tokens:
        pytest.skip("patch positions enter via embeddings; decode tested via "
                    "token tail elsewhere")
    logits = None
    for i in range(S):
        logits, cache = step(cache, toks[:, i:i + 1])
    np.testing.assert_allclose(np.asarray(logits[:, 0]),
                               np.asarray(full[:, -1]), rtol=2e-4, atol=2e-4)


def test_init_cache_reads_no_decoder_weights():
    """A decoder-only cache is zeros: building it reads no weight.  Slicing
    whole stacked periods (to find cross-attention weights) copied each
    period's expert weights, a transient weight-sized peak on the chip."""
    cfg = dataclasses.replace(registry()["mixtral-8x7b"].reduced(),
                              num_layers=4)
    params = jax.eval_shape(
        lambda k: transformer.init_params(k, cfg), jax.random.PRNGKey(0))
    assert params["periods"] is not None
    jaxpr = jax.make_jaxpr(lambda p: transformer.init_cache(
        p, cfg, 2, 16, jnp.float32))(params).jaxpr
    read = {id(v) for eqn in jaxpr.eqns for v in eqn.invars}
    assert not read & {id(v) for v in jaxpr.invars}


def test_remat_policies_same_loss():
    from repro.training.step import loss_fn
    cfg = registry()["mixtral-8x7b"].reduced()
    batch = {
        "tokens": jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0,
                                     cfg.vocab_size),
        "labels": jax.random.randint(jax.random.PRNGKey(2), (2, 32), 0,
                                     cfg.vocab_size),
    }
    params = transformer.init_params(jax.random.PRNGKey(0), cfg)
    losses = []
    for policy in ("none", "full", "memfine"):
        c = dataclasses.replace(cfg, remat_policy=policy)
        losses.append(float(loss_fn(params, c, CTX, batch)[0]))
    assert max(losses) - min(losses) < 1e-5


def test_prefix_layers_decode_matches_forward():
    """ModelConfig.prefix (unrolled leading layers + scanned body, the
    DeepSeek-mini layout) is consistent between forward and decode."""
    base = registry()["deepseek-mini-8l"]
    cfg = dataclasses.replace(
        base.reduced(), prefix=base.reduced().pattern[:1], num_layers=5)
    assert cfg.num_periods == 2 and len(cfg.prefix) == 1
    params = transformer.init_params(jax.random.PRNGKey(0), cfg)
    B, S = 2, 16
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab_size)
    full, _ = transformer.forward(params, cfg, CTX, {"tokens": toks})
    cache = transformer.init_cache(params, cfg, B, S, jnp.float32)
    logits = None
    for i in range(S):
        logits, cache = transformer.decode_step(params, cfg, CTX, cache,
                                                toks[:, i:i + 1])
    np.testing.assert_allclose(np.asarray(logits[:, 0]),
                               np.asarray(full[:, -1]), rtol=2e-4, atol=2e-4)
