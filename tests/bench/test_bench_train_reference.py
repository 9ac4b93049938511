"""The training reference (``bench/models/mixtral_train.py``), the training
batch generator (``bench/train_data.py``), the exposed share of a
collective (``bench/collectives.py``) and the training FLOP count, on the
CPU at toy sizes."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import collectives, flops, train_data, trace
from bench.models import mixtral_train as mt
from bench.models.mixtral import Dims

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
DM = Dims(d=32, h=4, kv=2, hd=8, e=8, k=2, f=48, v=64, window=0,
          theta=10000.0, eps=1e-5)


def _params(seed, layers=2):
    ks = iter(jax.random.split(jax.random.PRNGKey(seed), 16 * layers + 4))
    n = lambda *s, sc=1.0: sc * jax.random.normal(next(ks), s)  # noqa: E731
    d, f, e = DM.d, DM.f, DM.e
    lay = [{"norm1": 1 + n(d, sc=0.1), "wq": n(d, DM.h * DM.hd, sc=d ** -.5),
            "wk": n(d, DM.kv * DM.hd, sc=d ** -.5),
            "wv": n(d, DM.kv * DM.hd, sc=d ** -.5),
            "wo": n(DM.h * DM.hd, d, sc=d ** -.5), "norm2": 1 + n(d, sc=0.1),
            "router": n(d, e, sc=d ** -.5), "w1": n(e, d, f, sc=d ** -.5),
            "w3": n(e, d, f, sc=d ** -.5), "w2": n(e, f, d, sc=f ** -.5)}
           for _ in range(layers)]
    return {"embed": n(DM.v, d, sc=0.5), "head": n(d, DM.v, sc=d ** -.5),
            "final_norm": 1 + n(d, sc=0.1), "layers": lay}


def _dense_loss(params, tokens, labels, groups, coef):
    """The whole batch at once, every expert on every token: the loss the
    reference's two passes differentiate, written plainly."""
    n_moe = len(params["layers"])
    x = params["embed"][tokens]
    aux = 0.0
    onehot_g = jax.nn.one_hot(groups, int(groups.max()) + 1)      # (B, S, G)
    size = onehot_g.sum((0, 1))
    for lp in params["layers"]:
        h = jax.vmap(lambda xs: mt._attention(lp, xs, DM, None))(x)
        xn, probs = mt._probs(lp, h, DM)
        top, idx = jax.lax.top_k(probs, DM.k)
        hit = jax.lax.stop_gradient(jax.nn.one_hot(idx, DM.e).sum(2))
        gates = hit * probs / jnp.sum(top, -1, keepdims=True)
        out = jnp.stack([jax.nn.silu(xn @ lp["w1"][e]) * (xn @ lp["w3"][e])
                         @ lp["w2"][e] for e in range(DM.e)], -2)
        x = h + jnp.sum(gates[..., None] * out, -2)
        f = jnp.einsum("bsg,bse->ge", onehot_g, hit) / size[:, None]
        p = jnp.einsum("bsg,bse->ge", onehot_g, probs) / size[:, None]
        aux = aux + jnp.mean(DM.e * jnp.sum(f * p, -1) / DM.k)
    logits = mt._rms(x, params["final_norm"], DM.eps) @ params["head"]
    gold = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    ce = jnp.mean(jax.nn.logsumexp(logits, -1) - gold)
    return ce + coef * aux / n_moe


@pytest.mark.parametrize("devices,chunks", [(1, 1), (2, 4), (4, 2)])
def test_reference_gradient_is_the_grad_of_its_forward(devices, chunks):
    B, S = 4, 32
    rng = np.random.default_rng(devices * 10 + chunks)
    tokens = rng.integers(0, DM.v, (B, S)).astype(np.int32)
    labels = rng.integers(0, DM.v, (B, S)).astype(np.int32)
    mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:1]), ("x",))
    params = mt.place(_params(devices + chunks), mesh)
    groups = mt.chunk_groups(B, S, devices, chunks)
    with jax.default_matmul_precision("highest"):
        loss, grads = mt.batch_loss_grads(
            params, tokens, labels, DM, devices=devices, chunks=chunks,
            coef=0.5, cap_multiple=8)
        want_loss, want = jax.value_and_grad(_dense_loss)(
            params, jnp.asarray(tokens), jnp.asarray(labels),
            jnp.asarray(groups), 0.5)
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-5)
    got, ref = mt.flat(grads), mt.flat(want)
    for k in ref:
        np.testing.assert_allclose(got[k], ref[k], rtol=2e-4,
                                   atol=2e-5 * float(jnp.abs(ref[k]).max()),
                                   err_msg=k)


def test_one_group_aux_is_the_whole_batch_switch_term():
    rng = np.random.default_rng(0)
    B, S = 3, 16
    logits = rng.standard_normal((B, S, DM.e))
    probs = np.exp(logits) / np.exp(logits).sum(-1, keepdims=True)
    topk = np.argsort(-probs, -1)[..., :DM.k]
    w = mt.aux_weights(topk, np.zeros((B, S), int), DM, coef=1.0, n_moe=1)
    f = np.zeros(DM.e)
    np.add.at(f, topk.ravel(), 1.0)
    f /= B * S
    switch = DM.e * np.sum(f * probs.reshape(-1, DM.e).mean(0)) / DM.k
    assert float(np.sum(w * probs)) == pytest.approx(switch, rel=1e-6)


def test_chunk_groups_follow_the_ep_layout():
    # 2 sequences of 8 over 2 devices: device j holds positions 4j..4j+3 of
    # each sequence, batch-major; 2 chunks of its 8 tokens each
    g = mt.chunk_groups(2, 8, 2, 2)
    assert g.tolist() == [[0, 0, 0, 0, 2, 2, 2, 2], [1, 1, 1, 1, 3, 3, 3, 3]]
    assert mt.chunk_groups(2, 8, 2, 1).tolist() == \
        [[0] * 4 + [1] * 4] * 2
    with pytest.raises(ValueError, match="do not split"):
        mt.chunk_groups(1, 8, 2, 3)


def _mix():
    with open(os.path.join(ROOT, "bench", "traffic", "zipf-train.json")) as f:
        return dict(json.load(f), seq_len=64, global_batch=4, batches=3)


def test_zipf_batches_depend_only_on_schedule_and_seed():
    mix = _mix()
    a = train_data.TrainBatches(mix, 2**33 + 1, 1000)
    b = train_data.TrainBatches(mix, 2**33 + 1, 1000)
    c = train_data.TrainBatches(mix, 7, 1000)
    d = train_data.TrainBatches(dict(mix, schedule_seed=1), 2**33 + 1, 1000)
    for s in range(4):
        assert np.array_equal(a.batch_at(s)["tokens"], b.batch_at(s)["tokens"])
    assert not np.array_equal(a.rows, c.rows)
    assert np.array_equal(a.rank_to_id, c.rank_to_id)
    assert not np.array_equal(a.rank_to_id, d.rank_to_id)
    x = a.batch_at(1)
    assert x["tokens"].shape == (4, 64) and x["tokens"].dtype == np.int32
    assert np.array_equal(x["labels"][:, :-1], x["tokens"][:, 1:])
    assert np.array_equal(a.batch_at(3)["tokens"], a.batch_at(0)["tokens"])
    assert not np.array_equal(a.batch_at(0)["tokens"], a.batch_at(1)["tokens"])


def test_zipf_ids_follow_the_ranks():
    mix = dict(_mix(), seq_len=4096, global_batch=8, batches=2)
    g = train_data.TrainBatches(mix, 11, 32000)
    counts = np.bincount(g.rows.ravel(), minlength=32000)
    p = train_data.rank_probabilities(32000, 1.0)
    n = g.rows.size
    # the commonest ids are the first ranks, at their Zipf shares
    assert counts.argmax() == g.rank_to_id[0]
    for r in range(3):
        assert counts[g.rank_to_id[r]] / n == pytest.approx(p[r], rel=0.05)
    uniform = train_data.rank_probabilities(10, 0.0)
    assert np.allclose(uniform, 0.1)


def _profile():
    dev = "/device:TPU:0"
    return trace.Profile(
        device_ops={dev: [("fusion.1", 0, 30), ("all-to-all.3", 20, 50),
                          ("all-to-all-start.1", 60, 70),
                          ("fusion.2", 80, 100)],
                    "/device:TPU:1": [("all-to-all.3", 0, 10),
                                      ("fusion.1", 10, 100)]},
        spans=[("bench.window", 0, 100), ("bench.step", 0, 100)])


def test_exposed_all_to_all_share_on_a_hand_made_profile():
    prof = _profile()
    before = trace.summarize(prof)
    # chip 0: 30-50 and 60-70 with no other op; chip 1: 0-10
    assert collectives.exposed_share(prof) == pytest.approx(
        ((20 + 10) / 100 + 10 / 100) / 2)
    # the benchmark's own reduction reads as it did
    assert trace.summarize(prof) == before
    # chip 0 idles 50-60 and 70-80, chip 1 never
    assert before.idle_gaps == [["bench.step", pytest.approx(20e-9 / 2)]]
    none = trace.Profile(device_ops={"/device:TPU:0": [("fusion.1", 0, 9)]},
                         spans=[("bench.window", 0, 10)])
    assert collectives.exposed_share(none) is None


def test_exposed_share_reads_only_innermost_ops():
    # a scanned loop (while.1) holds a fusion and an all-to-all; a second
    # loop nests inside it; only the ops that hold no other count
    ops = [("while.1", 0, 100), ("fusion.1", 0, 40),
           ("all-to-all.1", 40, 60), ("while.2", 60, 100),
           ("fusion.2", 60, 70), ("all-to-all.2", 70, 100)]
    assert collectives.innermost(ops) == [ops[1], ops[2], ops[4], ops[5]]
    prof = trace.Profile(device_ops={"/device:TPU:0": ops},
                         spans=[("bench.window", 0, 100)])
    assert collectives.exposed_share(prof) == pytest.approx(0.5)
    assert trace.summarize(prof).idle_share == pytest.approx(0.0)


def test_recorded_trace_without_a_collective_has_no_exposed_share(tmp_path):
    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((128, 128))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            for _ in range(2):
                with jax.profiler.TraceAnnotation("bench.step"):
                    f(x).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    prof = trace.read_profile(str(tmp_path), host_ops=True)
    assert collectives.exposed_share(prof) is None
    s = trace.summarize(prof)
    assert 0 < s.busy_s < s.window_s


def test_train_flops_per_token_at_mixtral_widths():
    with open(os.path.join(ROOT, "bench", "configs",
                           "mixtral-8x7b-l1-ep4-train.json")) as f:
        cfg = json.load(f)
    per_token = 3 * flops.prefill_flops(cfg, 0, 4096, logits=4096) / 4096
    # projections 83.9M, routed experts 704.6M, router, scores over 2048.5
    # keys on average, head 262.1M; forward and backward
    want = 3 * (2 * 4096 * 10240 + 2 * 3 * 2 * 4096 * 14336 + 2 * 4096 * 8
                + 4 * 32 * 128 * 2048.5 + 2 * 4096 * 32000)
    assert per_token == pytest.approx(want, rel=1e-12)
    assert 3.2e9 < per_token < 3.3e9
