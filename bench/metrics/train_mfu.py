"""Model FLOPs of the window's steps over the summed host spans of those
steps, over the chips' bf16 peak, in %.  Model FLOPs per sequence are the
forward of every position (routed top-2 experts only, causal scores, the
head at every position; ``bench/flops.py``) times 3 for the backward; no
recompute, no capacity padding.  The spans leave out the host's time
between steps (and a traced run's profiler start).  Left out where the
configuration's adapter counts no FLOPs."""
from bench import flops, peaks
from bench.metrics import _train


def read(run):
    if not _train.is_train(run) or not run.steps:
        return None
    seq = flops.prefill_flops(run.config, 0, run.seq_len, logits=run.seq_len)
    if seq is None:                  # the adapter counts no FLOPs
        return None
    span = sum(t1 - t0 for t0, t1, _, _ in run.steps)
    per_step = 3 * run.global_batch * seq
    peak = peaks.peaks_for(run.device_kind).flops_bf16 * run.chips
    return 100.0 * per_step * len(run.steps) / span / peak
