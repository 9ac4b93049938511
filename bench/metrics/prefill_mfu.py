"""Model FLOPs of the prompt tokens prefilled in the window (adopted prefix
tokens excluded, routed experts only, the head once per prompt) over the
summed host spans of the steps that ran a prefill chunk, over the chip's
bf16 peak, in %.  Those spans also hold the step's decode wave, so the share
can only read low."""
from bench import flops, peaks
from bench.metrics import _serve


def read(run):
    if not _serve.is_serve(run):
        return None
    work = span = 0.0
    for t0, t1, chunk, _ in _serve.window_steps(run):
        if chunk is None:
            continue
        start, stop, length = chunk
        work += flops.prefill_flops(run.config, start, stop,
                                    logits=int(stop == length))
        span += t1 - t0
    if span <= 0:
        return None
    peak = peaks.peaks_for(run.device_kind).flops_bf16 * run.chips
    return 100.0 * work / span / peak
