"""Share of the experts that the traced decode waves read, in %: the
scheduler's own counters (``decode_experts_read`` over
``decode_experts_total``, its ``metrics()``), their growth from the trace's
start to the window's close, so that they count the waves whose device time
the trace gives.  A wave reads an expert's weights only where a live slot
routes to it; all of them would read 100%."""
from bench.metrics import _serve


def read(run):
    if not _serve.is_serve(run) or set(run.counters) != {"open", "close"}:
        return None
    a, b = run.counters["open"], run.counters["close"]
    if "decode_experts_total" not in b:
        return None
    total = b["decode_experts_total"] - a["decode_experts_total"]
    if total <= 0:
        return None
    return 100.0 * (b["decode_experts_read"] - a["decode_experts_read"]) \
        / total
