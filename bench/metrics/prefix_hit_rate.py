"""Share of the prompt tokens of the requests admitted in the window that
were adopted from the prefix trie instead of prefilled, in %.  The adopted
length is where the scheduler resumed each prompt's prefill (its
``chunks_done`` at the first chunk)."""
from bench.metrics import _serve


def read(run):
    if not _serve.is_serve(run):
        return None
    rids = [r for r in run.in_window if r in run.adopted]
    total = sum(run.prompt_len[r] for r in rids)
    if not total:
        return None
    return 100.0 * sum(run.adopted[r] for r in rids) / total
