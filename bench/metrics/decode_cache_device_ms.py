"""Device time of the paged cache in a decode wave: the device intervals of
the traced decode waves' ops under the program scopes ``paged_gather`` and
``paged_scatter`` (the slots' pages read into the wave's layout and its new
entries written back), over the number of waves, in ms."""
from bench.metrics import _serve


def read(run):
    return _serve.decode_scope_ms(run, ("paged_gather", "paged_scatter"))
