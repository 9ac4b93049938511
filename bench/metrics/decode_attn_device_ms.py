"""Device time of attention in a decode wave: the union of the device
intervals of the traced decode waves' ops under the program scope
``attention``, over the number of waves, in ms."""
from bench.metrics import _serve


def read(run):
    return _serve.decode_scope_ms(run, ("attention",))
