"""Device time of the MoE layer of a decode wave: the union of the device
intervals of the traced decode waves' ops under the program scope ``moe``
(router, expert FFN, combine), over the number of waves, in ms."""
from bench.metrics import _serve


def read(run):
    return _serve.decode_scope_ms(run, ("moe",))
