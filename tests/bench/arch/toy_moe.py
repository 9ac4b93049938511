"""A test-only second architecture (``model_type`` "toy_moe"): the Mixtral
equations under other key names (``n_routed_experts``,
``moe_intermediate_size``, as DeepSeek's configuration names them), and no
counts of operations or bytes.  ``tests/bench/test_bench_arch.py`` points
the adapter lookup here to show that a configuration of another
architecture runs through the harness with new files only, and that a
metric built on counts the adapter does not give is left out."""

from bench.arch import mixtral as _mixtral
from bench.models import mixtral as serve_reference


def _as_mixtral(c: dict) -> dict:
    return dict(c, num_local_experts=c["n_routed_experts"],
                intermediate_size=c["moe_intermediate_size"])


def dims(c: dict) -> serve_reference.Dims:
    return serve_reference.Dims.from_config(_as_mixtral(c))


def program_config(c: dict):
    return _mixtral.program_config(_as_mixtral(c))


reference_weights = _mixtral.reference_weights
