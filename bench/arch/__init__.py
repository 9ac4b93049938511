"""Architecture adapters, found by name.

Every configuration file carries the Hugging Face ``model_type`` of its
model.  Everything the harness needs to know of that architecture lives in
one file, ``bench/arch/<model_type>.py``:

* ``program_config(c)``: the program's ``ModelConfig`` for configuration
  file ``c`` (``train_program_config(c)`` for a training configuration);
* ``dims(c)``: the sizes the plain reference takes;
* ``serve_reference`` (and ``train_reference``): the module of the plain
  reference, which imports nothing of the program (``served_logits``,
  ``served_gaps``; ``train_steps``, ...);
* ``reference_weights(params)`` (``reference_tree``, ``reference_leaves``,
  ``reference_args``, ``reference_specs`` for training): the reference's
  view of the benchmark-made weights;
* optionally the counts of operations and bytes the published model needs
  (``prefill_flops``, ``weight_bytes``, ...; ``bench/flops.py``), and the
  program's ``jax.named_scope`` names beyond ``bench.trace.SCOPES``
  (``SCOPES``).  A metric whose count the adapter does not give is left out.

A configuration of a new architecture is then new files only: its
configuration, its adapter and its reference.
"""

from __future__ import annotations

import importlib.util
import os
import sys

#: where the adapters are looked up (a test points it at a directory of its
#: own)
ARCH_DIR = os.path.dirname(os.path.abspath(__file__))


def adapter(config: dict):
    """The adapter module of configuration file ``config``, by its
    ``model_type``.  Raises ``FileNotFoundError`` naming the missing file
    where there is none."""
    model_type = config.get("model_type")
    if not model_type:
        raise KeyError("the configuration names no model_type")
    path = os.path.abspath(os.path.join(ARCH_DIR, model_type + ".py"))
    if not os.path.isfile(path):
        raise FileNotFoundError(
            f"no architecture adapter for model_type {model_type!r}: "
            f"{path} is missing")
    name = "bench.arch." + model_type
    mod = sys.modules.get(name)
    if mod is None or os.path.abspath(mod.__file__) != path:
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod
        spec.loader.exec_module(mod)
    return mod
