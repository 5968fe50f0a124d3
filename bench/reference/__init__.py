"""Plain references the benchmark checks the system against.  They import
nothing of the program and take nothing it made.

A served model's reference is found by name: the module
``bench/reference/<model.reference>.py`` under the run's root
(``transformer`` where the configuration's ``model`` has no
``reference``).  It provides, as plain functions of the configuration's
``model`` dict:

* ``check_equations(model)``, raising on equations it does not implement,
  and ``CONTROL_BELOW``, the matmul arithmetic one step below each stated
  ``matmul_precision``;
* ``shapes(model)``: the weight tree the executor reads (leaf shapes as
  tuples, one stacked tree per segment element);
* ``hidden(weights, tokens, model, mode)`` and
  ``logits(weights, h, model, mode, cols)``;
* counts of layer ``i`` over ``batch`` sequences of ``seq`` tokens,
  ``layer_flops(model, i, batch, seq)`` and
  ``layer_bytes(model, i, batch, seq)``, the head's ``head_flops`` and
  ``head_bytes(model, batch, seq)``, and ``block_flops(model, i, seq)``,
  layer ``i``'s per-sample FLOPs as the deployment's task profile states
  them.
"""
from __future__ import annotations

import importlib
import importlib.util
import re
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
_HERE = Path(__file__).resolve().parent
_NAME = re.compile(r"^[A-Za-z_][A-Za-z0-9_]*$")
_LOADED: dict = {}


def model_module(model: dict, root: Path | None = None):
    """The reference module the configuration's ``model`` names, loaded once
    per file."""
    name = model.get("reference", "transformer")
    if not _NAME.match(name):
        raise ValueError(f"reference {name!r} is not a module name")
    path = (Path(root or ROOT) / "bench" / "reference" / f"{name}.py"
            ).resolve()
    if path not in _LOADED:
        if path.parent == _HERE:
            mod = importlib.import_module(f"bench.reference.{name}")
        else:
            spec = importlib.util.spec_from_file_location(
                f"bench_reference_{name}_{len(_LOADED)}", path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
        _LOADED[path] = mod
    return _LOADED[path]
