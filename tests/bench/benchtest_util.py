"""Shared set-up of the benchmark's CPU tests: import paths and the small
sizes the harness runs at here."""
from __future__ import annotations

import copy
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

#: a dense decoder small enough for the CPU, same layer kinds as the cell
TINY_MODEL = dict(num_layers=2, d_model=128, num_heads=4, num_kv_heads=2,
                  head_dim=32, d_ff=256, vocab_size=512)


def cell(name: str, *, devices: int | None = None, model: dict | None = None):
    """(spec, config, mix) of a cell with the model and fleet cut down."""
    from bench import run
    spec = run.load_spec()
    _, config, mix = run.cell_parts(spec, name)
    config, mix = copy.deepcopy(config), copy.deepcopy(mix)
    if "model" in config:
        config["model"].update(model or TINY_MODEL)
    if devices is not None:
        mix["devices"] = devices
    return spec, config, mix
