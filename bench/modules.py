"""Modules of the benchmark loaded by path, and those a configuration file
names.

A configuration file (`configs/<name>.json`) names, beside its sizes, the
modules that know its kind of block:

  "reference"  its plain reference: `logits_at(cfg, params, seqs,
               positions, *, batch, width, precision)`, in float32 and, with
               `precision="fp8"`, the control (default `reference`:
               bench/reference.py, a Llama-style dense decoder);
  "work"       its operations and bytes per serving step:
               `step_work(cfg, decode_keys, segments)` (default `work`:
               bench/work.py, the same dense decoder).

A name is a path under bench/ without `.py`, such as `reference` or
`tests/data/reference_no_qk_norm`.  A model with a new kind of block thus
comes as new files: its configuration, its reference and its work counts.
Each module is loaded once per process.
"""

from __future__ import annotations

import importlib.util
import sys
from pathlib import Path
from types import ModuleType
from typing import Dict

BENCH = Path(__file__).resolve().parent
DEFAULTS = {"reference": "reference", "work": "work"}


def load(name: str) -> ModuleType:
    """The module bench/<name>.py."""
    key = f"bench_{name}"
    mod = sys.modules.get(key)
    if mod is None:
        spec = importlib.util.spec_from_file_location(key,
                                                      BENCH / f"{name}.py")
        mod = importlib.util.module_from_spec(spec)
        sys.modules[key] = mod
        try:
            spec.loader.exec_module(mod)
        except BaseException:
            del sys.modules[key]
            raise
    return mod


def of_config(cfg: Dict, role: str) -> ModuleType:
    """The module that configuration `cfg` names for `role` ("reference"
    or "work"), or the default."""
    return load(cfg.get(role, DEFAULTS[role]))
