"""Find a configuration's architecture by name.

A configuration file may name its architecture in a top-level ``"arch"``
(``"dense"`` where it names none). ``archs/<arch>.py`` holds every piece of
code that knows that architecture's layers, and provides:

- ``linears(mc)``: ``(name, k, o, count)`` of each quantized linear in the
  serving layout, where ``count`` is the number of layers that hold it; the
  output head is named ``HEAD`` and has the count 1;
- ``attention_layers(mc)``: the number of layers with attention over a KV
  cache;
- ``weights_fn(config)``: ``key -> weights``, the benchmark's own plain dict,
  for one jitted call;
- ``program_params(weights, config) -> (cfg, params)``: the same buffers in
  the program's serving tree, checked against the layout the program builds
  itself;
- ``forward(mc, g, control, block, w, tokens, rows) -> logits``: the float32
  reference, blockwise.

The rest of the harness (weights, reference, work, metric readers) calls
through these and knows no layer.
"""

from __future__ import annotations

import importlib.util
import os
from types import ModuleType
from typing import Dict, List

DEFAULT = "dense"
HEAD = "lm_head"
PROVIDES = ("linears", "attention_layers", "weights_fn", "program_params", "forward")
# Where architecture modules are looked for, in order.
DIRS: List[str] = [os.path.join(os.path.dirname(os.path.abspath(__file__)), "archs")]
_LOADED: Dict[str, ModuleType] = {}


def found() -> List[str]:
    """The names of the architecture modules in ``DIRS``."""
    return sorted({f[:-3] for d in DIRS if os.path.isdir(d) for f in os.listdir(d)
                   if f.endswith(".py") and not f.startswith("_")})


def load(name: str) -> ModuleType:
    """``archs/<name>.py``, loaded by file path once per process."""
    path = next((p for p in (os.path.join(d, f"{name}.py") for d in DIRS)
                 if os.path.isfile(p)), None)
    if path is None:
        raise ValueError(f"unknown arch {name!r}; modules found: {found()}")
    if path not in _LOADED:
        spec = importlib.util.spec_from_file_location(f"tpubench_arch_{name}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        missing = [f for f in PROVIDES if not callable(getattr(mod, f, None))]
        if missing:
            raise ValueError(f"arch module {path} lacks {missing}")
        _LOADED[path] = mod
    return _LOADED[path]


def of(config: dict) -> ModuleType:
    """The architecture module of a configuration file's contents."""
    return load(config.get("arch", DEFAULT))
