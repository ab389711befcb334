"""Architecture families: what the harness needs to know of a model's layer
tree. A configuration names its family by its ``reference`` key, which
also names its plain reference (``reference/<name>.py``); a new family is
one module here beside its reference, and no harness file changes.

A family module gives:

- ``KEYS``: configuration-file keys -> the program's ``ModelConfig`` fields;
- ``head(root, m)``, ``prefix(root, m)`` and ``stack(root, layers, m)``:
  the seeded dense weights (``reference/seeded.py``, or the family's own
  seeded definition that its reference shares) in the program's param-tree
  layout, for the embedding and LM head, for the leading unstacked layers
  (a list), and for the stacked layers ``layers`` (absolute indices; a list
  with one tree per layer of the stack's repeating pattern, each stacked
  over ``layers``). ``lib/build.py`` packs them with the program's packer;
- ``WORK``: the required work ``(flops, bytes)`` of each kernel and of the
  whole decode step (``"decode_step"``), as functions of ``(m, args)``,
  where ``args`` are one ``engine.decode.dispatch`` span's args. A kernel
  the family does not run is absent, and its roofline reads nothing.
"""

from __future__ import annotations

import importlib
from types import ModuleType
from typing import Any, Dict


def of(m: Dict[str, Any]) -> ModuleType:
    """The family module that configuration ``m`` names."""
    return importlib.import_module(f"{__name__}.{m['reference']}")
