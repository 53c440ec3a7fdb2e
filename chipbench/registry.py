"""Builders, generators and readers found by name.

Each module under ``chipbench/deployments``, ``chipbench/generators`` and
``chipbench/readers`` exposes one dict (``BUILDERS`` / ``GENERATORS`` /
``READERS``) of name -> callable. A later PR adds a file; no file that is
there needs an edit. Two files offering one name is an error.
"""

from __future__ import annotations

import importlib
import pkgutil
from pathlib import Path
from typing import Callable, Dict

_KINDS = {"deployments": "BUILDERS", "generators": "GENERATORS",
          "readers": "READERS"}


def load(kind: str, root: Path | None = None) -> Dict[str, Callable]:
    """All callables of one kind, by name. ``root`` is the package
    directory (default: this one) — tests point it at a temporary copy."""
    attr = _KINDS[kind]
    if root is None:
        root = Path(__file__).resolve().parent
    pkg = f"{root.name}.{kind}"
    found: Dict[str, Callable] = {}
    for info in pkgutil.iter_modules([str(root / kind)]):
        mod = importlib.import_module(f"{pkg}.{info.name}")
        for name, fn in getattr(mod, attr, {}).items():
            if name in found:
                raise ValueError(f"{kind}: two files offer {name!r}")
            found[name] = fn
    return found


def find(kind: str, name: str, root: Path | None = None) -> Callable:
    table = load(kind, root)
    if name not in table:
        raise KeyError(f"no {kind[:-1]} named {name!r}; have {sorted(table)}")
    return table[name]
