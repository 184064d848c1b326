"""A configuration's architecture: ``bench/arch/<model_type>.py``, picked by
the ``model_type`` in its configuration file.

Adding an architecture to the benchmark is adding a module here, loaded
by path (``load_path``, which also loads a metric's reader). Each module
gives:

* ``program_config(cfg)``: the program's ``ModelConfig``;
* ``shapes(cfg)``: canonical weight name -> shape. ``weights.canonical``
  draws them by its shared rule (names ending in ``norm`` are RMSNorm
  scales, ``embed`` is the embedding, every other weight a linear or
  table read along its second-to-last axis);
* ``leaf_name(keys)``: the canonical name of the program's parameter
  at tree path ``keys`` (a tuple of dict keys), or None;
  ``weights.to_program_tree`` lays the weights out by it and raises on
  a leaf with no name and on a shape or dtype that differs;
* ``logits(cfg, W, tokens, bits)``: the plain reference, ``(S, vocab)``
  float32 logits at ``Precision.HIGHEST``, every linear through
  ``bench.reference``'s quantizers looked up at call time; it imports
  nothing of the program;
* ``linears(cfg)``, ``decode_ops(cfg, position)``,
  ``prompt_ops(cfg, length)``: the work counts of ``bench.work``.
"""

from __future__ import annotations

import functools
import importlib.util
from pathlib import Path

#: where the modules are found (a test may point it elsewhere)
ARCH_DIR = Path(__file__).resolve().parent


class UnknownArchitecture(LookupError):
    """The configuration names no ``model_type`` that has a module here."""


def names() -> list[str]:
    return sorted(p.stem for p in ARCH_DIR.glob("*.py") if p.stem != "__init__")


def load_path(path: Path):
    """The module in the file at ``path``, executed afresh."""
    sp = importlib.util.spec_from_file_location(f"bench_{path.parent.name}_{path.stem}", path)
    mod = importlib.util.module_from_spec(sp)
    sp.loader.exec_module(mod)
    return mod


_load = functools.lru_cache(maxsize=None)(load_path)


def for_config(cfg: dict):
    """The module of ``cfg["model_type"]``."""
    name = cfg.get("model_type")
    path = ARCH_DIR / f"{name}.py"
    if not isinstance(name, str) or name == "__init__" or not path.is_file():
        raise UnknownArchitecture(
            f"configuration {cfg.get('name')!r} has model_type {name!r}; {ARCH_DIR} has "
            f"modules for {names()}")
    return _load(path)
