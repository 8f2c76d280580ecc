"""One module per block: ``bench/blocks/<block>.py``, named by the
configuration file's ``"block"`` key.

A block module gives, and is the only place that spells:

* ``validate(model)``: raise ``ValueError`` for a configuration it cannot serve;
* ``arch_config(model)``: the engine's ``ArchConfig`` (the only function that
  imports the program);
* ``make_weights(model, seed)``: the tree the engine takes, from the seed, in
  one jitted call, importing nothing of the program;
* ``layer_weights(blocks, li, bits)`` and ``layer(x, p, model, kv_bits)``:
  layer ``li`` of the plain float32 reference at weight precision ``bits``,
  and that layer over a whole sequence ``x [T, d]``.
"""
from __future__ import annotations

import importlib
import re
from types import ModuleType

_NAME = re.compile(r"[a-z_][a-z0-9_]*")


def of(model: dict) -> ModuleType:
    """The block module a configuration names."""
    name = model.get("block")
    if not isinstance(name, str) or not _NAME.fullmatch(name):
        raise ValueError(f"configuration names no block module: block={name!r}")
    module = f"{__name__}.{name}"
    try:
        return importlib.import_module(module)
    except ModuleNotFoundError as e:
        if e.name != module:
            raise
        raise ValueError(
            f"block {name!r} has no module: add bench/blocks/{name}.py") from None
