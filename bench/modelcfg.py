"""A configuration file (``bench/configs/<name>.json``) and the engine's
ArchConfig, both as the block it names (``bench/blocks/<block>.py``) says."""
from __future__ import annotations

import json
from pathlib import Path

from bench import blocks


def load(path: Path) -> dict:
    model = json.loads(Path(path).read_text())
    try:
        blocks.of(model).validate(model)
    except ValueError as e:
        raise ValueError(f"{path}: {e}") from None
    return model


def arch_config(model: dict):
    return blocks.of(model).arch_config(model)
