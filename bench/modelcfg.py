"""A configuration file (``bench/configs/<name>.json``) as the engine's ArchConfig."""
from __future__ import annotations

import json
from pathlib import Path

_REQUIRED = (
    "hidden_size", "intermediate_size", "num_attention_heads",
    "num_key_value_heads", "head_dim", "num_hidden_layers", "vocab_size",
    "rope_theta", "rms_norm_eps",
)


def load(path: Path) -> dict:
    model = json.loads(Path(path).read_text())
    missing = [k for k in _REQUIRED if k not in model]
    if missing:
        raise ValueError(f"{path}: configuration lacks {missing}")
    if model.get("hidden_act", "silu") != "silu":
        raise ValueError(f"{path}: only the SwiGLU (silu) block is served")
    if model["vocab_size"] % 256:
        raise ValueError(f"{path}: vocab_size must be a multiple of 256")
    return model


def arch_config(model: dict):
    """The engine's ArchConfig for a dense SwiGLU/RMSNorm/RoPE GQA block."""
    from repro.configs.base import ArchConfig

    return ArchConfig(
        name=model["name"],
        family="dense",
        n_layers=model["num_hidden_layers"],
        d_model=model["hidden_size"],
        n_heads=model["num_attention_heads"],
        n_kv_heads=model["num_key_value_heads"],
        d_ff=model["intermediate_size"],
        vocab=model["vocab_size"],
        head_dim=model["head_dim"],
        rope_theta=float(model["rope_theta"]),
        norm_eps=float(model["rms_norm_eps"]),
        dtype="bfloat16",
    )
