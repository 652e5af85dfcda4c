"""A configuration file (``bench/configs/<name>.json``) and the sizes the
benchmark reads from it."""

from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path
from typing import Dict

CONFIG_DIR = Path(__file__).resolve().parent / "configs"


@dataclass(frozen=True)
class Dims:
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    d_head: int
    d_ff: int
    vocab: int
    rope_theta: float
    norm_eps: float


def load_config(name: str) -> Dict:
    with open(CONFIG_DIR / f"{name}.json") as f:
        return json.load(f)


def dims_of(conf: Dict) -> Dims:
    return Dims(
        layers=conf["num_hidden_layers"],
        d_model=conf["hidden_size"],
        heads=conf["num_attention_heads"],
        kv_heads=conf["num_key_value_heads"],
        d_head=conf["head_dim"],
        d_ff=conf["intermediate_size"],
        vocab=conf["vocab_size"],
        rope_theta=float(conf["rope_theta"]),
        norm_eps=float(conf["rms_norm_eps"]),
    )


def program_config(conf: Dict):
    """The program's ``ModelConfig`` named by the file, served on the Pallas
    path with the file's RoPE base and norm epsilon (the program's own
    entries may differ from the published ones; its layers read both)."""
    from repro.configs import get_config

    return get_config(conf["repro_config"]).replace(
        attn_impl="pallas", rope_theta=float(conf["rope_theta"]),
        norm_eps=float(conf["rms_norm_eps"]))


def check_program_config(d: Dims, cfg) -> None:
    """Raise unless the program's ``ModelConfig`` runs the sizes the file
    states (the benchmark's reference follows the file)."""
    pairs = {
        "num_hidden_layers": (d.layers, cfg.n_layers),
        "hidden_size": (d.d_model, cfg.d_model),
        "num_attention_heads": (d.heads, cfg.n_heads),
        "num_key_value_heads": (d.kv_heads, cfg.n_kv_heads),
        "head_dim": (d.d_head, cfg.d_head),
        "intermediate_size": (d.d_ff, cfg.d_ff),
        "vocab_size": (d.vocab, cfg.vocab_size),
        "rope_theta": (d.rope_theta, float(cfg.rope_theta)),
        "rms_norm_eps": (d.norm_eps, float(cfg.norm_eps)),
    }
    bad = {k: v for k, v in pairs.items() if v[0] != v[1]}
    if bad:
        raise ValueError(f"program config differs from the benchmark's file: {bad}")
    if not (cfg.qkv_bias and cfg.tie_embeddings and cfg.mlp_type == "swiglu"
            and cfg.rope_style == "standard" and cfg.attn_softcap == 0.0
            and cfg.logit_softcap == 0.0 and cfg.arch_type == "dense"):
        raise ValueError("program config is not the Qwen2 block the reference follows")
