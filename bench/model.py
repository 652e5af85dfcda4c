"""A configuration file (``bench/configs/<name>.json``) and the program's
configuration it names. The sizes the benchmark reads from the file are
its block's (``bench.blocks.block_for(conf).dims_of``)."""

from __future__ import annotations

import json
from pathlib import Path
from typing import Dict

CONFIG_DIR = Path(__file__).resolve().parent / "configs"


def load_config(name: str) -> Dict:
    with open(CONFIG_DIR / f"{name}.json") as f:
        return json.load(f)


def program_config(conf: Dict):
    """The program's ``ModelConfig`` named by the file, served on the Pallas
    path with the file's RoPE base and norm epsilon (the program's own
    entries may differ from the published ones; its layers read both)."""
    from repro.configs import get_config

    return get_config(conf["repro_config"]).replace(
        attn_impl="pallas", rope_theta=float(conf["rope_theta"]),
        norm_eps=float(conf["rms_norm_eps"]))
