"""Random weights from the seed, made by the benchmark (not by the program)
in the layout the program serves: one jitted call of the block's ``make``
(``bench/blocks/``) on the device, in the dtype the configuration states.
"""

from __future__ import annotations

from types import ModuleType
from typing import Dict

import jax


def key_from_seed(seed: int) -> jax.Array:
    """A PRNG key from any non-negative seed (more than 32 bits kept)."""
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def make_params(block: ModuleType, dims, wspec: Dict, seed: int, device) -> Dict:
    """All weights of one model from ``seed``, on ``device``, in one call."""
    fn = jax.jit(lambda k: block.make(k, dims, wspec))
    with jax.default_device(device):
        params = fn(key_from_seed(seed))
    return jax.block_until_ready(params)


def check_layout(params, expected) -> None:
    """Raise unless ``params`` has the tree, shapes and dtypes of
    ``expected`` (the program's abstract parameters)."""
    got = jax.tree_util.tree_structure(params)
    want = jax.tree_util.tree_structure(expected)
    if got != want:
        raise ValueError(f"weight tree differs from the program's:\n{got}\n{want}")
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(expected)):
        if a.shape != b.shape or a.dtype != b.dtype:
            raise ValueError(f"weight leaf {a.shape}/{a.dtype} != {b.shape}/{b.dtype}")
