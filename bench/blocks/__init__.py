"""One module per block: everything the benchmark knows of an architecture.

A configuration file's published ``model_type`` names the module that
serves it: ``bench/blocks/<model_type>.py``. The shared files (the
harness, ``bench/reference.py``, ``bench/weights.py``, ``bench/flops.py``
and the metric readers) reach the architecture only through these names,
which every block module defines:

``Dims``
    A frozen (so hashable) dataclass of the sizes the block reads; it is a
    static argument of the reference's jitted comparison.
``dims_of(conf: dict) -> Dims``
    The sizes, from the configuration file's published keys.
``check_program_config(d: Dims, cfg) -> None``
    Raise unless the program's ``ModelConfig`` serves exactly this block at
    these sizes.
``make(key, d: Dims, wspec: dict) -> params``
    Random weights from a PRNG key, in the program's layout and the dtype
    ``wspec["dtype"]`` states (traced once, inside one jitted call).
``hidden(params, d: Dims, tokens, quant: str) -> (S, D)``
    The plain float32 reference: final hidden states of one causal sequence
    of token ids; ``quant="fp8"`` rounds every matrix product's operands to
    float8 e4m3 (the control), ``"f32"`` leaves them.
``logits(params, d: Dims, rows, quant: str) -> (R, V)``
    The final norm and the vocabulary projection of hidden-state rows.
``token_flops(d: Dims, kv_len: int, logits: bool) -> float``
    Model FLOPs of one token at position ``kv_len - 1`` through every layer,
    plus the vocabulary projection when its logits are computed.
``chunk_flops(d: Dims, c: int, p0: int) -> float``
    Model FLOPs of one prefill chunk of ``c`` tokens at offset ``p0``, with
    the logits of its last row.
``paged_attention_call(d, kv_lens, shared_pages, page_size) -> [(n_layers, flops, bytes)]``
    One decode step's paged attention: for each kind of layer, how many
    layers of it there are and the FLOPs and bytes one of them needs for
    the decoding lanes (``kv_lens``: keys each attends, its new token
    included; a shared run of ``shared_pages`` pages is read once).
``chunked_prefill_call(d, c, p0, page_size) -> [(n_layers, flops, bytes)]``
    The same for one prefill chunk of ``c`` rows at offset ``p0``.

A block with one kind of layer returns one entry from the two ``*_call``
functions; one with window and full layers returns two. Every count is a
lower bound on the work (see ``bench/flops.py``).
"""

from __future__ import annotations

import importlib
from pathlib import Path
from types import ModuleType
from typing import Dict

BLOCK_DIR = Path(__file__).resolve().parent


def block_for(conf: Dict) -> ModuleType:
    """The block module named by ``conf["model_type"]``; a type with no
    module is an error that names the file it would be."""
    name = conf["model_type"]
    module = f"{__name__}.{name}"
    try:
        return importlib.import_module(module)
    except ModuleNotFoundError as e:
        if e.name != module:
            raise
        raise ModuleNotFoundError(
            f"no block for model_type {name!r}: {BLOCK_DIR / f'{name}.py'} does not exist",
            name=module) from None
