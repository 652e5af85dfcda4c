"""The paged MHA and GQA paths keep their numbers: chunked paged prefill
and paged decode (with a shared-prefix run) of reduced Qwen1.5 (MHA) and
Qwen2 (GQA) models, on the Pallas path (interpret mode) and the reference
path, against logits recorded before the paged kernels learned latent
(MLA) pages. Equal to the bit.

Regenerate the data file only on purpose:
``PYTHONPATH=src python tests/test_paged_identity.py --record``."""

import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import decode_step_paged, init_params, layer_groups
from repro.models.cache import init_paged_pool
from repro.models.prefill import prefill_chunk_paged

DATA = Path(__file__).resolve().parent / "data" / "qwen-paged-identity.npz"
ARCHS = ("paper-qwen1.5-0.5b", "qwen2-0.5b")
PS, N_PAGES, MP = 8, 16, 4


def paged_logits(arch: str, impl: str) -> np.ndarray:
    """Two lanes whose prompts share their first page: lane 0 prefilled in
    one chunk, lane 1 in a chunk after the shared page (``n_skip`` 1), then
    three greedy decode steps of both lanes with the shared page passed as
    the shared-prefix run. Every logit row, stacked."""
    cfg = get_config(arch).reduced(attn_impl=impl)
    params = init_params(jax.random.key(0), cfg)
    pools = [init_paged_pool(cfg, s.n_blocks, N_PAGES, PS) for s in layer_groups(cfg)]
    rng = np.random.default_rng(0)
    common = rng.integers(1, cfg.vocab_size, PS)
    prompts = [np.concatenate([common, rng.integers(1, cfg.vocab_size, n)])
               for n in (12, 5)]
    tables = np.array([[1, 2, 3, 4], [1, 5, 6, 7]], np.int32)
    out = []
    nxt = []
    for lane, (ids, p0, skip) in enumerate(zip(prompts, (0, PS), (0, 1))):
        chunk = ids[p0:]
        toks = np.zeros((1, 32), np.int32)
        toks[0, : len(chunk)] = chunk
        logits, pools = prefill_chunk_paged(
            params, cfg, pools, jnp.asarray(tables[lane : lane + 1]),
            jnp.asarray(toks), jnp.array([p0], jnp.int32),
            jnp.array([len(chunk)], jnp.int32), n_skip=skip,
        )
        out.append(np.asarray(logits[0]))
        nxt.append(int(np.argmax(logits[0])))
    pos = np.array([len(p) for p in prompts], np.int32)
    slot = np.arange(MP * PS, dtype=np.int32)[None, :]
    kv_pos = jnp.asarray(np.where(slot < pos[:, None], slot, -1).astype(np.int32))
    tokens = np.array(nxt, np.int32)
    for _ in range(3):
        logits, pools, kv_pos = decode_step_paged(
            params, cfg, pools, jnp.asarray(tables), kv_pos,
            jnp.asarray(tokens[:, None]), jnp.asarray(pos),
            shared_pages=jnp.array([1], jnp.int32),
        )
        rows = np.asarray(logits[:, 0])
        out.extend(rows)
        tokens = rows.argmax(-1).astype(np.int32)
        pos = pos + 1
    return np.stack(out).astype(np.float32)


@pytest.mark.parametrize("impl", ["pallas", "reference"])
@pytest.mark.parametrize("arch", ARCHS)
def test_paged_logits_equal_recorded(arch, impl):
    want = np.load(DATA)[f"{arch}/{impl}"]
    got = paged_logits(arch, impl)
    np.testing.assert_array_equal(got, want)


if __name__ == "__main__" and "--record" in sys.argv:
    np.savez_compressed(DATA, **{f"{a}/{i}": paged_logits(a, i)
                                 for a in ARCHS for i in ("pallas", "reference")})
    print("wrote", DATA)
