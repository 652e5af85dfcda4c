"""On-mesh context migration across the ``pod`` axis — beyond-paper.

The paper replicates *token* context between edge nodes and leaves "directly
manipulating the internal KV cache" as future work (§5). Here both levels
exist as mesh programs, with the pod axis standing in for edge sites:

- ``migrate_tokens``  — the paper's own mechanism on-mesh: the tokenized
  session context (a (B, L) int32 buffer) moves pod→pod via lax.ppermute.
- ``migrate_kv_cache`` — the beyond-paper mechanism: the model's *internal*
  state (attention KV caches / SSM states) moves pod→pod, so the receiving
  pod skips re-prefilling the context entirely.

``migration_vs_reprefill`` quantifies the trade analytically per
architecture: ship state bytes over ICI vs. re-run prefill FLOPs. For SSM
archs the state is O(1) in context length — migration wins by orders of
magnitude, which is why DESIGN.md calls them the best fit for DisCEdge.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models.config import ModelConfig
from ..launch.mesh import ICI_BW_PER_LINK, PEAK_FLOPS_BF16


# ---------------------------------------------------------------------------
# Mesh programs
# ---------------------------------------------------------------------------

def _pod_perm(n_pods: int, src: int, dst: int) -> List[Tuple[int, int]]:
    """Permutation that moves src pod's shard to dst (others keep theirs —
    identity links are omitted; absent sources deliver zeros, which is fine
    because only dst consumes the migrated value)."""
    return [(src, dst)]


def migrate_tokens(
    mesh: Mesh, token_buffer: jax.Array, src_pod: int, dst_pod: int
):
    """Move a (pods, B, L) pod-sharded tokenized-context buffer's src shard
    to dst. Returns the updated buffer. Lowerable on the production mesh."""

    def body(buf):  # buf: (1, B, L) — this pod's shard
        moved = jax.lax.ppermute(buf, "pod", _pod_perm(
            mesh.shape["pod"], src_pod, dst_pod))
        me = jax.lax.axis_index("pod")
        return jnp.where(me == dst_pod, moved, buf)

    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=P("pod", None, None),
        out_specs=P("pod", None, None),
    )
    return fn(token_buffer)


def migrate_kv_cache(
    mesh: Mesh, caches: Any, src_pod: int, dst_pod: int
):
    """Move every pod-sharded leaf of a cache pytree from src to dst pod.
    Leaves must carry 'pod' as their leading mesh axis; the data/model
    sharding *within* the pod is untouched (the transfer is pure pod-to-pod
    ICI traffic — exactly what the roofline's collective term prices)."""

    def one(leaf):
        nd = leaf.ndim

        def body(x):
            moved = jax.lax.ppermute(
                x, "pod", _pod_perm(mesh.shape["pod"], src_pod, dst_pod)
            )
            me = jax.lax.axis_index("pod")
            return jnp.where(me == dst_pod, moved, x)

        fn = jax.shard_map(
            body, mesh=mesh,
            in_specs=P(*(("pod",) + (None,) * (nd - 1))),
            out_specs=P(*(("pod",) + (None,) * (nd - 1))),
        )
        return fn(leaf)

    return jax.tree.map(one, caches)


# ---------------------------------------------------------------------------
# Analytic comparison: migrate state vs. re-prefill at the new site
# ---------------------------------------------------------------------------

@dataclass
class MigrationAnalysis:
    arch: str
    context_len: int
    state_bytes: int
    migrate_s: float        # state_bytes over ICI
    token_bytes: int
    reprefill_flops: float
    reprefill_s: float      # prefill at the receiving pod (compute roofline)
    winner: str

    def to_row(self) -> str:
        return (
            f"{self.arch:22s} ctx={self.context_len:>7d} "
            f"state={self.state_bytes/1e6:9.1f}MB migrate={self.migrate_s*1e3:8.2f}ms "
            f"reprefill={self.reprefill_s*1e3:9.2f}ms -> {self.winner}"
        )


def internal_state_bytes(cfg: ModelConfig, context_len: int, batch: int = 1) -> int:
    """Size of the model's internal decode state for one session."""
    bpe = 2  # bf16
    total = 0
    if cfg.arch_type in ("ssm", "hybrid"):
        nh = cfg.n_ssm_heads
        hd = cfg.d_inner // nh
        n_ssm = cfg.n_layers
        total += n_ssm * batch * nh * hd * cfg.ssm_state * bpe
        from ..models.ssm import conv_dim

        total += n_ssm * batch * cfg.ssm_conv * conv_dim(cfg) * bpe
        if cfg.arch_type == "hybrid" and cfg.shared_attn_period:
            n_inv = cfg.n_layers // cfg.shared_attn_period
            total += (
                2 * n_inv * batch * context_len * cfg.n_kv_heads * cfg.d_head * bpe
            )
    else:
        per_layer_len = context_len
        if cfg.layer_pattern == "local_global":
            # half the layers cache only the window
            w = min(cfg.sliding_window, context_len)
            n_local = cfg.n_layers // 2
            n_global = cfg.n_layers - n_local
            total += 2 * n_local * batch * w * cfg.n_kv_heads * cfg.d_head * bpe
            total += 2 * n_global * batch * context_len * cfg.n_kv_heads * cfg.d_head * bpe
            return total
        if cfg.attn_variant == "sliding_window":
            per_layer_len = min(cfg.sliding_window or 8192, context_len)
        total += 2 * cfg.n_layers * batch * per_layer_len * cfg.n_kv_heads * cfg.d_head * bpe
    return total


def migration_vs_reprefill(
    cfg: ModelConfig, context_len: int, chips_per_pod: int = 256
) -> MigrationAnalysis:
    state = internal_state_bytes(cfg, context_len)
    # pod-to-pod transfer rides the inter-pod links of all chips holding
    # shards; assume the state is spread over the pod's chips
    links = chips_per_pod
    migrate_s = state / (links * ICI_BW_PER_LINK)
    reprefill_flops = 2.0 * cfg.active_param_count() * context_len
    reprefill_s = reprefill_flops / (chips_per_pod * PEAK_FLOPS_BF16)
    token_bytes = context_len * (2 if cfg.vocab_size <= 65536 else 4)
    return MigrationAnalysis(
        arch=cfg.name,
        context_len=context_len,
        state_bytes=state,
        migrate_s=migrate_s,
        token_bytes=token_bytes,
        reprefill_flops=reprefill_flops,
        reprefill_s=reprefill_s,
        winner="migrate-state" if migrate_s < reprefill_s else "reprefill-tokens",
    )
