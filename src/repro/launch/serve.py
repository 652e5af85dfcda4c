"""Serving launcher: bring up a DisCEdge cluster and run a scripted session
against it.

    PYTHONPATH=src python -m repro.launch.serve --nodes 3 --turns 6
    PYTHONPATH=src python -m repro.launch.serve --mode raw --roam
    PYTHONPATH=src python -m repro.launch.serve --arch paper-qwen1.5-0.5b

:func:`build_cluster` is the one way to build a served cluster; the CLI
and ``chip_smoke.py`` both call it. With ``arch``, the model is served at
its published widths and every node gets its own paged, continuously
batched service on the Pallas attention path, placed on the device it is
given (by default ``jax.devices()[0]``). Without one, every node runs the
analytic echo service, which does no device work.
"""

from __future__ import annotations

import argparse
import os
from pathlib import Path
from typing import TYPE_CHECKING, Optional, Sequence, Tuple

if TYPE_CHECKING:
    from ..edge import EdgeCluster

REPO_ROOT = Path(__file__).resolve().parents[3]


def configure_compile_cache() -> str:
    """Give JAX's persistent compilation cache a fixed home and return it:
    the directory ``JAX_COMPILATION_CACHE_DIR`` names, which JAX reads by
    itself, or else ``<repo>/.jax_cache``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    path = str(REPO_ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def build_cluster(
    arch=None,
    n_nodes: int = 2,
    *,
    seed: int = 0,
    devices: Optional[Sequence] = None,
    max_len: int = 2048,
    replication: str = "full",
    kv_ship: bool = False,
    kv_ship_force: Optional[str] = None,
    router: Optional[str] = None,
) -> Tuple["EdgeCluster", str]:
    """Build ``n_nodes`` edge nodes ``edge-0..`` serving one model and
    return ``(cluster, model_name)``.

    ``arch`` is a configuration name (``repro.configs``) or a
    :class:`~repro.models.ModelConfig`. With it, weights are made from
    ``seed``, one copy per device
    (services that share a device share it); ``devices[i]`` hosts node i's
    service, its page pool sized by the service defaults at ``max_len``.
    ``kv_ship`` mounts page shipping, with each node's ship-vs-recompute
    constant measured from its own prefill. The remaining options pass
    through to :meth:`~repro.edge.EdgeCluster.build`."""
    from ..edge import EchoLLMService, EdgeCluster
    from ..store import Link

    node_ids = [f"edge-{i}" for i in range(n_nodes)]
    if arch is None:
        model = "echo-qwen"
        services = {nid: EchoLLMService(model=model) for nid in node_ids}
    else:
        import jax

        from ..configs import get_config
        from ..models import init_params
        from ..serving import BatchedLLMService

        configure_compile_cache()
        cfg = get_config(arch) if isinstance(arch, str) else arch
        cfg = cfg.replace(attn_impl="pallas")
        model = cfg.name
        devs = list(devices) if devices is not None else [jax.devices()[0]] * n_nodes
        assert len(devs) == n_nodes, (len(devs), n_nodes)
        params = {}
        services = {}
        for nid, dev in zip(node_ids, devs):
            if dev not in params:
                with jax.default_device(dev):
                    params[dev] = init_params(jax.random.key(seed), cfg)
            svc = BatchedLLMService.create(
                model, cfg, seed=seed, max_len=max_len, paged=True,
                device=dev, params=params[dev],
            )
            if kv_ship:
                svc.measure_prefill_ms_per_token()
            services[nid] = svc
    cluster = EdgeCluster.build(
        node_ids, services.__getitem__,
        inter_node_link=Link(latency_ms=3.0, bandwidth_mbps=100.0),
        client_link=Link(latency_ms=8.0, bandwidth_mbps=20.0),
        replication=replication,
        kv_ship=kv_ship,
        kv_ship_force=kv_ship_force,
        router=router,
    )
    return cluster, model


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--nodes", type=int, default=2)
    ap.add_argument("--turns", type=int, default=6)
    ap.add_argument("--mode", default="tokenized",
                    choices=["tokenized", "raw", "client_side"])
    ap.add_argument("--roam", action="store_true",
                    help="switch nodes every other turn")
    ap.add_argument("--arch", default=None,
                    help="serve this model at its published widths instead "
                         "of the echo service")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights")
    ap.add_argument("--replication", default="full", choices=["full", "delta"])
    args = ap.parse_args()

    from ..core import ContextMode
    from ..data.synthetic import synthetic_session
    from ..edge import LLMClient

    import numpy as np

    cluster, model = build_cluster(
        args.arch, args.nodes, seed=args.seed, replication=args.replication,
    )
    node_ids = list(cluster.nodes)
    client = LLMClient(cluster, model=model, mode=ContextMode(args.mode),
                       max_new_tokens=16)

    rng = np.random.default_rng(0)
    turns = synthetic_session(rng, n_turns=args.turns)
    prompts = [c for r, c in turns if r == "user"][: args.turns]
    print(f"{'node':8s} {'turn':4s} {'ctx':5s} {'rt_ms':8s}")
    for i, p in enumerate(prompts):
        node = node_ids[(i // 2) % len(node_ids)] if args.roam else node_ids[0]
        r = client.chat(p, node)
        assert r.error is None, r.error
        print(f"{node:8s} {r.turn:<4d} {r.n_context_tokens:<5d} "
              f"{r.timing.response_time_ms:<8.1f}")
        client.think(400)
    cluster.converge()
    print(f"\nsync={cluster.sync_bytes()}B uplink={sum(client.request_bytes_log)}B")


if __name__ == "__main__":
    main()
