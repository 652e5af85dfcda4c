#!/usr/bin/env python3
"""Chip smoke test: serve the paper's Qwen1.5-0.5B at its published widths
on a TPU through the normal entry points, and check what comes out.

    python chip_smoke.py            # one chip: the served path and its checks
    python chip_smoke.py --chips 4  # four chips: one node per device vs the
                                    # same sessions with every node on one

One chip, in order:

1. device check — platform, kind and count; anything but a TPU exits 1;
2. served path — a 2-node cluster built by ``repro.launch.serve``'s
   ``build_cluster`` (paged batched services, Pallas attention, KV-page
   shipping, eager warm start; both services on the one chip), driven by
   four tenants through ``LLMClient.run_session``: two open with the same
   system prompt (the shared-prefix cascade pass runs), one roams from
   ``edge-0`` to ``edge-1``;
3. served-path assertions — every ticket resolved without error and
   generated tokens, the roam turn was warmed (``"pages"``/``"tokens"``),
   no prime or install failed;
4. kernel check — the lowered decode step contains ``tpu_custom_call``;
5. reference check — logits of one chunked-prefill step and one decode step
   (with a shared-prefix run), Pallas path vs jnp reference path.

Informational lines (compile seconds, per-turn response times, peak device
memory) are printed before the last line, which is one JSON object:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
A failed phase raises, so the script exits non-zero and never prints it.
The script runs in one process and starts no other.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent
SRC = ROOT / "src"
ARCH = "paper-qwen1.5-0.5b"

# Pallas vs reference logits: max|Δ| <= TOL_REL * max|reference logit|.
# Both paths run the same bf16 program outside attention; they differ only
# in the attention core (Mosaic kernel at default dot precision vs jnp
# under "highest"), whose output is rounded to bf16. A rounding that lands
# differently moves an element by one bf16 ulp (2^-8 of its magnitude);
# 24 layers of residual stream carry such ulps to the logits. 2^-5 of the
# largest logit is 4-8 bf16 ulps there. Each run also plants a wrong page
# id in the shared run and asserts that the check sees it.
TOL_REL = 2.0 ** -5

SYSTEM = (
    "You are the control assistant of an autonomous mobile robot fleet at "
    "the edge. Answer with short, concrete steps. The robots carry lidar, "
    "ultrasonic and infrared sensors, a camera, wheel odometry and a "
    "battery monitor; each runs a proportional integral derivative "
    "controller for its motors and a particle filter for localization on a "
    "grid map. When you explain a concept, name the sensor, the state "
    "estimate and the control gain involved."
)


def _sessions():
    """(tenant, [(prompt, node)]) — A and B share SYSTEM on edge-0, C roams
    edge-0 -> edge-1 at its third turn, D stays on edge-1."""
    return [
        ("A", [(SYSTEM + " How do I tune the derivative gain?", "edge-0"),
               ("What happens when the gain is too high?", "edge-0"),
               ("Write the update step in python.", "edge-0")]),
        ("B", [(SYSTEM + " What does a Kalman filter add?", "edge-0"),
               ("Compare it with the particle filter.", "edge-0"),
               ("Which sensor noise matters most?", "edge-0")]),
        ("C", [("What are the most common types of robot sensors?", "edge-0"),
               ("Explain the concept of obstacle avoidance.", "edge-0"),
               ("How would lidar help with mapping?", "edge-1"),
               ("Now include the camera.", "edge-1")]),
        ("D", [("Explain simultaneous localization and mapping.", "edge-1"),
               ("What are the main challenges at the edge?", "edge-1"),
               ("How much memory does a grid map need?", "edge-1")]),
    ]


def track_compiles():
    """Count and time XLA's backend compiles through ``jax.monitoring``
    (tracing and lowering are left out: their events nest)."""
    import jax

    log = {"seconds": 0.0, "backend_compiles": 0}

    def listener(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            log["seconds"] += duration
            log["backend_compiles"] += 1

    jax.monitoring.register_event_duration_secs_listener(listener)
    return log


def run_sessions(cluster, model, sessions, think_ms, max_new_tokens=12):
    """Drive every tenant's session through ``LLMClient.run_session`` on the
    shared event clock. Returns ``{tenant: [Response]}`` after asserting
    that every ticket resolved without error and generated tokens."""
    from repro.edge import LLMClient

    traces = {}
    for tenant, turns in sessions:
        client = LLMClient(cluster, model=model, max_new_tokens=max_new_tokens)
        traces[tenant] = client.run_session(turns, think_ms=think_ms)
    cluster.run_until_quiet()
    out = {}
    for tenant, trace in traces.items():
        n = len(dict(sessions)[tenant])
        assert len(trace.tickets) == n, (tenant, len(trace.tickets), n)
        for i, t in enumerate(trace.tickets):
            assert t.done, (tenant, i, "ticket never resolved")
            r = t.response
            assert r.error is None, (tenant, i, r.error)
            assert r.n_generated_tokens > 0, (tenant, i, "no tokens")
        out[tenant] = [t.response for t in trace.tickets]
    return out


def assert_no_hidden_fallbacks(cluster):
    """Device errors in the warm-start prime or the page install are
    counted and their text kept; on this path there must be none."""
    store = cluster.store
    assert store.prime_failures == 0, store.prime_errors
    ship = cluster.kv_ship
    if ship is not None:
        assert ship.install_failures == 0, ship.install_errors
        bad = [f for f in ship.fallback_log if f[2].startswith("install-failed")]
        assert not bad, bad


def served_path(arch, seed=0):
    """Phases 2 and 3. Returns (cluster, model, responses)."""
    from repro.launch.serve import build_cluster

    cluster, model = build_cluster(arch, 2, seed=seed, kv_ship=True)
    responses = run_sessions(cluster, model, _sessions(), think_ms=2000.0)
    roam = responses["C"][2]
    assert roam.served_by == "edge-1", roam.served_by
    assert roam.timing.kv_warm_source in ("pages", "tokens"), (
        "roam turn was not warmed", roam.timing.kv_warm_source,
    )
    assert_no_hidden_fallbacks(cluster)
    srv = cluster.node("edge-0").service.server
    assert srv.cascade_steps > 0, "the shared-prefix cascade pass never ran"
    return cluster, model, responses


def kernel_check(server):
    """Phase 4: lower one decode step of a served node — a width of 16
    pages with an 8-page shared run — and count its Mosaic kernels."""
    import numpy as np

    w, ps = 16, server.allocator.page_size
    lowered = server._decode_paged.lower(
        server.params, server.allocator.pools,
        server._put(server._table[:, :w]), server._kv_pos[:, : w * ps],
        server._put(server._next_tok[:, None]), server._put(server._pos),
        server._put(np.arange(1, 9, dtype=np.int32)),
    )
    n = lowered.as_text().count("tpu_custom_call")
    assert n >= 2, f"decode step has {n} tpu_custom_call (want paged + cascade)"
    return n


def reference_check(cfg, params, device, seed=0):
    """Phase 5: the logits of one 100-token chunked-prefill step and of one
    2-lane decode step whose lanes share a 4-page prefix, Pallas path vs
    jnp reference path (reference under "highest" matmul precision).
    Returns [(name, max|Δ|, max|ref|)] after asserting the tolerance; the
    last entry is a planted fault, asserted to exceed it."""
    import jax
    import numpy as np

    from repro.models import decode_step_paged, layer_groups, prefill_chunk_paged
    from repro.models.cache import init_paged_pool

    ps, n_pages, n, mp = 16, 16, 100, 8
    kcfg = cfg.replace(attn_impl="pallas")
    rcfg = cfg.replace(attn_impl="reference")
    pools = [init_paged_pool(cfg, spec.n_blocks, n_pages, ps, device=device)
             for spec in layer_groups(cfg)]
    rng = np.random.default_rng(seed)
    toks = np.zeros((1, 128), np.int32)
    toks[0, :n] = rng.integers(8, cfg.vocab_size, n)
    table = np.array([[1, 2, 3, 4, 5, 6, 7, 0]], np.int32)
    chunk_args = jax.device_put(
        (table, toks, np.zeros(1, np.int32), np.array([n], np.int32),
         np.int32(0)), device,
    )

    def chunk(c):
        return jax.jit(
            lambda p, *a: prefill_chunk_paged(p, c, *a[:-1], n_skip=a[-1])
        )

    lp, pools_k = chunk(kcfg)(params, pools, *chunk_args)
    with jax.default_matmul_precision("highest"):
        lr, _ = chunk(rcfg)(params, pools, *chunk_args)

    # lane 0 continues the prefilled 100 tokens; lane 1 shares its first
    # 4 pages (positions 0..63) and writes its own token into page 9
    iota = np.arange(mp * ps)
    dec_args = jax.device_put((
        np.array([[1, 2, 3, 4, 5, 6, 7, 0], [1, 2, 3, 4, 9, 0, 0, 0]], np.int32),
        np.stack([np.where(iota < n, iota, -1),
                  np.where(iota < 64, iota, -1)]).astype(np.int32),
        rng.integers(8, cfg.vocab_size, (2, 1)).astype(np.int32),
        np.array([n, 64], np.int32),
    ), device)
    shared = jax.device_put(np.array([1, 2, 3, 4], np.int32), device)

    def decode(c):
        return jax.jit(lambda p, *a: decode_step_paged(p, c, *a))

    dp, _, _ = decode(kcfg)(params, pools_k, *dec_args, shared)
    with jax.default_matmul_precision("highest"):
        dr, _, _ = decode(rcfg)(params, pools_k, *dec_args)
    # the check must see a fault: the same decode with one wrong page id in
    # the shared run (the cascade pass reads page 5 where page 4 belongs)
    bad = jax.device_put(np.array([1, 2, 3, 5], np.int32), device)
    df, _, _ = decode(kcfg)(params, pools_k, *dec_args, bad)

    def delta(name, a, b):
        a = np.asarray(a, np.float32)
        b = np.asarray(b, np.float32)
        assert np.isfinite(a).all() and np.isfinite(b).all(), name
        return float(np.abs(a - b).max()), float(np.abs(b).max())

    results = []
    for name, a, b in (("chunked prefill", lp, lr), ("decode", dp, dr)):
        d, scale = delta(name, a, b)
        assert d <= TOL_REL * scale, (name, d, TOL_REL * scale)
        results.append((name, d, scale))
    d, scale = delta("planted fault", df, dr)
    assert d > TOL_REL * scale, ("planted fault not seen", d, TOL_REL * scale)
    results.append(("decode with a wrong shared page (must fail)", d, scale))
    return results


def probe_logits(service, seed=0, n=48):
    """Prefill a fixed ``n``-token prompt through a node's own prefiller
    into scratch pages and return its last-position logits (host)."""
    import numpy as np

    srv = service.server
    alloc = srv.allocator
    pages = alloc.alloc(alloc.pages_for(n))
    assert pages is not None
    ids = np.random.default_rng(seed).integers(8, srv.cfg.vocab_size, n)
    try:
        return np.asarray(srv._prefiller.run_chunk(pages, ids.tolist(), 0),
                          np.float32)
    finally:
        alloc.decref(pages)


def four_chip_layouts(arch, devices, seed=0):
    """The four-chip path: 4 nodes behind the residency router, node i's
    service on ``devices[i]``, one roam that ships KV pages from edge-0 to
    edge-2; then the same sessions with every node on ``devices[0]``.
    Asserts identical ticket outcomes and probe logits within TOL_REL.
    Both layouts serve at max_len 1024, so that four nodes' page pools fit
    on the one device of the second."""
    import numpy as np

    from repro.launch.serve import build_cluster

    sessions = [
        ("R", [("What are the most common types of robot sensors?", "edge-0"),
               ("Explain the concept of obstacle avoidance.", "edge-0"),
               ("How would lidar help with mapping?", "edge-2")]),
        ("S", [(SYSTEM + " How do I tune the derivative gain?", None),
               ("Write the update step in python.", None)]),
        ("T", [("Explain simultaneous localization and mapping.", None),
               ("What are the main challenges at the edge?", None)]),
    ]
    layouts = {"one node per device": list(devices[:4]),
               "all nodes on one device": [devices[0]] * 4}
    seen = {}
    for name, devs in layouts.items():
        cluster, model = build_cluster(
            arch, 4, seed=seed, devices=devs, max_len=1024, kv_ship=True,
            kv_ship_force="ship", router="residency",
        )
        for nid, dev in zip(cluster.nodes, devs):
            srv = cluster.node(nid).service.server
            placed = {d for leaf in (srv.allocator.pools[0]["k"],
                                     srv.params["embed"]["tok"])
                      for d in leaf.devices()}
            assert placed == {dev}, (nid, placed, dev)
        outcomes = []
        for tenant, turns in sessions:   # one tenant at a time: same order
            resp = run_sessions(cluster, model, [(tenant, turns)], 5000.0, 8)
            outcomes += [(tenant, r.served_by, r.text, r.n_generated_tokens,
                          r.timing.kv_warm_source) for r in resp[tenant]]
        roam = outcomes[2]
        assert roam[1] == "edge-2" and roam[4] == "pages", roam
        assert cluster.kv_ship.installed >= 1, cluster.kv_ship.stats()
        assert_no_hidden_fallbacks(cluster)
        probes = [probe_logits(cluster.node(nid).service) for nid in cluster.nodes]
        seen[name] = (outcomes, probes, cluster.kv_ship.installed)
        del cluster
    (oa, pa, ia), (ob, pb, ib) = seen.values()
    assert oa == ob, (oa, ob)
    deltas = []
    for a, b in zip(pa, pb):
        d, scale = float(np.abs(a - b).max()), float(np.abs(b).max())
        assert d <= TOL_REL * scale, (d, scale)
        deltas.append(d)
    return oa, deltas, (ia, ib)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip layout comparison")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and probe data")
    args = ap.parse_args(argv)

    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: {SRC / 'repro'} not found — run from a checkout "
              "of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))
    import time

    import jax

    from repro.launch.serve import configure_compile_cache

    cache_dir = configure_compile_cache()
    devs = jax.devices()
    dev = devs[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devs)}")
    if dev.platform != "tpu":
        print("chip_smoke: no TPU found; this smoke has no CPU fallback",
              file=sys.stderr)
        return 1
    compiles = track_compiles()
    t0 = time.perf_counter()

    if args.chips == 4:
        assert len(devs) >= 4, f"--chips 4 needs 4 devices, found {len(devs)}"
        outcomes, deltas, installed = four_chip_layouts(ARCH, devs, args.seed)
        for o in outcomes:
            print(f"turn: tenant={o[0]} node={o[1]} tokens={o[3]} warm={o[4]}")
        print(f"four-chip: outcomes identical across layouts; page installs "
              f"{installed[0]} / {installed[1]}; probe logits max|Δ| per node "
              f"{deltas}")
    else:
        cluster, model, responses = served_path(ARCH, args.seed)
        for tenant, rs in responses.items():
            for r in rs:
                print(f"turn: tenant={tenant} turn={r.turn} node={r.served_by} "
                      f"ctx={r.n_context_tokens} gen={r.n_generated_tokens} "
                      f"warm={r.timing.kv_warm_source} "
                      f"response_ms={r.timing.response_time_ms:.1f} "
                      f"queue_ms={r.timing.queue_ms:.1f} "
                      f"ttft_ms={r.timing.ttft_ms:.1f} "
                      f"decode_p99_ms={r.timing.decode_p99_ms:.1f}")
        srv = cluster.node("edge-0").service.server
        ship = cluster.kv_ship.stats()
        print(f"served: cascade_steps={srv.cascade_steps} "
              f"prime_failures={cluster.store.prime_failures} "
              f"install_failures={ship['install_failures']} "
              f"ship_installed={ship['installed']} fallbacks={ship['fallbacks']}")
        n = kernel_check(srv)
        print(f"kernel: decode step lowers to {n} tpu_custom_call ops")
        for name, d, scale in reference_check(srv.cfg, srv.params, srv.device,
                                              args.seed):
            print(f"reference: {name} logits max|Δ|={d:.6g} "
                  f"max|ref|={scale:.6g} tol={TOL_REL * scale:.6g}")

    stats = dev.memory_stats() or {}
    print(f"info: backend_compile_seconds={compiles['seconds']:.1f} "
          f"backend_compiles={compiles['backend_compiles']} "
          f"wall_seconds={time.perf_counter() - t0:.1f} "
          f"peak_bytes_in_use={stats.get('peak_bytes_in_use')} "
          f"compile_cache={cache_dir}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": len(devs),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
