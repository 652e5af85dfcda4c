"""Multi-tenant continuous batching demo (beyond-paper: the paper's eval is
single-client; §5 names multi-tenant scalability as future work).

Submits a burst of requests from several simulated users to one edge node's
BatchedServer and reports completion order, latency, and slot utilization.

    PYTHONPATH=src python examples/multi_tenant.py --slots 4 --requests 10
"""

import argparse
import time
import sys, os
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax

from repro.models import ModelConfig, init_params
from repro.serving import BatchedServer
from repro.tokenizer import get_tokenizer


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--requests", type=int, default=10)
    ap.add_argument("--max-new", type=int, default=12)
    ap.add_argument("--paged", action="store_true",
                    help="page-table KV (block-granular shared pool) instead "
                         "of full-width per-slot caches")
    ap.add_argument("--pallas", action="store_true",
                    help="fused Pallas attention kernels (with --paged: "
                         "decode attends through the page table; interpret "
                         "mode on CPU, so slower here — Mosaic on TPU)")
    args = ap.parse_args()

    cfg = ModelConfig(
        name="mt-demo", arch_type="dense", n_layers=2, d_model=128,
        n_heads=4, n_kv_heads=2, d_ff=256, vocab_size=8192,
        param_dtype="float32", compute_dtype="float32",
        attn_impl="pallas" if args.pallas else "reference",
    )
    params = init_params(jax.random.key(0), cfg)
    tok = get_tokenizer(cfg.vocab_size, seed=0)
    srv = BatchedServer(cfg, params, n_slots=args.slots, max_len=256,
                        paged=args.paged)

    prompts = [
        f"user {i} asks about {topic}"
        for i, topic in enumerate(
            ["slam", "pid control", "lidar", "batteries", "path planning",
             "kalman filters", "grid maps", "motor drivers", "imu fusion",
             "depth cameras"][: args.requests]
        )
    ]
    t0 = time.perf_counter()
    for p in prompts:
        srv.submit(tok.encode(p), max_new=args.max_new)
    done_ms = {}  # every request was submitted at t0
    while srv.busy:
        srv.step()
        now = (time.perf_counter() - t0) * 1e3
        for f in srv.finished:
            done_ms.setdefault(f.request_id, now)
    fin = srv.finished
    wall = time.perf_counter() - t0

    print(f"{len(fin)} requests completed in {wall*1e3:.0f}ms "
          f"on {args.slots} slots")
    for f in sorted(fin, key=lambda f: done_ms[f.request_id]):
        print(f"  req {f.request_id}: {len(f.token_ids):2d} tokens, "
              f"latency {done_ms[f.request_id]:7.1f}ms")
    total_tokens = sum(len(f.token_ids) for f in fin)
    print(f"aggregate throughput: {total_tokens / wall:.1f} tok/s")
    mode = "paged" if args.paged else "full-width"
    print(f"resident KV between requests ({mode}): "
          f"{srv.resident_kv_bytes() / 1e6:.2f} MB "
          f"of {srv.total_kv_bytes() / 1e6:.2f} MB budget")


if __name__ == "__main__":
    main()
