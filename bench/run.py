#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result.

    python3 bench/run.py --workload qwen15-roam-chat --seed 7 --seconds 30 --trace 0

The cell, its configuration, traffic mix and metrics are found by name in
``BENCHMARK.json``. Informational lines come first; the last line of
standard output is one JSON object (``correct``, ``attempted``, ``failed``,
``metrics``, ``device``, with ``--trace 1`` also ``breakdown``, and last
``checks``: every number compared, beside its limit), and the same checks
are the last lines of standard error. Without a TPU, or with fewer chips
than the cell asks for, it prints no result and exits 1. One process, no
child.
"""

import time

PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
CACHE_DIR = ROOT / ".jax_cache"


def use_cache_dir() -> None:
    """Give JAX's persistent compile cache its fixed home in the checkout
    (before JAX is imported), and cache every program."""
    CACHE_DIR.mkdir(parents=True, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"


def load_spec(bench, name: str, program_cfg=None):
    """The cell ``name`` of ``bench`` (BENCHMARK.json) with its files."""
    from bench import harness, model, traffic

    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise SystemExit(f"bench/run.py: no workload {name!r} in BENCHMARK.json")
    conf = model.load_config(entry["config"])
    with open(ROOT / "bench" / "cells" / f"{name}.json") as f:
        cell = json.load(f)
    if program_cfg is None:
        program_cfg = model.program_config(conf)
    return entry, harness.CellSpec(name=name, conf=conf,
                                   mix=traffic.load_mix(entry["traffic"]),
                                   cell=cell, program_cfg=program_cfg)


def readers_for(bench, name: str, trace: bool):
    """{metric: (read, unit)} of the metrics this cell reports: with trace,
    its per-layer metrics, else its end-to-end metrics."""
    group = bench["per_layer"] if trace else bench["end_to_end"]
    out = {}
    for m in group:
        if "workloads" in m and name not in m["workloads"]:
            continue
        mod = importlib.import_module(f"bench.metrics.{m['name']}")
        out[m["name"]] = (mod.read, m["unit"])
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a non-negative whole number")

    if not (ROOT / "src" / "repro").is_dir():
        print(f"bench/run.py: {ROOT / 'src' / 'repro'} not found: run from a "
              "checkout of the repository", file=sys.stderr)
        return 2
    # the persistent compile cache lives at a fixed path inside the checkout
    use_cache_dir()
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    with open(ROOT / "BENCHMARK.json") as f:
        bench = json.load(f)
    entry, spec = load_spec(bench, args.workload)

    import jax

    from bench import harness

    devs = jax.devices()
    dev = devs[0]
    print(f"device: platform={dev.platform} device_kind={dev.device_kind} "
          f"count={len(devs)}", flush=True)
    if dev.platform != "tpu" or len(devs) < entry["chips"]:
        print(f"bench/run.py: the cell needs {entry['chips']} TPU chip(s); found "
              f"{len(devs)} {dev.platform} device(s). No CPU fallback.",
              file=sys.stderr)
        return 1
    out = harness.run(spec, args.seed, args.seconds, bool(args.trace),
                      PROCESS_START, dev, readers_for(bench, args.workload, args.trace),
                      log=lambda *a: print(*a, flush=True))
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": out["memory_peak_bytes"]}
    if "busy_s" in out:
        device["busy_s"], device["window_s"] = out["busy_s"], out["window_s"]
    line = {"correct": out["correct"], "attempted": out["attempted"],
            "failed": out["failed"], "metrics": out["metrics"], "device": device}
    if "breakdown" in out:
        line["breakdown"] = out["breakdown"]
    line["checks"] = out["checks"]
    for k, v in out["checks"].items():
        print(f"check: {k} {v['value']} limit {v['limit']}", file=sys.stderr)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
