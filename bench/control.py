#!/usr/bin/env python3
"""Readings that set a cell's ``gap_limit``, on the chip, in one process:
for each seed, one run of the cell with a short window at the cell's own
load, its served tokens compared with the float32 reference (the program's
reading) and the tokens the reference computed in fp8 would have served
(the control's reading), on the same sample.

    python3 bench/control.py --workload qwen15-roam-chat --seconds 8 --seeds 1 2 3
    python3 bench/control.py --workload qwen15-roam-chat --seconds 40 --seeds 1 \
        --rates 0.1 0.2 0.3      # a sweep of the session rate for the knee

Prints one JSON line per seed. Not part of a benchmark run."""

import time

PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

from run import ROOT, use_cache_dir  # noqa: E402


def queue_mean(run, half: int):
    """Mean ``Timing.queue_ms`` of the answered turns sent in one half of
    the window."""
    t0 = float(run.spec.cell["warmup_s"]) * 1e3 + half * run.window_ms / 2
    qs = [t.response.timing.queue_ms for t in run.turns
          if t.ok and t0 <= t.sent_ms < t0 + run.window_ms / 2]
    return sum(qs) / len(qs) if qs else None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--rates", type=float, nargs="*", default=[],
                    help="sweep: sessions per second in place of the mix's, "
                         "no control reading")
    args = ap.parse_args(argv)
    use_cache_dir()
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax

    from bench import harness
    from bench.run import load_spec, readers_for

    with open(ROOT / "BENCHMARK.json") as f:
        bench = json.load(f)
    _, spec = load_spec(bench, args.workload)
    mix = spec.mix
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print("bench/control.py: no TPU", file=sys.stderr)
        return 1
    readers = readers_for(bench, args.workload, False)
    # the sweep judges the knee by the queue as well: its 90th percentile,
    # and its mean over each half of the window (a backlog that grows
    # through the window reads higher in the second half)
    readers["queue_p90_ms"] = (importlib.import_module("bench.metrics.queue_p90_ms").read, "ms")
    for half in (0, 1):
        readers[f"queue_mean_ms_half{half + 1}"] = (
            lambda run, half=half: queue_mean(run, half), "ms")
    for rate, seed in [(r, s) for r in args.rates or [None] for s in args.seeds]:
        spec.mix = dict(mix, session_rate_per_s=rate) if rate else mix
        lines = []
        t = time.perf_counter()
        out = harness.run(spec, seed, args.seconds, False, t, dev, readers,
                          control=rate is None, log=lines.append)
        ref = next(x for x in lines if str(x).startswith("reference"))
        win = next(x for x in lines if str(x).startswith("window"))
        pages = next(x for x in lines if str(x).startswith("pages"))
        print(json.dumps({"seed": seed, "rate": spec.mix.get("session_rate_per_s"),
                          "checks": {k: v["value"] for k, v in out["checks"].items()},
                          "reference": ref, "window": win, "pages": pages,
                          "attempted": out["attempted"], "failed": out["failed"],
                          "metrics": {k: v["value"] for k, v in out["metrics"].items()},
                          "wall_s": time.perf_counter() - t}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
