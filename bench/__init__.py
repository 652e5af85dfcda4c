"""The benchmark of the served edge path: cells, traffic, the reference and
the reduction from traces to metrics. Run one cell with
``python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>``."""
