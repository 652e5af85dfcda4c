"""Inter-token latency: every gap between consecutive tokens of one turn on
the cluster clock; 95th percentile over all gaps of every turn sent in the
window (a failed turn adds one gap that misses every limit)."""

import numpy as np

from bench.stats import MISSED_MS, percentile


def read(run):
    gaps = []
    for t in run.turns:
        if t.ok:
            gaps.extend(np.diff(t.token_ms).tolist())
        else:
            gaps.append(MISSED_MS)
    return percentile(gaps, 95)
