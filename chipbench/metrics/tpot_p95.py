"""tpot_p95 (ms): 95th percentile over every gap between consecutive
output tokens, on the host, of the steps due in the window."""
from chipbench import stats


def read(run):
    g = stats.token_gaps(run.rec.steps)
    return 1e3 * stats.percentile(g, 95) if g else None
