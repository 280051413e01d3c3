"""ttft_p90 (s): 90th percentile, over every step due in the window, of
the time from its due time to its first token on the host."""
from chipbench import stats


def read(run):
    t = stats.ttfts(run.rec.steps, run.rec.end)
    return stats.percentile(t, 90) if t else None
