"""goodput (req/s): step requests due in the window that finished all
their tokens by their deadline, per second of the window."""
from chipbench import stats


def read(run):
    return stats.goodput(run.rec.steps, run.rec.seconds)
