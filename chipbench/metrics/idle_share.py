"""idle_share (%): 1 - busy / window, where busy is the union of the
device operations' intervals in the traced window (averaged over chips),
and the window leaves out the steps whose programs the trace lost."""
from chipbench import work


def read(run):
    if run.trace is None:
        return None
    busy, window = work.coverage(run)
    return 100.0 * (1.0 - busy / window) if busy else None
