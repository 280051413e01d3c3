"""first_token_ms (ms): 90th percentile, over the steps due in the window
that their engine admitted, of the time from the start of a step's
admission (``t_admit``) to its first token on the host (``t_first``), by
the engine's own stamps on the request: the prefill and the host's wait
for its first token.  With ``queue_wait_p90`` it splits ``ttft_p90``.
A step admitted but without a first token when the run stops counts the
time up to the stop.  None where the engine stamps no times."""
from chipbench import stats


def read(run):
    times = []
    for s in run.rec.steps:
        if not s.counted or s.req is None:
            continue
        if not hasattr(s.req, "t_first"):
            return None
        t_admit, t_first = s.req.t_admit, s.req.t_first
        if t_admit is not None:
            times.append((run.rec.end if t_first is None else t_first)
                         - t_admit)
    return 1e3 * stats.percentile(times, 90) if times else None
