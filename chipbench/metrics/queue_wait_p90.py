"""queue_wait_p90 (s): 90th percentile, over the steps due in the window,
of the time each waited in its engine's queue, by the engine's own
stamps on the request: from ``t_submit`` (submitted) to ``t_admit`` (its
admission began).  A step still queued when the run stops counts the
time up to the stop.  None where the engine stamps no times."""
from chipbench import stats


def read(run):
    waits = []
    for s in run.rec.steps:
        if not s.counted or s.req is None:
            continue
        t_submit = getattr(s.req, "t_submit", None)
        if t_submit is None:
            return None
        t_admit = s.req.t_admit
        waits.append((run.rec.end if t_admit is None else t_admit)
                     - t_submit)
    return stats.percentile(waits, 90) if waits else None
