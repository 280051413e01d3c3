"""prefill_stall_share (%): seconds of the prefill events in the window
over the seconds of prefill and decode events (engine events): the share
of engine time in which decoding requests wait on an admitted prompt."""


def read(run):
    tot = {"prefill": 0.0, "decode": 0.0}
    for _, kind, _, dt, t in run.rec.events:
        if run.rec.in_window(t):
            tot[kind] += dt
    den = tot["prefill"] + tot["decode"]
    return 100.0 * tot["prefill"] / den if den and tot["prefill"] else None
