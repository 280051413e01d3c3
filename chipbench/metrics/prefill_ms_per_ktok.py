"""prefill_ms_per_ktok (ms): prefill event seconds in the window per
thousand prompt tokens prefilled (engine events; splice included)."""


def read(run):
    s = n = 0.0
    for _, kind, size, dt, t in run.rec.events:
        if kind == "prefill" and run.rec.in_window(t):
            s, n = s + dt, n + size
    return 1e6 * s / n if n else None
