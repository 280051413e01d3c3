"""decode_step_ms (ms): mean time of a decode step in the window, by the
engine's own clock up to the next tokens on the host (engine events)."""


def read(run):
    dts = [dt for _, kind, _, dt, t in run.rec.events
           if kind == "decode" and run.rec.in_window(t)]
    return 1e3 * sum(dts) / len(dts) if dts else None
