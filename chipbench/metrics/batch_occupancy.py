"""batch_occupancy (%): active requests of each decode step in the window
over the batch slots, averaged over the steps (engine events)."""


def read(run):
    act = [size for _, kind, size, _, t in run.rec.events
           if kind == "decode" and run.rec.in_window(t)]
    slots = run.cell.spec["serve"]["max_batch"]
    return 100.0 * sum(act) / (len(act) * slots) if act else None
