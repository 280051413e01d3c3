"""out_tok_s (tokens/s): output tokens that reached the host inside the
window, of any step, per second of the window."""
from chipbench import stats


def read(run):
    r = run.rec
    return stats.tokens_in(r.steps, r.t0, r.t0 + r.seconds) / r.seconds
