"""prefill_roofline (%): least time of the window's prefills over the device
time of the prefill and splice programs in the trace."""
from chipbench import work


def read(run):
    dev = work.device_s(run)
    return work.share(work.prefill(run)[1], dev and dev["prefill"])
