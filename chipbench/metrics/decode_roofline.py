"""decode_roofline (%): least time of the window's decode steps (the larger
of model operations over peak and bytes over HBM bandwidth, from shapes
and each request's actual context) over the device time of the decode
program in the trace."""
from chipbench import work


def read(run):
    dev = work.device_s(run)
    return work.share(work.decode(run)[1], dev and dev["decode"])
