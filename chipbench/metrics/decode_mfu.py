"""decode_mfu (%): model operations of the window's decode steps over the
decode program's device time times the chip's bf16 peak: the whole
step's share of the peak."""
from chipbench import work


def read(run):
    dev = work.device_s(run)
    return work.share(work.decode(run)[0], dev and dev["decode"]
                      * run.peak["bf16_flops_s"])
