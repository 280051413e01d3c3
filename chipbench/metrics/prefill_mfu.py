"""prefill_mfu (%): model operations of the window's prefills over the
prefill and splice programs' device time times the chip's bf16 peak."""
from chipbench import work


def read(run):
    dev = work.device_s(run)
    return work.share(work.prefill(run)[0], dev and dev["prefill"]
                      * run.peak["bf16_flops_s"])
