"""setup_s (s): process start to the opening of the window: weights
made on the device, programs loaded or compiled, warm-up."""


def read(run):
    return run.setup_s
