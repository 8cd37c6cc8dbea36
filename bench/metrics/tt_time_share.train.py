"""Kernels: device time of the TT kernel family over device busy time."""


def read(run):
    if run.kind != "train" or run.trace is None or run.trace["busy_s"] <= 0:
        return None
    kernel_s = run.trace["families"].get("tt", 0.0)
    if kernel_s <= 0:
        return None
    return 100.0 * kernel_s / run.trace["busy_s"]
