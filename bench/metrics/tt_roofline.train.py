"""Kernels: least time of the projections that ran on TT kernels (the
larger of FLOPs over peak and bytes over HBM bandwidth, benchlib.work)
over the device time of the TT kernel family in the trace."""


def read(run):
    if run.kind != "train" or run.trace is None:
        return None
    kernel_s = run.trace["families"].get("tt", 0.0)
    if kernel_s <= 0 or run.counter.tt_least_s <= 0:
        return None
    return 100.0 * run.counter.tt_least_s / kernel_s
