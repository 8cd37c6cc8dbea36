"""Device: share of the traced window in which no operation ran on the
device (1 - busy / window, busy being the union of device op intervals)."""


def read(run):
    if run.kind != "serve" or run.trace is None:
        return None
    t = run.trace
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
