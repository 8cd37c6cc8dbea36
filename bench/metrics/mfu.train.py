"""Model step: FLOPs the train window's real tokens need (benchlib.work)
over the window's seconds times the chip's peak."""


def read(run):
    if run.kind != "train" or run.counter.flops <= 0:
        return None
    return 100.0 * run.counter.flops / (run.window["window_s"]
                                        * run.peak["flops"])
