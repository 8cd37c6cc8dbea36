"""Kernels: device time of the ``streaming_tt`` kernel (ops named
``%streaming_tt.``) over device busy time, in training."""

from benchlib import program


def read(run):
    if run.kind != "train":
        return None
    return program.kernel_share(run, "streaming_tt")
