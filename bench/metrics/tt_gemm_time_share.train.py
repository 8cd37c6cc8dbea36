"""Kernels: device time of the ``tt_gemm`` kernel (ops named
``%tt_gemm.``) over device busy time, in training."""

from benchlib import program


def read(run):
    if run.kind != "train":
        return None
    return program.kernel_share(run, "tt_gemm")
