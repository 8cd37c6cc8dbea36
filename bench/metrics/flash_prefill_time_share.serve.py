"""Kernels: device time of the ``flash_prefill`` prefill attention kernel
(ops named ``%flash_prefill.``) over device busy time, in serving.  None
where no op carries the name: a program without the kernel, or a cell
whose prefills never take it."""

from benchlib import program


def read(run):
    if run.kind != "serve":
        return None
    return program.kernel_share(run, "flash_prefill")
