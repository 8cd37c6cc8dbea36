"""Engine: share of the window copying logits to the host (program spans
``serve.decode.fetch`` and ``serve.prefill.fetch``, each an
``np.asarray`` of the device's logits)."""

from benchlib import program


def read(run):
    if run.kind != "serve":
        return None
    return program.window_share(run, "serve.decode.fetch",
                                "serve.prefill.fetch")
