"""Engine: share of the window's host time spent inside the engine's
``prefill_request`` and ``admit`` calls, in which no lane decodes."""


def read(run):
    share = run.window.get("prefill_share")
    return None if share is None else 100.0 * share
