"""Scheduler: occupied decode lanes over ``n_slots``, averaged over every
decode call of the window (counted by the benchmark's engine wrapper)."""


def read(run):
    occ = run.window.get("lane_occupancy")
    return None if occ is None else 100.0 * occ
