"""Faults planted under the timed path, to show that ``correct`` sees them.

Each fault wraps one of the program's functions: the serving engine's
decode step, or the train step the program builds.  ``plant(name)``
installs it and returns a function that takes it out again.  The CPU
tests plant them at smoke size; ``bench/readings.py --fault <name>``
plants one at the cell's own size on the chip.  The benchmark's own runs
never plant one.
"""

from __future__ import annotations

from typing import Callable


def _serve_token_altered(orig):
    calls = [0]

    def decode(self, tok, pos, caches):
        import numpy as np

        rows, caches = orig(self, tok, pos, caches)
        calls[0] += 1
        if calls[0] % 4 == 0:   # every 4th step's tokens replaced where made
            rows = rows.copy()
            lanes = np.arange(rows.shape[0])
            rows[lanes, rows.argmin(axis=1)] = rows.max(axis=1) + 1.0
        return rows, caches

    return decode


def _serve_state_unchanged(orig):
    def decode(self, tok, pos, caches):
        import jax
        import jax.numpy as jnp

        kept = jax.tree.map(jnp.copy, caches)
        rows, _ = orig(self, tok, pos, caches)
        return rows, kept   # the step's cache writes are lost

    return decode


def _serve_half_batch(orig):
    def decode(self, tok, pos, caches):
        rows, caches = orig(self, tok, pos, caches)
        half = rows.shape[0] // 2
        rows = rows.copy()
        rows[half:] = rows[:rows.shape[0] - half]   # upper lanes left out
        return rows, caches

    return decode


def _train_state_unchanged(orig):
    def make(*a, **kw):
        step = orig(*a, **kw)

        def broken(params, opt_state, batch):
            _, _, metrics = step(params, opt_state, batch)
            return params, opt_state, metrics

        return broken

    return make


def _train_half_batch(orig):
    def make(*a, **kw):
        step = orig(*a, **kw)

        def broken(params, opt_state, batch):
            half = batch["tokens"].shape[0] // 2
            return step(params, opt_state,
                        {k: v[:half] for k, v in batch.items()})

        return broken

    return make


def _engine():
    from repro.serve import engine

    return engine.ServeEngine, "decode"


def _steps():
    from repro.launch import steps

    return steps, "make_train_step"


#: name -> (where the wrapped function lives, the wrapper)
FAULTS = {
    "serve.token_altered": (_engine, _serve_token_altered),
    "serve.state_unchanged": (_engine, _serve_state_unchanged),
    "serve.half_batch": (_engine, _serve_half_batch),
    "train.state_unchanged": (_steps, _train_state_unchanged),
    "train.half_batch": (_steps, _train_half_batch),
}


def plant(name: str) -> Callable[[], None]:
    """Install fault ``name``; returns the function that takes it out."""
    where, wrap = FAULTS[name]
    owner, attr = where()
    orig = getattr(owner, attr)
    setattr(owner, attr, wrap(orig))
    return lambda: setattr(owner, attr, orig)
