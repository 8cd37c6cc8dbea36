"""The benchmark's own request generator, driven by a traffic file.

Lengths come from a fixed grid of quantiles of a clipped lognormal.  A
serving backlog is a run of blocks of ``block`` requests, and every block
holds the same (prompt, output) pairs: the ``block`` prompt quantiles,
each paired with an output quantile by a permutation fixed for the mix.
The seed only orders the requests inside each block and draws the token
ids.  A window that closes part-way through the backlog has then served
the same lengths on every seed, up to the order inside one block, and
the spread of runs with different seeds is the system's, not the
generator's.
"""

from __future__ import annotations

import statistics

import numpy as np


def lognormal_grid(n: int, dist: dict) -> np.ndarray:
    """``n`` lengths at the quantiles (i + 0.5) / n of the clipped
    lognormal ``{"median", "sigma", "min", "max"}``."""
    nd = statistics.NormalDist()
    z = np.array([nd.inv_cdf((i + 0.5) / n) for i in range(n)])
    x = np.round(dist["median"] * np.exp(dist["sigma"] * z))
    return np.clip(x, dist["min"], dist["max"]).astype(np.int64)


def backlog(traffic: dict, vocab: int, seed: int) -> list:
    """The seeded backlog of a serving mix: ``repro.serve.Request``s,
    every one due at the start of the window (arrival 0)."""
    from repro.serve import Request

    n, b = int(traffic["requests"]), int(traffic["block"])
    if n % b:
        raise ValueError(f"requests {n} is not a multiple of block {b}")
    prompts = lognormal_grid(b, traffic["prompt"])
    # a pairing fixed for the mix, so long prompts do not always come
    # with long outputs, and no seed pairs them otherwise
    outputs = lognormal_grid(b, traffic["output"])[
        np.random.default_rng(0).permutation(b)]
    rng = np.random.default_rng(seed)
    order = np.concatenate([rng.permutation(b) for _ in range(n // b)])
    ids = rng.integers(0, vocab, size=int(prompts.sum()) * (n // b),
                       dtype=np.int64)
    out, at = [], 0
    for rid, i in enumerate(order):
        p = int(prompts[i])
        prompt = tuple(ids[at:at + p].tolist())
        at += p
        out.append(Request(rid=rid, prompt=prompt,
                           max_new_tokens=int(outputs[i]), arrival=0.0))
    return out


def max_seq(traffic: dict) -> int:
    """Cache positions per lane: the mix's ``max_seq``, which must hold
    the longest request it can produce."""
    b = int(traffic["prompt_bucket"])
    p, g = int(traffic["prompt"]["max"]), int(traffic["output"]["max"])
    need = max(-(-p // b) * b, p + g - 1)
    if int(traffic["max_seq"]) < need:
        raise ValueError(f"max_seq {traffic['max_seq']} < {need} needed")
    return int(traffic["max_seq"])

