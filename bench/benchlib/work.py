"""Operations and bytes a model step needs, counted from shapes.

Counted the same way whatever implements a layer, and as a lower bound
for any implementation, so that no plan, path or fusion can read above
its roofline:

  TT projection  the least input-dependent multiply-accumulates over
                 every contraction order of its network (steps that join
                 cores alone count zero: they can be folded once);
  dense matrix   tokens x d_in x d_out;
  attention      causal, over the real context only: 2 x heads x
                 head_dim MACs per (query, visible key) pair;
  head           the tied TT head, only where the model needs logits;
  training       3 x forward; recomputed work does not count;
  bytes          a projection's input and output activations plus its
                 cores.

FLOPs are 2 x MACs.  The peaks of each chip are in ``peaks.json`` beside
this package, keyed by JAX's ``device_kind``.
"""

from __future__ import annotations

import functools
import json
import math
import os
from typing import Optional

import numpy as np

PEAKS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "peaks.json")


def peaks(device_kind: str) -> dict:
    """``{"flops", "hbm_bytes_per_s", ...}`` of a chip; an unknown chip is
    an error, never a default."""
    with open(PEAKS) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"have {sorted(table)}")
    return table[device_kind]


# -- least input-dependent MACs of a tensor network ----------------------

def least_input_macs(cores: list[dict], x: dict) -> int:
    """Least multiply-accumulates, over every contraction order, of the
    steps that involve the streamed input ``x``.

    ``cores`` and ``x`` map edge labels to sizes; an edge shared by two
    tensors is contracted.  Any order absorbs the cores into the input
    node group by group (each group joined among itself first, at no
    input-dependent cost), and one absorption costs the product of the
    sizes of every edge it touches.  A dynamic programme over the set of
    absorbed cores finds the least sum exactly.
    """
    n = len(cores)
    full = (1 << n) - 1
    sizes = {e: d for t in cores + [x] for e, d in t.items()}

    def free(members) -> set:
        c: dict[str, int] = {}
        for t in members:
            for e in t:
                c[e] = c.get(e, 0) + 1
        return {e for e, k in c.items() if k == 1}

    @functools.lru_cache(maxsize=None)
    def group_edges(mask: int) -> frozenset:
        return frozenset(free([cores[i] for i in range(n) if mask >> i & 1]))

    @functools.lru_cache(maxsize=None)
    def x_edges(mask: int) -> frozenset:
        return frozenset(free([x] + [cores[i] for i in range(n)
                                     if mask >> i & 1]))

    best = {0: 0}
    for mask in range(full + 1):
        if mask not in best:
            continue
        rest = full & ~mask
        sub = rest
        while sub:
            edges = x_edges(mask) | group_edges(sub)
            cost = best[mask] + math.prod(sizes[e] for e in edges)
            nxt = mask | sub
            if cost < best.get(nxt, math.inf):
                best[nxt] = cost
            sub = (sub - 1) & rest
    return int(best[full])


def tt_linear_tensors(tokens: int, in_modes, out_modes, ranks):
    """Cores and input of a TT matrix ``d_in -> d_out`` (cores carry the
    output modes first, then the input modes, joined by rank edges)."""
    modes = [("o", m) for m in out_modes] + [("i", m) for m in in_modes]
    cores = []
    for k, (side, m) in enumerate(modes):
        c = {f"{side}{k}": m}
        if k > 0:
            c[f"r{k}"] = ranks[k - 1]
        if k < len(modes) - 1:
            c[f"r{k + 1}"] = ranks[k]
        cores.append(c)
    x = {"t": tokens}
    x.update({f"i{len(out_modes) + k}": m for k, m in enumerate(in_modes)})
    return cores, x


def tt_head_tensors(tokens: int, v_modes, d_modes, ranks):
    """Cores ``(r, v, d, r)`` and input ``(t, d...)`` of the tied TT head
    ``d_model -> vocab``."""
    n = len(v_modes)
    cores = []
    for k in range(n):
        c = {f"v{k}": v_modes[k], f"d{k}": d_modes[k]}
        if k > 0:
            c[f"r{k}"] = ranks[k - 1]
        if k < n - 1:
            c[f"r{k + 1}"] = ranks[k]
        cores.append(c)
    x = {"t": tokens}
    x.update({f"d{k}": m for k, m in enumerate(d_modes)})
    return cores, x


# -- the model's projections, read from its parameter shapes -------------

class Projection:
    """One projection of a layer: a TT matrix or a dense matrix.

    Every input-dependent step of a TT contraction carries the token
    edge, so its cost is linear in the tokens, and so is the least:
    ``macs(t) = t * macs(1)``.
    """

    def __init__(self, name: str, d_in: int, d_out: int,
                 tt: Optional[tuple] = None, core_elems: int = 0,
                 head: bool = False) -> None:
        self.name, self.d_in, self.d_out = name, d_in, d_out
        self.tt, self.core_elems = tt, core_elems
        if tt is None:
            self.macs_per_token = d_in * d_out
        else:
            build = tt_head_tensors if head else tt_linear_tensors
            self.macs_per_token = least_input_macs(*build(1, *tt))

    def macs(self, tokens: int) -> int:
        return tokens * self.macs_per_token

    def bytes(self, tokens: int, act_bytes: int, param_bytes: int) -> int:
        return (tokens * (self.d_in + self.d_out) * act_bytes
                + (self.core_elems or self.d_in * self.d_out) * param_bytes)


def _tt_from_cores(shapes: list, n_out: int):
    n = len(shapes)
    modes = [s[-2] if k < n - 1 else s[-1] for k, s in enumerate(shapes)]
    ranks = [s[-1] for s in shapes[:-1]]
    elems = sum(math.prod(s[-3:] if 0 < k < n - 1 else s[-2:])
                for k, s in enumerate(shapes))
    return (tuple(modes[n_out:]), tuple(modes[:n_out]), tuple(ranks)), elems


def projections(param_shapes, n_out: int) -> dict[str, Projection]:
    """Per-layer projections (``attn.wq`` ... ``mlp.wd``) and the tied
    ``head``, from the parameter tree's shapes (layer leaves are stacked
    on a leading axis)."""
    out: dict[str, Projection] = {}
    blocks = param_shapes["blocks"]
    for group in ("attn", "mlp"):
        for name, p in blocks[group].items():
            full = f"{group}.{name}"
            if "w" in p:
                d_in, d_out = p["w"].shape[-2:]
                out[full] = Projection(full, d_in, d_out)
                continue
            n = sum(1 for k in p if k.startswith("core"))
            tt, elems = _tt_from_cores(
                [p[f"core{k}"].shape for k in range(n)], n_out)
            out[full] = Projection(full, math.prod(tt[0]), math.prod(tt[1]),
                                   tt, elems)
    e = param_shapes["embed"]
    if "table" in e:
        v, d = e["table"].shape
        out["head"] = Projection("head", d, v)
    else:
        n = sum(1 for k in e if k.startswith("core"))
        cs = [e[f"core{k}"].shape for k in range(n)]
        v_modes = tuple(c[1] for c in cs)
        d_modes = tuple(c[2] for c in cs)
        ranks = tuple(c[3] for c in cs[:-1])
        out["head"] = Projection(
            "head", math.prod(d_modes), math.prod(v_modes),
            (v_modes, d_modes, ranks), sum(math.prod(c) for c in cs),
            head=True)
    return out


class Counter:
    """Accumulates the FLOPs of a window's model steps, and the least
    time of the projections that ran on TT kernels (``kernels``: the
    names of the projections the execution log saw on one)."""

    def __init__(self, projs: dict[str, Projection], *, n_layers: int,
                 n_heads: int, head_dim: int, act_bytes: int,
                 param_bytes: int, peak: dict) -> None:
        self.projs = projs
        self.n_layers, self.n_heads, self.head_dim = n_layers, n_heads, head_dim
        self.act_bytes, self.param_bytes = act_bytes, param_bytes
        self.peak = peak
        self.flops = 0.0
        self.tt_least_s = 0.0

    def _layers(self, tokens: int, scale: float, kernels) -> None:
        for name, p in self.projs.items():
            if name == "head":
                continue
            macs = p.macs(tokens)
            self.flops += scale * 2.0 * macs * self.n_layers
            if name in kernels:
                t_f = 2.0 * macs / self.peak["flops"]
                t_b = p.bytes(tokens, self.act_bytes, self.param_bytes) \
                    / self.peak["hbm_bytes_per_s"]
                self.tt_least_s += scale * max(t_f, t_b) * self.n_layers

    def _attention(self, pairs: int, scale: float) -> None:
        self.flops += (scale * 2.0 * 2 * self.n_heads * self.head_dim
                       * pairs * self.n_layers)

    def _head(self, tokens: int, scale: float) -> None:
        self.flops += scale * 2.0 * self.projs["head"].macs(tokens)

    def prefill(self, prompt_len: int, kernels=()) -> None:
        """One prompt: every position through the layers, causal
        attention, logits at the last position only."""
        self._layers(prompt_len, 1.0, kernels)
        self._attention(prompt_len * (prompt_len + 1) // 2, 1.0)
        self._head(1, 1.0)

    def decode(self, contexts, kernels=()) -> None:
        """One decode step of the lanes in use; ``contexts`` holds each
        lane's visible keys (its position + 1)."""
        contexts = np.asarray(contexts)
        if contexts.size == 0:
            return
        self._layers(int(contexts.size), 1.0, kernels)
        self._attention(int(contexts.sum()), 1.0)
        self._head(int(contexts.size), 1.0)

    def train_step(self, batch: int, seq: int, kernels=()) -> None:
        """Forward and backward of ``batch`` rows of ``seq`` tokens."""
        self._layers(batch * seq, 3.0, kernels)
        self._attention(batch * seq * (seq + 1) // 2, 3.0)
        self._head(batch * seq, 3.0)
