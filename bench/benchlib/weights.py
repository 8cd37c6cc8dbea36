"""Seeded random weights in the program's parameter layout.

The benchmark, not the program, makes the weights: one jitted call on the
device turns ``--seed`` into every leaf, in the type it is served in.
The program's tree structure and leaf shapes are read with
``jax.eval_shape`` of its init (nothing is computed by it), and the
reference rebuilds the very same arrays from the seed after the window,
so it takes nothing the program made.

Scales follow the usual initialisation, so activations stay in the range
a trained model's do: a dense ``(d_in, d_out)`` matrix has standard
deviation sqrt(2 / (d_in + d_out)); the cores of a TT matrix share one
standard deviation chosen so that their contraction has it; the TT
embedding has 0.02.  Norm scales are 1 + 0.1 N(0, 1) and biases
0.02 N(0, 1), so that neither path can be dropped unseen.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int) -> jax.Array:
    """A JAX key from any whole-number seed (wider than 32 bits too)."""
    words = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32),
                                    impl="threefry2x32")


def _tt_linear_std(shapes: list[tuple[int, ...]], n_out: int) -> float:
    """Per-core std of a TT matrix with cores ``core0..`` (trailing dims:
    ``(m, r)``, ``(r, m, r)`` ... ``(r, m)``), the first ``n_out``
    carrying output modes."""
    n = len(shapes)
    modes = [s[-2] if k < n - 1 else s[-1] for k, s in enumerate(shapes)]
    ranks = [s[-1] for s in shapes[:-1]]
    d_out, d_in = math.prod(modes[:n_out]), math.prod(modes[n_out:])
    target = math.sqrt(2.0 / (d_in + d_out))
    return (target ** 2 / math.prod(ranks)) ** (1.0 / (2 * n))


def _embed_std(shapes: list[tuple[int, ...]]) -> float:
    ranks = [s[-1] for s in shapes[:-1]]
    return (0.02 ** 2 / math.prod(ranks)) ** (1.0 / (2 * len(shapes)))


def leaf_stds(tree_shapes, n_out: int) -> dict:
    """``{path: (kind, std)}`` for every leaf of the parameter tree."""
    out = {}
    flat, _ = jax.tree_util.tree_flatten_with_path(tree_shapes)
    groups: dict[tuple, dict[str, tuple]] = {}
    for path, leaf in flat:
        keys = tuple(getattr(k, "key", getattr(k, "idx", None)) for k in path)
        groups.setdefault(keys[:-1], {})[keys[-1]] = leaf.shape
    for parent, leaves in groups.items():
        cores = sorted((k for k in leaves if str(k).startswith("core")),
                       key=lambda k: int(k[4:]))
        if cores:
            shapes = [leaves[k] for k in cores]
            std = (_embed_std(shapes) if parent and parent[-1] == "embed"
                   else _tt_linear_std(shapes, n_out))
            for k in cores:
                out[parent + (k,)] = ("normal", std)
        for k, shape in leaves.items():
            if k == "w":
                out[parent + (k,)] = (
                    "normal", math.sqrt(2.0 / (shape[-2] + shape[-1])))
            elif k == "table":
                out[parent + (k,)] = ("normal", 0.02)
            elif k == "b":
                out[parent + (k,)] = ("normal", 0.02)
            elif k == "scale":
                out[parent + (k,)] = ("scale", 0.1)
            elif not str(k).startswith("core"):
                raise ValueError(f"no rule for parameter {parent + (k,)}")
    return out


def make(init_shapes, seed: int, dtype, n_out: int):
    """The seeded parameter tree, made on the device in one jitted call.

    ``init_shapes`` is ``jax.eval_shape`` of the program's init; ``n_out``
    is the number of output-mode cores of a TT matrix (the config's
    ``tt.d``)."""
    stds = leaf_stds(init_shapes, n_out)
    flat, treedef = jax.tree_util.tree_flatten_with_path(init_shapes)
    plan = []
    for i, (path, leaf) in enumerate(flat):
        keys = tuple(getattr(k, "key", getattr(k, "idx", None)) for k in path)
        plan.append((i, tuple(leaf.shape)) + stds[keys])

    def build(key):
        leaves = []
        for i, shape, kind, std in plan:
            z = jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.float32)
            x = 1.0 + std * z if kind == "scale" else std * z
            leaves.append(x.astype(dtype))
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return jax.jit(build)(seed_key(seed))
