"""Seeded random weights, made on the device in one jitted call.

The benchmark makes the weights, not the program: the program hands over
only the shapes of its parameter tree.  Every leaf is drawn from a key
folded from the run's seed and the leaf's index, scaled by the usual
fan-in rule, and cast to the type the weights are served in inside the
same jitted call, so no float32 copy of a large leaf ever sits in device
memory between calls and nothing is made on the host.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative integer, wider than 32 bits too.
    The ``rbg`` generator: the chip's own random bits, many times faster
    than threefry for billions of weights, and the same on every run of
    one platform."""
    words = np.random.SeedSequence(int(seed)).generate_state(2)
    return jax.random.fold_in(jax.random.key(int(words[0]), impl="rbg"),
                              int(words[1]))


def leaf_std(path: str, shape: Tuple[int, ...]) -> float:
    """The standard deviation a leaf is drawn with; 0 marks a leaf of ones.

    Norm scales are ones; the token table has unit-norm rows on average
    (std ``d**-0.5``); every other matrix is scaled by its fan-in: the
    input axis, or for the attention output projection ``[heads, dh, d]``
    the two input axes."""
    if len(shape) == 1:
        return 0.0
    if path.endswith("table"):
        return shape[-1] ** -0.5
    fan_in = shape[0] * shape[1] if path.endswith("wo") else shape[0]
    return 1.0 / math.sqrt(fan_in)


def path_name(path) -> str:
    return "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                    for p in path)


def make_weights(shapes: Any, seed: int, dtype=jnp.bfloat16) -> Any:
    """Weights for a tree of ``ShapeDtypeStruct`` leaves, in one jit call.

    Returns uncommitted device arrays of ``dtype``."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    specs = [(path_name(p), tuple(x.shape)) for p, x in leaves]

    def draw(key):
        out = []
        for i, (name, shape) in enumerate(specs):
            std = leaf_std(name, shape)
            if std == 0.0:
                out.append(jnp.ones(shape, dtype))
                continue
            k = jax.random.fold_in(key, i)
            out.append((jax.random.normal(k, shape, jnp.float32) * std)
                       .astype(dtype))
        return out

    arrays = jax.jit(draw)(seed_key(seed))
    return jax.tree_util.tree_unflatten(treedef, arrays)


def flat_weights(params: Any) -> Dict[str, jax.Array]:
    """``{"layers/3/mixer/wq": array, ...}``: the tree keyed by path, the
    form the reference reads."""
    leaves, _ = jax.tree_util.tree_flatten_with_path(params)
    return {path_name(p): x for p, x in leaves}

