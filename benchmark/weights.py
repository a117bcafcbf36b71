"""Weights and tokens made from ``--seed`` by the benchmark, not the program.

Both the program under test and the plain reference start from these
same values, so the reference takes nothing the program made. Leaf ``i``
of the parameter tree (in JAX's flatten order of the nested dict, i.e.
sorted by key path) draws from ``fold_in(key(seed), i)``: a matrix is
normal with std ``init_std``, a norm scale is ones. All leaves are made
on the device in one jitted call, float32 like the program's master
weights, already in the layout the caller asks for.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any whole number, 64-bit seeds included."""
    words = np.random.SeedSequence(seed % 2**64).generate_state(2)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32))


def start_leaf(key, index: int, shape, init_std: float):
    """Traceable: leaf ``index`` of the seeded start."""
    if len(shape) <= 1:
        return jnp.ones(shape, jnp.float32)
    k = jax.random.fold_in(key, index)
    return init_std * jax.random.normal(k, shape, jnp.float32)


def init_params(key, shapes, init_std: float):
    """Traceable: the parameter tree for ``shapes`` (a tree of objects
    with ``.shape``) from ``key``."""
    leaves, treedef = jax.tree_util.tree_flatten(shapes)
    return jax.tree_util.tree_unflatten(
        treedef,
        [start_leaf(key, i, s.shape, init_std) for i, s in enumerate(leaves)],
    )


def make_params(seed: int, shapes, init_std: float, shardings=None):
    """The seeded parameter tree, placed by ``shardings`` when given."""
    fn = jax.jit(
        lambda k: init_params(k, shapes, init_std), out_shardings=shardings
    )
    return fn(seed_key(seed))


def token_pool(seed: int, n: int, batch: int, seq: int, vocab: int) -> np.ndarray:
    """``n`` batches of uniform token ids, ``(n, batch, seq)`` int32."""
    rng = np.random.default_rng(seed % 2**64)
    return rng.integers(0, vocab, (n, batch, seq), dtype=np.int32)
