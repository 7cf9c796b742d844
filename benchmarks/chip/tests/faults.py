"""Faults planted under the timed path, for the test that sees
``correct`` come out false: each wraps the compiled programs of a
``write_read`` cell the way a broken program would behave."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def unchanged(cell):
    """The write returns its state unchanged: a file of zeros."""
    w = cell.write

    def write(*a):
        f, st = w(*a)
        return jnp.zeros_like(f), st
    cell.write = write


def half_batch(cell):
    """Half of every rank's requests left out."""
    w = cell.write

    def write(o, l, c, d):
        return w(o, l, c // 2, d)
    cell.write = write


def no_exchange(cell):
    """The exchange between nodes left out: each node's domain holds
    only the bytes of that node's own ranks."""
    w = cell.write
    n_nodes = cell.layout.stripe_count
    per_node = cell.counts.shape[0] // n_nodes

    def write(o, l, c, d):
        parts = []
        for n in range(n_nodes):
            mine = cell.counts.copy()
            mine[np.arange(mine.shape[0]) // per_node != n] = 0
            f, st = w(o, l, jax.device_put(mine, c.sharding), d)
            parts.append(np.asarray(f)[n])
        return jnp.asarray(np.stack(parts)), st
    cell.write = write


def altered_write(cell):
    """One byte of the file altered where the write produces it."""
    w = cell.write

    def write(*a):
        f, st = w(*a)
        return f.at[0, 7].add(1 << 8), st
    cell.write = write


def altered_read(cell):
    """One byte of a rank's payload altered where the read produces it."""
    r = cell.read

    def read(*a):
        return r(*a).at[0, 3].add(1)
    cell.read = read


ONE_CHIP = ("unchanged", "half_batch", "altered_write", "altered_read")
FOUR_CHIPS = ONE_CHIP + ("no_exchange",)
