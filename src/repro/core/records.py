"""The scanned round program's client records: one packed table.

Every client's records sit in ONE 2-D table of 32-bit words, clients
concatenated in client order with no padding between them, each record
one row holding every leaf's payload side by side. 4-byte leaves (f32,
s32) are bitcast into the words as they are; 1- and 2-byte leaves pack
whole words, lowest bits first. ``offsets[c]`` is client c's first row;
a client with no records points at the zero row that ends the table.

A round's cohort batch is then one row gather at flat indices
``offsets[cohort] + idx``, unpacked back into the ``{name: (K, steps, B,
...)}`` dict the local training takes, bit for bit what a per-leaf
gather from client stacks padded to the largest client gives. On the
TPU a gather costs per index more than per byte, so one gather of wide
rows beats one per leaf, and no row of the table is padding.
"""
from __future__ import annotations

import math
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np


class Leaf(NamedTuple):
    """Where one leaf's per-record payload lies in a table row."""
    name: str
    dtype: np.dtype
    shape: tuple
    start: int                # first word of the row
    words: int


def _per_word(dtype) -> int:
    size = np.dtype(dtype).itemsize
    if size > 4:
        raise ValueError(f"records of {np.dtype(dtype)} do not pack into "
                         "32-bit words")
    return 4 // size


def _to_words(a: np.ndarray) -> np.ndarray:
    """(n, ...) records -> (n, words) u32, lowest bits first."""
    flat = a.reshape(len(a), -1)
    per = _per_word(a.dtype)
    if per == 1:
        return flat.view(np.uint32)
    bits = 32 // per
    u = flat.view(np.dtype(f"u{4 // per}")).astype(np.uint32)
    u = np.pad(u, ((0, 0), (0, -flat.shape[1] % per)))
    u = u.reshape(len(a), u.shape[1] // per, per) << (
        bits * np.arange(per, dtype=np.uint32))
    return np.bitwise_or.reduce(u, axis=2)


def _from_words(words, leaf: Leaf):
    """(..., leaf.words) u32 -> (...,) + leaf.shape of leaf.dtype."""
    dtype = np.dtype(leaf.dtype)
    lead = words.shape[:-1]
    per = _per_word(dtype)
    if per == 1:
        vals = jax.lax.bitcast_convert_type(words, dtype)
    else:
        bits = 32 // per
        shifts = jnp.arange(per, dtype=jnp.uint32) * bits
        u = (words[..., None] >> shifts) & jnp.uint32((1 << bits) - 1)
        u = u.reshape(lead + (-1,))[..., :math.prod(leaf.shape)]
        u = u.astype(np.dtype(f"u{4 // per}"))
        vals = (u != 0 if dtype == np.bool_
                else jax.lax.bitcast_convert_type(u, dtype))
    return vals.reshape(lead + leaf.shape)


@jax.tree_util.register_pytree_node_class
class RecordTable:
    """``table`` u32 (rows, W) and ``offsets`` s32 (N,) on the device, the
    leaf layout static (it rides the pytree structure, so a jitted
    program specializes on it). Leading axes, as a stack of seeds adds
    them, batch both arrays alike."""

    def __init__(self, table, offsets, leaves: tuple):
        self.table = table
        self.offsets = offsets
        self.leaves = leaves

    def tree_flatten(self):
        return (self.table, self.offsets), self.leaves

    @classmethod
    def tree_unflatten(cls, leaves, children):
        return cls(*children, leaves)

    def take(self, cohort, idx) -> dict:
        """The cohort's records: row ``idx[k, ...]`` of client
        ``cohort[k]``, as ``{name: idx.shape + leaf shape}``, through one
        gather of whole table rows."""
        rows = self.offsets[cohort].reshape(
            cohort.shape + (1,) * (idx.ndim - 1)) + idx
        # indexing's gather promises in-bounds rows, as the per-leaf
        # gathers did: every row lies inside its client's records
        words = self.table[rows.reshape(-1)]
        words = words.reshape(rows.shape + words.shape[-1:])
        return {leaf.name: _from_words(
                    words[..., leaf.start:leaf.start + leaf.words], leaf)
                for leaf in self.leaves}


def pack(client_arrays: Sequence[dict]) -> RecordTable:
    """Packs N clients' ``{name: (n_c, ...)}`` records (the same leaves,
    shapes and dtypes for every client) into one table, on the host, and
    puts it on the device. Leaves keep the dtype a device array of them
    has (a float64 leaf becomes f32, as ``jnp.asarray`` makes it)."""
    names = list(client_arrays[0])
    cols, leaves, start = [], [], 0
    for name in names:
        a = np.concatenate([np.asarray(c[name]) for c in client_arrays])
        a = np.ascontiguousarray(
            a, dtype=jax.dtypes.canonicalize_dtype(a.dtype))
        w = _to_words(a)
        leaves.append(Leaf(name, a.dtype, a.shape[1:], start,
                           w.shape[1]))
        cols.append(w)
        start += w.shape[1]
    table = np.concatenate(cols, axis=1)
    table = np.concatenate([table, np.zeros((1, start), np.uint32)])
    sizes = np.asarray([len(next(iter(c.values()))) for c in client_arrays])
    offsets = np.cumsum(sizes) - sizes
    offsets = np.where(sizes > 0, offsets, len(table) - 1)
    return RecordTable(jnp.asarray(table),
                       jnp.asarray(offsets, jnp.int32), tuple(leaves))


def stack(tables: Sequence[RecordTable]) -> RecordTable:
    """Stacks tables of the same leaves along a new leading axis, the
    rows padded with zero rows to the longest table (each table's
    offsets keep pointing inside its own rows)."""
    leaves = tables[0].leaves
    if any(t.leaves != leaves for t in tables):
        raise ValueError("stacked record tables must hold the same leaves")
    rows = max(t.table.shape[0] for t in tables)
    return RecordTable(
        jnp.stack([jnp.pad(t.table, ((0, rows - t.table.shape[0]), (0, 0)))
                   for t in tables]),
        jnp.stack([t.offsets for t in tables]), leaves)
