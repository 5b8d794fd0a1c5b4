"""The per-slot closed-loop step's outputs, packed into one slab per dtype.

The runtime allocates one device buffer per output array of a call, every
call.  On a TPU v5e each allocation costs 34-136 us, so the step's 27
per-UE output leaves alone cost about 1.2 ms a slot.  The compiled
per-slot step (``pipeline._closed_slot_step``) therefore returns them
packed: every leaf of its output dict, flattened to ``(U, columns)``,
concatenated into one UE-major slab per dtype (``pack``).  The values are
those of the leaves, bit for bit.

``SlotOutputs`` is what the host gets back: a mutable mapping with the
nested-dict interface of the step's output (``out["mcs"]``,
``out["kpms"]["oai"]["snr"]``, item assignment, ``dict(out, ...)``), and a
pytree whose leaves are the slabs (``block_until_ready``, ``device_get``,
``jax.tree.map`` over the UE axis).  The layout is static pytree data, so
building a view issues no device operation.  The first read unpacks every
field in one jitted call and keeps them; a pytree flatten after a field was
replaced packs the fields again.

The scan paths keep their dict trajectories: a scan allocates its stacked
outputs once per campaign, not once per slot.
"""

from __future__ import annotations

from collections.abc import MutableMapping
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np


class Field(NamedTuple):
    """One output leaf's place: the dict path, its slab, its first column
    and its trailing shape (after the leading UE axis)."""

    path: tuple
    slab: int
    start: int
    shape: tuple


class Layout(NamedTuple):
    """Static description of the slabs: their dtypes and every field."""

    dtypes: tuple
    fields: tuple


def _flat(tree) -> list:
    """``[(path, leaf)]`` of a nested dict, paths as tuples of keys."""
    pairs, _ = jax.tree_util.tree_flatten_with_path(tree)
    return [(tuple(k.key for k in path), leaf) for path, leaf in pairs]


def _nest(pairs) -> dict:
    """The nested dict of ``[(path, value)]``."""
    tree = {}
    for path, value in pairs:
        node = tree
        for k in path[:-1]:
            node = node.setdefault(k, {})
        node[path[-1]] = value
    return tree


def pack(tree) -> tuple[tuple, Layout]:
    """``(slabs, layout)`` of a nested dict of ``(U, ...)`` arrays: one
    ``(U, columns)`` slab per dtype, in the order the dtypes first appear
    among the sorted paths."""
    pairs = _flat(tree)
    n_ues = pairs[0][1].shape[0]
    dtypes, cols, fields = [], [], []
    for path, leaf in pairs:
        if leaf.shape[:1] != (n_ues,):
            raise ValueError(f"{path}: {leaf.shape} has no leading UE axis "
                             f"of {n_ues}")
        dt = jnp.dtype(leaf.dtype).name
        if dt not in dtypes:
            dtypes.append(dt)
            cols.append([])
        i = dtypes.index(dt)
        start = sum(c.shape[1] for c in cols[i])
        cols[i].append(jnp.reshape(leaf, (n_ues, -1)))
        fields.append(Field(path, i, start, tuple(leaf.shape[1:])))
    slabs = tuple(jnp.concatenate(c, axis=1) for c in cols)
    return slabs, Layout(tuple(dtypes), tuple(fields))


def unpack(slabs, layout: Layout) -> tuple:
    """Every field's array, in ``layout.fields`` order.  The slabs may
    carry leading axes before the UE axis (a stack of slots)."""
    out = []
    for f in layout.fields:
        slab = slabs[f.slab]
        cols = slab[..., f.start:f.start + int(np.prod(f.shape))]
        out.append(cols.reshape(slab.shape[:-1] + f.shape))
    return tuple(out)


_unpack_on_device = jax.jit(unpack, static_argnums=1)


@jax.tree_util.register_pytree_node_class
class SlotOutputs(MutableMapping):
    """The step's output dict as a view of its slabs (see the module)."""

    def __init__(self, slabs, layout: Layout):
        self.slabs = tuple(slabs)
        self.layout = layout
        self._read = None  # the unpacked fields, once read
        self._tree = None  # the nested dict handed out to readers

    def _fields(self) -> dict:
        if self._tree is None:
            on_host = all(isinstance(s, np.ndarray) for s in self.slabs)
            read = (unpack if on_host else _unpack_on_device)(
                self.slabs, self.layout)
            self._read = read
            self._tree = _nest(
                (f.path, a) for f, a in zip(self.layout.fields, read))
        return self._tree

    def _sync(self):
        """Repack the slabs if a field was replaced since they were read."""
        if self._tree is None:
            return
        now = _flat(self._tree)
        paths = tuple(f.path for f in self.layout.fields)
        if (tuple(p for p, _ in now) == paths
                and all(a is b for (_, a), b in zip(now, self._read))):
            return
        self.slabs, self.layout = pack(self._tree)
        self._read = tuple(a for _, a in now)

    def to_dict(self) -> dict:
        """A plain nested dict of the fields (new containers)."""
        return _nest(_flat(self._fields()))

    def __getitem__(self, key):
        return self._fields()[key]

    def __setitem__(self, key, value):
        self._fields()[key] = value

    def __delitem__(self, key):
        del self._fields()[key]

    def __iter__(self):
        if self._tree is not None:
            return iter(self._tree)
        return iter(dict.fromkeys(f.path[0] for f in self.layout.fields))

    def __len__(self):
        return sum(1 for _ in self)

    def __repr__(self):
        shapes = ", ".join(f"{d}{list(np.shape(s))}"
                           for d, s in zip(self.layout.dtypes, self.slabs))
        return f"SlotOutputs({shapes}; {len(self.layout.fields)} fields)"

    def tree_flatten(self):
        self._sync()
        return self.slabs, self.layout

    @classmethod
    def tree_unflatten(cls, layout, slabs):
        return cls(slabs, layout)


def stack(views) -> dict:
    """The per-slot views of a campaign as one dict trajectory, every leaf
    ``(n_slots, U, ...)``: what the scan path returns."""
    stacked = jax.tree.map(lambda *s: jnp.stack(s, 0), *views)
    return stacked.to_dict()
