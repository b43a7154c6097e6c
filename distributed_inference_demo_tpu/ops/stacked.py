"""One layer OF a stack, addressed in place.

The decoder scans one block over stacked layers.  What the scan slices
out of a stack for a custom call (a Pallas kernel) or a scatter is a copy
of it in HBM, every layer call: 128 MiB a projection for olmoe's expert
stacks (27 % of the device's busy time, my chip run, PR 28), a layer's
whole K and V plane of the page pool out and back (41-65 % of it, PR 25
and PR 28).  So what is large and consumed by such an op goes to the
layer whole, beside the layer's index, and the consumer addresses
``(layer, ...)`` in it: the expert stacks (``ops.grouped_matmul``) and the
page pool (``ops.paged_attention``) both arrive as a :class:`LayerOf`.
"""

from __future__ import annotations

from typing import Any, NamedTuple

import jax


class LayerOf(NamedTuple):
    """One layer of a stack, not sliced out of it: ``stack`` has a leading
    layer axis (``[L, E, k, n]`` expert matrices, ``[L, N, H, bt, D]``
    pages; a quantized stack's leaves all do) and ``layer`` is a traced
    int32 scalar."""

    stack: Any
    layer: jax.Array

    @property
    def shape(self):
        return self.stack.shape[1:]

    def sliced(self):
        """The layer as a tree of its own (a copy, where it is large)."""
        return jax.tree.map(
            lambda a: jax.lax.dynamic_index_in_dim(a, self.layer, 0,
                                                   keepdims=False),
            self.stack)

    def updated(self, plane):
        """The stack with this layer replaced by ``plane``: the other
        half of :meth:`sliced`, for a consumer that took the slice."""
        return jax.tree.map(
            lambda a, p: jax.lax.dynamic_update_index_in_dim(
                a, p, self.layer, 0),
            self.stack, plane)
