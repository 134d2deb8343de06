"""Model-sized host arrays, kept from round to round.

New pages are what a pass over a model costs on a TPU host: a copy of
1.45 GB takes 1.6 s into fresh memory and a twentieth of that into memory
the process has touched before, and ``free`` gives the pages back (PERF.md,
PR 25). So whoever writes a round's arrays -- the loopback world its means
and copies, the device outer plane its assembled pseudo-gradient -- takes
them from an ``OutputPool`` and lets go of them by dropping its references.
"""

from __future__ import annotations

import sys
from typing import Optional

import numpy as np


class OutputPool:
    """The rounds' output arrays, kept across rounds.

    The pool keeps the arrays it has handed out and hands one out again once
    nothing else refers to it: not its caller, not a view of it, not a
    transfer still reading it -- each of those holds a reference, which is
    what ``sys.getrefcount`` counts. An array somebody still holds is never
    reused, so a result stays its caller's for as long as the caller keeps
    it. Not thread-safe: callers hold their owner's lock.
    """

    # references to a kept array that nobody else holds: the list's, the
    # loop variable's in ``take``, and getrefcount's own argument
    _FREE = 3

    def __init__(self, keep: int):
        self.keep = keep  # arrays remembered per position, shape and layout
        self.new_bytes = 0  # bytes of every array allocated so far
        self._arrays: dict[tuple, list[np.ndarray]] = {}

    def take(
        self,
        i,
        like: np.ndarray,
        shape: Optional[tuple] = None,
        dtype=np.float32,
    ) -> np.ndarray:
        """An array of ``dtype`` in ``like``'s memory order for position ``i``
        of a round, its contents undefined. Of ``like``'s shape, or of
        ``shape`` (same rank) when ``like`` is one part of the whole. ``i`` is
        whatever names the position from round to round (hashable): a leaf's
        index in the plane, a round's tag with an array's index in its call."""
        shape = like.shape if shape is None else tuple(shape)
        kept = self._arrays.setdefault(
            (i, shape, like.strides, np.dtype(dtype)), []
        )
        for a in kept:
            if sys.getrefcount(a) == self._FREE:
                return a
        a = np.empty_like(like, dtype=dtype, shape=shape)
        self.new_bytes += a.nbytes
        kept.append(a)
        del kept[: -self.keep]  # forget the oldest: its holder keeps it
        return a
