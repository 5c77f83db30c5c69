"""One host-to-device transfer a dispatch.

A round's step arrays (tokens, positions, block tables, slots, the cache's
row extras, the sampling arrays) are a dozen numpy arrays of a few KB.  A
transfer costs the runtime the same few tenths of a millisecond whatever
its size, and the engine's serial round pays each of them with the device
idle (PERF.md, PRs 25 and 32).  So the arrays cross in ONE ``int32``
buffer: :meth:`RoundLayout.pack` lays them end to end on the host, and the
jitted program (:meth:`RoundLayout.program`) takes the buffer as its last
operand and cuts it back into the operands its function takes, by static
slices: the math after the cut is what it was.

A layout belongs to one step program (its kind, greedy or sampled): the
shapes and dtypes of the arrays of its first round, in order.  Every later
round is checked against it; one that differs raises, since the compiled
program's slices are the first round's.  ``float32`` arrays (temperature,
top_p) ride as their bit patterns, so a sampled round is bit-exact too.
"""

from __future__ import annotations

import math

import numpy as np

_WORD = np.dtype(np.int32)


class RoundLayout:
    """Where each of a step program's host arrays lies in its one buffer."""

    __slots__ = ("fields", "size")

    def __init__(self, like=()):
        # (shape, dtype, start, stop) an array, in words of the buffer;
        # None until the program's first round (or ``like``) says
        self.fields: tuple | None = None
        self.size = 0
        if like:
            self._fix(like)

    def _fix(self, arrays) -> tuple:
        fields, at = [], 0
        for a in arrays:
            shape, dtype = tuple(a.shape), np.dtype(a.dtype)
            if dtype.itemsize != _WORD.itemsize:
                raise TypeError(
                    f"a step array of dtype {dtype} does not ride an int32 "
                    "buffer word for word")
            n = math.prod(shape)
            fields.append((shape, dtype, at, at + n))
            at += n
        self.fields, self.size = tuple(fields), at
        return self.fields

    def pack(self, arrays) -> np.ndarray:
        """The arrays end to end in a FRESH ``int32`` buffer: a transfer
        still pending, or a CPU backend's zero-copy array, may read the
        last round's.  The first call fixes the layout."""
        fields = self.fields if self.fields is not None \
            else self._fix(arrays)
        if len(arrays) != len(fields) or any(
                a.shape != shape or a.dtype != dtype
                for a, (shape, dtype, _s, _e) in zip(arrays, fields)):
            raise ValueError(
                "a round's step arrays depart from the program's layout: "
                f"{[(a.shape, str(a.dtype)) for a in arrays]} against "
                f"{[(shape, str(dtype)) for shape, dtype, _s, _e in fields]}")
        return np.concatenate([a.reshape(-1).view(_WORD) for a in arrays])

    def unpack(self, packed) -> tuple:
        """The program's side: the buffer cut back into its arrays."""
        from jax import lax

        if self.fields is None:
            raise RuntimeError("a packed program traced before its first "
                               "round was packed: no layout yet")
        if packed.shape != (self.size,):
            raise ValueError(f"a buffer of {packed.shape} words for a "
                             f"layout of {self.size}")
        out = []
        for shape, dtype, start, stop in self.fields:
            flat = lax.slice(packed, (start,), (stop,))
            if dtype != _WORD:
                flat = lax.bitcast_convert_type(flat, dtype)
            out.append(flat.reshape(shape))
        return tuple(out)

    def program(self, fn):
        """``fn(params, *cache arrays, *host arrays)`` as a function of
        ``(params, *cache arrays, packed)``.  It keeps ``fn``'s name: the
        device trace calls the jitted module by it (``jit__mixed_fn``),
        and the benchmark's readers find the step programs so."""
        def packed_fn(params, *operands):
            *state, packed = operands
            return fn(params, *state, *self.unpack(packed))

        packed_fn.__name__ = fn.__name__
        packed_fn.__qualname__ = fn.__qualname__
        return packed_fn
