"""Device-resident embedding store: vectors live in HBM from the encoder's
forward pass to the index matmul, and never round-trip through the host.

Why this exists: the index matmul runs where the encoder ran, so a
device->host fetch of every embedding, and a host->device upload of the
index, would be pure overhead, and a synchronizing fetch per batch would
stall the pipelined encoder dispatches.  The reference architecture
(embedder service returns vectors to the host, host pushes them into the
index — xpacks/llm/embedders.py + brute_force_knn_integration.rs) does both;
here ingest keeps embeddings on device and the host sees only token ids and
top-k results.

`DeviceVecStore` accumulates the encoder's output batches (each a (B, d)
jax array) without synchronizing.  `DeviceVec` is the per-row handle that
flows through the engine as an ordinary column value — tiny on host, with
lazy `__array__` materialization for any consumer that truly needs numbers.
The KNN index consolidates referenced rows into one (N, d) device matrix
with a single gather dispatch (ops/knn.py searches it in-place).
"""

from __future__ import annotations

import itertools
from typing import Any

import numpy as np

_store_ids = itertools.count()


def _write_fn():
    """Jitted fixed-shape writer: one compile per (buffer, batch) shape
    pair — the batch shapes are already bucketed by the encoder, so the
    compile set is tiny and ingest never recompiles at steady state."""
    import jax

    @jax.jit
    def write(buf, arr, start):
        return jax.lax.dynamic_update_slice(
            buf, arr.astype(buf.dtype), (start, 0))

    return write


_write = None


class DeviceVecStore:
    """Append-only pool of device-resident embedding rows.

    Rows live in preallocated fixed-capacity `(BUF_ROWS, d)` HBM buffers
    written with `lax.dynamic_update_slice` — every XLA computation in
    the ingest path has a STATIC shape, so nothing recompiles as the
    corpus grows (the previous design concatenated a growing batch list,
    which changed the gather's input arity on every ingest batch and
    paid ~1s of XLA compile each time).  A new buffer is allocated every
    BUF_ROWS rows; a batch that does not fit the current buffer starts
    the next one (the gap is never referenced)."""

    BUF_ROWS = 8192

    def __init__(self, dimensions: int | None = None):
        self.id = next(_store_ids)
        self.dim = dimensions
        self._buffers: list[Any] = []   # jax arrays, (BUF_ROWS, d) f32
        self._fill = 0                  # rows used in the LAST buffer

    def _ensure_space(self, n: int) -> None:
        import jax.numpy as jnp

        if not self._buffers or self._fill + n > self.BUF_ROWS:
            self._buffers.append(
                jnp.zeros((self.BUF_ROWS, self.dim), jnp.float32))
            self._fill = 0

    def append_batch(self, dev_arr, n_valid: int | None = None) -> list["DeviceVec"]:
        """Register one encoder output batch (no sync, no fetch); returns a
        handle per valid row."""
        global _write
        if self.dim is None:
            self.dim = int(dev_arr.shape[1])
        n_rows = int(dev_arr.shape[0])
        n = n_rows if n_valid is None else n_valid
        if n_rows > self.BUF_ROWS:
            raise ValueError(
                f"batch of {n_rows} rows exceeds DeviceVecStore buffer "
                f"capacity {self.BUF_ROWS}"
            )
        self._ensure_space(n_rows)
        if _write is None:
            _write = _write_fn()
        bid = len(self._buffers) - 1
        start = self._fill
        self._buffers[bid] = _write(self._buffers[bid], dev_arr, start)
        self._fill += n_rows
        return [DeviceVec(self, bid, start + r) for r in range(n)]

    def n_batches(self) -> int:
        return len(self._buffers)

    def gather(self, refs: list[tuple[int, int]], pad_to: int | None = None):
        """One (N, d) device array holding the given (buffer, row) refs in
        a single take dispatch (zero-copy single-buffer fast path; the
        multi-buffer concat changes shape only once per BUF_ROWS rows).
        `pad_to` pads the output with zero rows to a bucketed size so the
        downstream matmul/top-k shapes stay static as the index grows."""
        import jax.numpy as jnp

        if not refs and (pad_to is None or not self._buffers):
            # empty store: honor pad_to with a zero-fill instead of
            # indexing _buffers[0] (advisor r3); pad_to=0 is treated like
            # None rather than conflated with it
            n_pad = pad_to or 0
            return jnp.zeros((n_pad, self.dim or 0), jnp.float32)
        full = (self._buffers[0] if len(self._buffers) == 1
                else jnp.concatenate(self._buffers, axis=0))
        flat = np.fromiter(
            (bid * self.BUF_ROWS + row for bid, row in refs),
            dtype=np.int32, count=len(refs),
        )
        if pad_to is not None and pad_to > len(refs):
            # padding gathers buffer row 0 (cheap); consumers mask by
            # n_valid, so the content never surfaces
            flat = np.concatenate(
                [flat, np.zeros(pad_to - len(refs), np.int32)])
        return jnp.take(full, jnp.asarray(flat), axis=0)

    def row(self, batch: int, r: int) -> np.ndarray:
        """Host materialization of one row (the slow path — serving and
        ingest never call this; debug/pickle/compat consumers may)."""
        return np.asarray(self._buffers[batch][r], dtype=np.float32)


class DeviceVec:
    """Handle to one device-resident embedding row.

    Behaves as a value in the engine: equality/hash follow the (store,
    batch, row) identity, which is stable for the lifetime of the run;
    pickling materializes to numpy so snapshots stay self-contained.
    """

    __slots__ = ("store", "batch", "row_idx")

    def __init__(self, store: DeviceVecStore, batch: int, row_idx: int):
        self.store = store
        self.batch = batch
        self.row_idx = row_idx

    # -- engine value semantics -------------------------------------------
    def __eq__(self, other):
        if isinstance(other, DeviceVec):
            return (self.store.id, self.batch, self.row_idx) == (
                other.store.id, other.batch, other.row_idx
            )
        return NotImplemented

    def __hash__(self):
        return hash(("DeviceVec", self.store.id, self.batch, self.row_idx))

    def __repr__(self):
        return f"DeviceVec(store={self.store.id}, batch={self.batch}, row={self.row_idx})"

    # -- lazy host materialization ----------------------------------------
    def __array__(self, dtype=None, copy=None):
        arr = self.store.row(self.batch, self.row_idx)
        return arr.astype(dtype) if dtype is not None else arr

    def to_numpy(self) -> np.ndarray:
        return self.store.row(self.batch, self.row_idx)

    def __reduce__(self):
        # snapshots/pickles carry the numbers, not the handle
        return (np.asarray, (self.to_numpy(),))
