"""Host memory for the executor's flush outputs, handed out again.

A flush's logits are large (a 256,000-token vocabulary makes 32.8 MB a
32-token request).  A fresh ``np.zeros`` of that size is mapped lazily:
the first write faults its pages in one at a time, and freeing it unmaps
them, so the next flush faults them in again.  :class:`HostOutputs` keeps
the mapping of an output that nothing refers to any more and hands it out
for the next one, whose writes then land in resident pages.

A buffer counts as free when CPython's reference count on its base array
says that only the pool holds it: every view of an output (``out[1]``,
a reshape, a ``memoryview``) keeps that base alive.
"""
from __future__ import annotations

import math
import mmap
import sys
import threading

import numpy as np


class _Buffer:
    """One anonymous mapping and the byte array every output view of it
    hangs from."""

    __slots__ = ("mem", "base", "used", "resident", "_own")

    def __init__(self, nbytes: int):
        # private: mmap's default shared anonymous mapping is shared memory,
        # slower to fault in, and MADV_DONTNEED would not free its pages
        self.mem = mmap.mmap(-1, nbytes, flags=mmap.MAP_PRIVATE)
        self.base = np.frombuffer(self.mem, np.uint8)
        #: bytes of the output handed out last, and the most of the mapping
        #: that may hold pages (everything below it was written at least
        #: once since the mapping was made or last trimmed)
        self.used = self.resident = 0
        #: the reference count that means "held by the pool alone"
        self._own = sys.getrefcount(self.base)

    @property
    def free(self) -> bool:
        return sys.getrefcount(self.base) <= self._own

    def trim(self) -> None:
        """Release the pages past the output that still holds this buffer,
        so a live output keeps no more than its own bytes."""
        start = -(-self.used // mmap.PAGESIZE) * mmap.PAGESIZE
        if self.resident > start:
            self.mem.madvise(mmap.MADV_DONTNEED, start, len(self.mem) - start)
            self.resident = start


class HostOutputs:
    """A pool of host output buffers for one executor.

    :meth:`take` returns an uninitialised, writable array: the caller
    writes every element.  A buffer is handed out again only once nothing
    refers to the last output taken from it or to any view of that output.
    A free buffer serves any output that fits; a new one is mapped as large
    as the largest output asked for so far, so that after the first few
    flushes one free buffer serves every size.  Pages are touched only by
    writes, and the pages past an output that stays alive beyond the next
    :meth:`take` are released, so a live output holds its own bytes
    (rounded up to a page).  At most one free buffer is kept, the largest.
    """

    def __init__(self):
        self._lock = threading.Lock()
        self._bufs: list[_Buffer] = []
        self._largest = 0
        #: outputs handed out, and how many of them reused a buffer
        self.taken = self.reused = 0

    def take(self, shape: tuple[int, ...],
             dtype=np.float32) -> tuple[np.ndarray, bool]:
        """An uninitialised ``shape`` array of ``dtype``, and whether its
        memory came from an earlier output."""
        dtype = np.dtype(dtype)
        nbytes = math.prod(shape) * dtype.itemsize
        with self._lock:
            free = []
            for b in self._bufs:
                if b.free:
                    free.append(b)
                else:
                    b.trim()
            fits = [b for b in free if len(b.mem) >= nbytes]
            buf = min(fits, key=lambda b: len(b.mem), default=None)
            reused = buf is not None
            if buf is None:
                self._largest = max(self._largest, nbytes)
                buf = _Buffer(self._largest)
                self._bufs.append(buf)
            spare = max((b for b in free if b is not buf),
                        key=lambda b: len(b.mem), default=None)
            self._bufs = [b for b in self._bufs
                          if b is buf or b is spare or b not in free]
            buf.used = nbytes
            buf.resident = max(buf.resident, nbytes)
            self.taken += 1
            self.reused += reused
            out = buf.base[:nbytes].view(dtype).reshape(shape)
        return out, reused
