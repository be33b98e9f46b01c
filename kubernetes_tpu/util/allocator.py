"""Allocator policy: the serving process owns its malloc policy.

A drain allocates on the host what it decides about: per assumed pod one
object, a ``__dict__`` of 29 entries (over pymalloc's 512 B, so glibc's),
two dict/set stores; per batch the packed numpy blocks and the dict and
list tables of a few thousand entries.  glibc, left to itself, serves a
THREAD that allocates beside another from an arena of its own, and a
scheduler's loop thread is new whenever it starts leading.  A non-main
arena grows by ``grow_heap``: one ``mprotect`` of exactly the pages the
request lacks, no padding — about one system call and one first-touch fault
per 4 KB, 3,952 of them for 10,000 assumed pods — and when the drain's pods
are deleted ``heap_trim`` hands the pages back (``MADV_DONTNEED`` past the
trim threshold of free top), so the next drain pays again.  Under a
sandboxing kernel every one of those calls is a round trip (PERF.md §6
PR 40: ``assume_pods_bulk`` read 62-72 µs a pod there against 5 µs of
statements; a process with 104 arenas).

None of that memory is ever surplus to a scheduler: the next drain needs
it again.  So the process says so, once, before its second thread exists:
**one arena** (``M_ARENA_MAX``).  Every thread then allocates from the main
heap, which grows by ``brk`` in padded steps, which the process's start-up
and warm-up have already grown and touched; in no measured run did a
drain's frees reach the kernel or its allocations come from it.  Python threads
allocate under the interpreter lock, so one malloc lock costs them nothing;
the backend's own threads share it (a cold compile was no slower for it).

Raising ``M_TRIM_THRESHOLD``, ``M_TOP_PAD`` and ``M_MMAP_THRESHOLD`` on top
was tried and read the same to the run in every cell measured (PERF.md §6
PR 40): with one arena glibc's own dynamic thresholds already keep what a
drain grew.  They are left to glibc.  One arena A CORE (13 there) was tried
too and gained nothing, with or without trimming off: only the main heap
grows in padded steps and is already grown; every other arena grows by the
page.  The price is paid where XLA computes and compiles ON the host: its
threads then queue on the one malloc lock (a compile-heavy test file took
72 s against 36 on the sandbox's CPU backend, PERF.md §6 PR 40).

glibc fixes its arena limit ONCE (``arena_get2``: the first time a thread
needs a new arena with this parameter set, or once more than eight arenas
exist) and never reads the parameter again; arenas already made stay in
use.  The package therefore engages the policy at import
(``kubernetes_tpu/__init__``), where it already sets the process's other
policy (x64, the compile cache) and before the backend brings its thread
pools up: set after them it is inert (the threads stay spread over the
arenas that exist).  glibc only, by
observation: on a libc without ``gnu_get_libc_version`` and ``mallopt``
engaging does nothing and raises nothing.  It changes no decision.

``HeapWatch`` is the counter that says the policy holds: the bytes the
process obtained from the kernel between two samples (``mallinfo2``'s
``arena`` + ``hblkhd``), sampled when the loop starts, after an iteration
that decided something but at most once in ``SAMPLE_EVERY_S``, and at
``stop()`` — never per pod, and not while the loop idles — and the three
series read on scrape.
"""

from __future__ import annotations

import ctypes
import threading
import time
from typing import Optional, Tuple

# <malloc.h>'s number for the parameter
M_ARENA_MAX = -8
# One arena: a loop thread that is new when it starts leading, the binding
# workers, the reflectors and the API client all allocate from the heap the
# process has grown already.
ARENA_MAX = 1

# The loop samples at most this often.  ``mallinfo2`` walks every arena's
# free lists under that arena's lock: 4.5-5.0 ms a call on the chip machine
# after a 10,000-pod warm-up was deleted (PERF.md §6 PR 40), during which no
# thread of a one-arena process can allocate.  A sample a chained batch was
# 2.6 % of a cross-pod window; one a second is under 0.5 % of anything, and
# the growth between two samples is the same however far apart they are.
SAMPLE_EVERY_S = 1.0

_MB = float(1 << 20)

_mu = threading.Lock()
_engaged: Optional[bool] = None  # None: not tried yet


class _Mallinfo2(ctypes.Structure):
    _fields_ = [
        (name, ctypes.c_size_t)
        for name in (
            "arena", "ordblks", "smblks", "hblks", "hblkhd",
            "usmblks", "fsmblks", "uordblks", "fordblks", "keepcost",
        )
    ]


def _lookup():
    """The process's libc if it is glibc with ``mallopt``, else None."""
    try:
        libc = ctypes.CDLL(None)
        libc.gnu_get_libc_version
        libc.mallopt
    except (OSError, AttributeError):
        return None
    return libc


def engage() -> bool:
    """Set the policy, once a process.  True if it is set."""
    global _engaged
    with _mu:
        if _engaged is None:
            libc = _lookup()
            _engaged = libc is not None and libc.mallopt(M_ARENA_MAX, ARENA_MAX) == 1
        return _engaged


def engaged() -> bool:
    return bool(_engaged)


def _system_bytes(libc) -> Optional[Tuple[int, int]]:
    """(bytes in the arenas' heaps, bytes in mapped blocks) as glibc sums
    them over every arena, or None where ``libc`` cannot say."""
    try:
        fn = libc.mallinfo2
    except AttributeError:  # glibc before 2.33, or no glibc (None)
        return None
    fn.restype = _Mallinfo2
    mi = fn()
    return mi.arena, mi.hblkhd


def _arenas(libc) -> Optional[int]:
    """Arenas glibc has made, counted in ``malloc_info``'s report."""
    try:
        libc.open_memstream.restype = ctypes.c_void_p
        buf, size = ctypes.c_char_p(), ctypes.c_size_t()
        fp = libc.open_memstream(ctypes.byref(buf), ctypes.byref(size))
        if not fp:
            return None
        rc = libc.malloc_info(0, ctypes.c_void_p(fp))
        libc.fclose(ctypes.c_void_p(fp))
        report = ctypes.string_at(buf, size.value)
        libc.free(buf)
    except AttributeError:
        return None
    return report.count(b"<heap nr=") if rc == 0 else None


class HeapWatch:
    """One serving loop's reading of the process's heap.  ``sample`` is the
    loop's (and ``start()``'s and ``stop()``'s); ``sync_registry`` is the
    scrape's."""

    def __init__(self, phases) -> None:
        self.phases = phases  # the scheduler's PhaseAccumulator
        self._libc = _lookup()
        self._last: Optional[int] = None
        self._sampled_at = 0.0

    def sample(self, due_only: bool = False) -> None:
        """Book what the process obtained from the kernel since the last
        sample as the count ``alloc.sys_grown_mb``.  The first sample (the
        loop starts) is the base; memory handed back lowers the base the
        next growth is counted from and books nothing.  ``due_only``: the
        loop's own call, skipped within ``SAMPLE_EVERY_S`` of the last."""
        now = time.monotonic()
        if due_only and now - self._sampled_at < SAMPLE_EVERY_S:
            return
        self._sampled_at = now
        got = _system_bytes(self._libc)
        if got is None:
            return
        last, self._last = self._last, got[0] + got[1]
        if last is not None and self._last > last:
            self.phases.count("alloc.sys_grown_mb", (self._last - last) / _MB)

    def sync_registry(self, prom) -> None:
        got = _system_bytes(self._libc)
        if got is not None:
            prom.malloc_system_bytes.set(got[0])
            prom.malloc_mmapped_bytes.set(got[1])
        arenas = _arenas(self._libc)
        if arenas is not None:
            prom.malloc_arenas.set(arenas)
