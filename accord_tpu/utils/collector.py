"""The process's garbage collector, set for a store that lives.

A CommandStore in steady state keeps millions of tracked objects (55 a
resident command: 5.4 M at 98,304 resident) and replaces a twelfth of them a
round of 4,096, so CPython's rule for its oldest generation (collect it once
a quarter as many objects have grown old as it held) asks for a full
collection every third round. Each is a stall of 1.3-1.6 s on the store's
thread at that size and frees nothing: commands, their deps and the cfk
entries are acyclic and go by reference count when a wave truncates them
(0 objects collected by every full collection of a run of 73 rounds;
`tests/test_collector.py` holds that a round leaves no cyclic garbage). So a
process that serves stores asks for the oldest generation rarely, and keeps
the two young ones, which are cheap and catch what cycles a request makes,
as they are."""
from __future__ import annotations

import contextlib
import gc

# collections of the middle generation between two of the oldest: a store of
# 98,304 resident runs about 50 a round of 4,096, so one full collection in
# about 200 rounds, where CPython's own 10 (and its quarter rule) gave one
# in 3
OLD_GENERATION_EVERY = 10_000

_holders = 0
_was = None


@contextlib.contextmanager
def settled_collector():
    """From start-up to shutdown: the oldest generation is collected once
    `OLD_GENERATION_EVERY` collections of the middle one have run (and, by
    CPython's own rule, a quarter as many objects have grown old as it holds). The setting
    is the process's: the first holder makes it and the last to leave puts
    back what it found, so node servers that share a process nest."""
    global _holders, _was
    if not _holders:
        _was = gc.get_threshold()
        gc.set_threshold(_was[0], _was[1], OLD_GENERATION_EVERY)
    _holders += 1
    try:
        yield
    finally:
        _holders -= 1
        if not _holders:
            gc.set_threshold(*_was)
