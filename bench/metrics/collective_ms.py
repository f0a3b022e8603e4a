"""collective_ms (ms): own device time a round of the collective operations
between chips, averaged over the cell's chips. A collective is an operation
whose short HLO name is ``all-reduce``, ``all-gather``, ``reduce-scatter``,
``collective-permute`` or ``all-to-all``, or the ``-start`` or ``-done`` half
of one; its own time leaves out the operations nested in it. An asynchronous
collective's transfer between its halves is not counted."""
import re

COLLECTIVE = re.compile(r"^(all-reduce|all-gather|reduce-scatter|"
                        r"collective-permute|all-to-all)(-start|-done)?"
                        r"(\.\d+)*$")


def read(t):
    total, found = 0.0, False
    for ops in t.ops:
        for name, ns in t.tz.own_times(ops, t.lo, t.hi).items():
            if COLLECTIVE.match(name):
                total += ns
                found = True
    if not found or t.rounds <= 0:
        return None
    return total / len(t.ops) / t.rounds / 1e6
