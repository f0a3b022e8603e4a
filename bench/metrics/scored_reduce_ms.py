"""scored_reduce_ms (ms): device time of the score kernel
(``kernels/scored_reduce.py``) per round, from its events in the trace,
averaged over the cell's chips. The ``jnp.pad`` copy of the update plane
before it is a separate operation and is not counted."""


def read(t):
    total = 0.0
    for ops in t.ops:
        ev = t.tz.kernel_events(ops, t.plane_rows, t.n_params)
        if not ev:
            return None
        total += sum(e[1] for e in ev if t.lo <= e[0] <= t.hi)
    return total / len(t.ops) / t.rounds / 1e6
