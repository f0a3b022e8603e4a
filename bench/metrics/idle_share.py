"""idle_share (%): the share of the traced window in which no operation ran
on the device, 1 - (union of the XLA Ops intervals) / window, averaged over
the cell's chips."""


def read(t):
    span = t.hi - t.lo
    if span <= 0 or not any(t.ops):
        return None
    busy = sum(t.tz.busy_ns(o, t.lo, t.hi) for o in t.ops) / len(t.ops)
    return 100.0 * (1.0 - busy / span)
