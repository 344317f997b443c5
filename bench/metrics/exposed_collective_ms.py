"""exposed_collective_ms: the part of collective_ms during which no other
op runs on that device, per step, averaged over the cell's chips."""


def read(run):
    tr = run.trace
    if tr is None or not tr.ops:
        return None
    from xtrace import (collective_intervals, is_collective, length,
                        subtract, union)
    tot, seen = 0, False
    for dev in tr.ops:
        coll = collective_intervals(tr, dev)
        other = union((s, e) for s, e, name, _ in tr.clipped(dev)
                      if not is_collective(name))
        seen = seen or bool(coll)
        tot += length(subtract(coll, other))
    if not seen:
        return None
    return tot * 1e-6 / len(tr.ops) / tr.steps
