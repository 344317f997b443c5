"""collective_ms: device time per step in which an all-gather,
reduce-scatter, all-reduce, all-to-all or collective-permute runs or is in
flight (an asynchronous one from its start to its done), averaged over the
cell's chips."""


def read(run):
    tr = run.trace
    if tr is None or not tr.ops:
        return None
    from xtrace import collective_intervals, length
    tot = sum(length(collective_intervals(tr, dev)) for dev in tr.ops)
    if not tot:
        return None
    return tot * 1e-6 / len(tr.ops) / tr.steps
