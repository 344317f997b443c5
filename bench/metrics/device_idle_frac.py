"""device_idle_frac: the share of the traced window in which no operation
runs on a device, averaged over the cell's chips."""


def read(run):
    tr = run.trace
    if tr is None or not tr.ops:
        return None
    from xtrace import busy_ns
    w = tr.window[1] - tr.window[0]
    idle = [1.0 - busy_ns(tr, dev) / w for dev in tr.ops]
    return 100.0 * sum(idle) / len(idle)
