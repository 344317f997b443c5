"""host_bound_idle_ms: per traced step, the device's idle time (on the
host's clock, each chip's offset applied) that overlaps the program's
``train`` step span outside its ``train/sync`` phase, averaged over the
cell's chips: the part of the idle gap the loop's own host work leaves
the chip idle for.  The rest of the idle time falls in ``train/sync``
(launch and transfer latency) or between step spans.  None where the
program emits no step spans or a chip's clock offset cannot be bounded."""


def read(run):
    import loopspans
    loop = loopspans.for_run(run)
    if loop is None or not loop.steps or not run.trace.ops:
        return None
    return read_from(run, loop)


def read_from(run, loop):
    import loopspans
    per_chip = []
    for dev in run.trace.ops:
        split = loopspans.idle_split(run.trace, loop, dev)
        if split is None:
            return None
        per_chip.append(sum(v for k, v in split.items()
                            if k not in (loopspans.SYNC, "between")))
    return sum(per_chip) * 1e-6 / len(per_chip) / len(loop.steps)
