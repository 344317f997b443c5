"""host_loop_ms: the training loop's host time per traced step inside the
program's ``train`` step span and outside its ``train/sync`` phase (the
wait for the device), averaged over the traced steps: the host's own work
on the step's critical path.  None where the program emits no step spans."""


def read(run):
    import loopspans
    loop = loopspans.for_run(run)
    if loop is None or not loop.steps:
        return None
    return loopspans.host_loop_ns(loop) * 1e-6 / len(loop.steps)
