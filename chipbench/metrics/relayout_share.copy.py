"""Share of the device's busy time spent in operations other than the copy
kernels: the relayouts into and out of the ``(rows, 1, unit)`` row view,
the copy of the destination pool that aliasing without donation forces,
and the index preparation around each drain."""


def read(run):
    trace = run.trace
    if trace is None or trace.busy_s <= 0:
        return None
    return trace.nonkernel_s / trace.devices / trace.busy_s * 100.0
