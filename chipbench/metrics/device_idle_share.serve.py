"""Share of the traced window in which no program ran on the device
(1 - union of program intervals / window), as a mean over the devices."""


def read(run):
    trace = run.trace
    if trace is None or trace.window_s <= 0:
        return None
    return trace.idle_share * 100.0
