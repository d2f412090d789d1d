"""The whole window's share of the chip's peak, read against HBM bandwidth
because this path's work is bytes: needed bytes of every chain of the
window (payload read once and written once) over HBM peak x window x
chips. It counts the same bytes as ``descriptor_copy_roofline`` whatever
implements the copy."""


def read(run):
    trace = run.trace
    needed = run.counts.get("needed_bytes", 0)
    if trace is None or not needed:
        return None
    peak = run.peaks["hbm_bytes_per_s"] * trace.window_s * run.chips
    return needed / peak * 100.0
