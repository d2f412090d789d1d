"""The descriptor-copy Pallas kernels' share of the HBM roofline.

Needed bytes (every payload byte read once and written once) over the HBM
peak is the least time the copy could take; the kernels' time is the summed
device time of the ``tpu_custom_call`` operations inside the
``descriptor_copy`` programs of the traced window (``descriptor_copy`` and
``descriptor_copy_bucketed`` both run there).
"""


def read(run):
    trace = run.trace
    if trace is None:
        return None
    kernel_s = trace.kernel_s.get("descriptor_copy", 0.0)
    needed = run.counts.get("needed_bytes", 0)
    if kernel_s <= 0 or not needed:
        return None
    return needed / run.peaks["hbm_bytes_per_s"] / kernel_s * 100.0
