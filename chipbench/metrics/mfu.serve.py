"""The whole window's share of the chip's bf16 peak: operations of every
slot-token the engine processed in the window (two per weight multiplied,
and attention over the positions each sequence actually holds) over
bf16 peak x window x chips."""


def read(run):
    trace = run.trace
    flops = run.counts.get("flops", 0)
    if trace is None or not flops:
        return None
    peak = run.peaks["bf16_flops_per_s"] * trace.window_s * run.chips
    return flops / peak * 100.0
