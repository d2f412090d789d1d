"""Host time in ``DMARuntime.drain_until_idle`` per round (channel drains,
lowered chains, kernel enqueue), taken before ``block_until_ready``."""


def read(run):
    spans = run.spans.get("drain")
    if not spans:
        return None
    return sum(spans) / len(spans) * 1e3
