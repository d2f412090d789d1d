"""Host time in ``DMARuntime.poll`` (completion records and callbacks) per
chain completed, from the benchmark's span around each poll."""


def read(run):
    n = run.counts.get("chains")
    spans = run.spans.get("poll")
    if not n or not spans:
        return None
    return sum(spans) / n * 1e6
