"""Host time in ``DMARuntime.submit`` (coalesce, translation plan, ring
push) per descriptor submitted, from the benchmark's span around each call."""


def read(run):
    n = run.counts.get("descriptors")
    spans = run.spans.get("submit")
    if not n or not spans:
        return None
    return sum(spans) / n * 1e6
