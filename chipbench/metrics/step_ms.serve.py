"""Host time of ``ServeEngine.step`` (admission, the decode program and the
argmax read back to the host) per step of the window."""


def read(run):
    spans = run.spans.get("step")
    if not spans:
        return None
    return sum(spans) / len(spans) * 1e3
