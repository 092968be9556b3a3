"""Host time per answered request spent turning the device's results into
the answers' Python dicts (device-to-host copy included), in milliseconds:
the union of the server's ``serve.convert`` spans within the window, on the
client loop's thread that calls ``step()``.  None for a program without
that span."""


def read(w):
    if w.trace is None or not w.completions:
        return None
    try:
        from repro.exec.engine import SPAN_CONVERT
    except ImportError:
        return None
    from bench.trace_reduce import union

    lo, hi = w.trace.window
    spans = [(max(a, lo), min(b, hi)) for a, b, n in w.trace.client if n == SPAN_CONVERT and b > lo and a < hi]
    if not spans:
        return None
    return sum(b - a for a, b in union(spans)) / 1e6 / len(w.completions)
