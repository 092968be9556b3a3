"""Host-to-device transfer time per answered request, in milliseconds: the
chunks the streamed executor uploads."""


def read(w):
    if w.trace is None or not w.completions:
        return None
    s = w.trace.h2d_s()
    if s is None:
        return None
    return 1e3 * s / len(w.completions)
