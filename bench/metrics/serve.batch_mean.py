"""Requests per micro-batch the server ran within the window: the window's
change in ``responses`` over its change in ``batches`` (QueryServer
counters)."""


def read(w):
    batches = w.counter_delta("batches")
    if batches <= 0:
        return None
    return w.counter_delta("responses") / batches
