"""Time a request waited in the server's queue before its batch started,
per response, in milliseconds: the window's change in the ``queue_wait_s``
counter over its change in ``responses`` (QueryServer counters).  None for
a program without that counter."""


def read(w):
    n = w.counter_delta("responses")
    if "queue_wait_s" not in w.counters_after or n <= 0:
        return None
    return 1e3 * w.counter_delta("queue_wait_s") / n
