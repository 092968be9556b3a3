"""Functions JAX traced within the window (its compile-path monitoring
events).  Every shape and batch size is served once in set-up, so a trace
here is a retrace on rebind, or a program built per request."""


def read(w):
    return w.jax_traces
