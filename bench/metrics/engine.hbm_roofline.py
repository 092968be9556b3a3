"""The engine's share of the HBM roofline: the bytes the answered requests
had to read at the least (each query's ``required_bytes``: its columns, once)
over what the chip's HBM could have moved in the device's busy time.  Read
only in unbatched cells, where each request reads its columns for itself."""


def read(w):
    if w.trace is None or w.trace.busy_s <= 0 or not w.completions:
        return None
    need = sum(w.queries[c.qname].required_bytes(w.scale_factor) for c in w.completions)
    return 100.0 * need / (float(w.peaks["hbm_bytes_per_s"]) * w.trace.busy_s)
