"""Multi-build layer: beam-loop trips per insertion batch in the
window's builds, ``BuildCounters.hops / batches`` as the program drains
them in each build's one host sync (read from ``obs.ESTIMATES``).  Each
batch's loop runs until its slowest (row, graph) lane has converged.
A count."""
import window_calls


def read(name: str, records: dict):
    hops = window_calls.total(records, "hops")
    batches = window_calls.total(records, "batches")
    if hops is None or not batches:
        return None
    return hops / batches
