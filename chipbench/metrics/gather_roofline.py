"""Kernel layer: the gather-distance kernel's share of its HBM roofline.

Numerator: the bytes the counted search distances need (the program's own
counters for the traced window: ``BuildCounters.search`` plus every eval
or serving search's ``n_computed``; each distance reads one f32 row of
``d`` values) over the chip's peak HBM bandwidth.  Denominator: the device
time of the gather kernel's events in the trace.  Memory-bound.  The work
is counted from counters, not from the kernel's operands, so it reads the
same whatever implements the gather; a window with no gather kernel time
reports nothing.
"""
import peaks

KERNEL = r"gather_distance(\.\d+)?"
BYTES_PER_VALUE = 4


def read(name: str, records: dict):
    red = records.get("trace")
    if red is None or not records.get("search_dist"):
        return None
    import chipbench_trace
    seconds = chipbench_trace.kernel_seconds(red, KERNEL)
    n_bytes = records["search_dist"] * records["d"] * BYTES_PER_VALUE
    return peaks.memory_bound_share(n_bytes, seconds, records["peak"])
