"""Multi-build layer: the share, in %, of the lockstep beam's (row,
graph) lanes that did work in the window's builds, ``100 *
BuildCounters.expansions / lane_hops``: pool slots expanded over hops x
live rows x graphs x W (read from ``obs.ESTIMATES``)."""
import window_calls


def read(name: str, records: dict):
    done = window_calls.total(records, "expansions")
    lanes = window_calls.total(records, "lane_hops")
    if done is None or not lanes:
        return None
    return 100.0 * done / lanes
