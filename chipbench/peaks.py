"""Peak rates of a chip, keyed by the ``device_kind`` JAX reports.

A device that is not in ``peaks.json`` is an error, never a default.
"""
from __future__ import annotations

import json
import os

PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "peaks.json")


def peaks(device_kind: str, path: str = PEAKS_FILE) -> dict:
    with open(path) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} has no entry in "
                       f"{os.path.basename(path)} (known: {sorted(table)})")
    return table[device_kind]


def memory_bound_share(n_bytes: float, seconds: float, peak: dict) -> float | None:
    """Roofline share, in %, of work bound by HBM bandwidth: the least
    time the bytes need at the peak rate over the time taken.  None where
    no time was spent, so that a silent kernel reports nothing."""
    if seconds <= 0 or n_bytes <= 0:
        return None
    return 100.0 * (n_bytes / peak["hbm_bytes_per_s"]) / seconds
