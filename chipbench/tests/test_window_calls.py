"""CPU tests of the readers that take the window's estimate calls from the
program's own record (``repro.core.obs.ESTIMATES``): they read only the
window's calls, and nothing where the record is missing or does not add
up.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest chipbench/tests -q
"""
from __future__ import annotations

import collections
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [p for p in (BENCH, os.path.join(ROOT, "src"))
                if p not in sys.path]

import run  # noqa: E402
import window_calls  # noqa: E402
from repro.core import obs  # noqa: E402


def _call(configs, total, hops, expansions, lane_hops, batches, compiles):
    return {"configs": configs, "total": total, "hops": hops,
            "expansions": expansions, "lane_hops": lane_hops,
            "batches": batches, "compiles": compiles}


# set-up's warm-up call, then the window's two calls
WARM = _call(8, 300, 80, 8_000, 81_920, 80, 57)
WINDOW = [_call(8, 1_000, 12_000, 400_000, 900_000, 80, 0),
          _call(8, 1_000, 13_000, 420_000, 1_000_000, 80, 1)]
RECORDS = {"configs": 16, "build_dist": 2_000}


@pytest.fixture
def estimates(monkeypatch):
    log = collections.deque(maxlen=256)
    monkeypatch.setattr(obs, "ESTIMATES", log)
    return log


def test_the_window_calls_are_the_latest_that_add_up(estimates):
    estimates.extend([WARM] + WINDOW)
    assert window_calls.calls(RECORDS) == WINDOW[::-1]
    assert window_calls.total(RECORDS, "hops") == 25_000


@pytest.mark.parametrize("metric,value", [
    ("build_hops_per_batch.tune", 25_000 / 160),
    ("beam_occupancy.tune", 100.0 * 820_000 / 1_900_000),
    ("window_compiles.tune", 1)])
def test_readers_on_synthetic_calls(estimates, metric, value):
    estimates.extend([WARM] + WINDOW)
    assert run.reader(metric).read(metric, dict(RECORDS)) == \
        pytest.approx(value)


@pytest.mark.parametrize("metric", ["build_hops_per_batch.tune",
                                    "beam_occupancy.tune",
                                    "window_compiles.tune"])
@pytest.mark.parametrize("case", ["no calls", "short", "dist differs",
                                  "no records", "old program"])
def test_readers_read_nothing_without_their_input(estimates, metric, case):
    records = dict(RECORDS)
    if case != "no calls":
        estimates.extend([WARM] + WINDOW)
    if case == "short":
        records["configs"] = 32             # more than the record holds
    elif case == "dist differs":
        records["build_dist"] += 1
    elif case == "no records":
        records = {}
    elif case == "old program":             # counters it did not keep
        estimates.clear()
        estimates.extend({"configs": 8, "total": 1_000} for _ in range(2))
    assert run.reader(metric).read(metric, records) is None
