"""The program's own record of the estimate calls a window made.

``repro.core.obs.ESTIMATES`` keeps one entry per ``estimator.estimate``
call, newest last.  The window's calls are the latest ones: the warm-up
call of set-up comes before them, and nothing calls ``estimate`` after
the window.  They are taken from the end until they hold as many
configurations as the window's records count, and kept only if their
#dist adds up to the window's ``build_dist`` as well, so that a call
from outside the window can never be read as one of its own.
"""
from __future__ import annotations


def calls(records: dict) -> list[dict] | None:
    """The window's entries of ``obs.ESTIMATES``, else None (a program
    that keeps no such record, or records that do not add up)."""
    try:
        from repro.core import obs
    except ImportError:
        return None
    want = records.get("configs")
    if not want or "build_dist" not in records:
        return None
    got, configs = [], 0
    for entry in reversed(obs.ESTIMATES):
        if configs >= want:
            break
        got.append(entry)
        configs += entry["configs"]
    if configs != want or sum(e["total"] for e in got) != \
            records["build_dist"]:
        return None
    return got


def total(records: dict, field: str) -> int | None:
    """``field`` summed over the window's calls, else None."""
    got = calls(records)
    if got is None or any(field not in e for e in got):
        return None
    return sum(e[field] for e in got)
