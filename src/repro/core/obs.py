"""Where the estimate path's time and work go: the names a profile reads.

Three kinds of measurement, all inert unless someone looks:

* **Device scopes** (``jax.named_scope``), in the traced code.  They land
  in each HLO instruction's ``op_name`` metadata and cost nothing at run
  time; a profile attributes a device op's self time to the innermost
  scope in its ``op_name``.  ``SCOPES`` is the one list of them.
* **Host spans** (``span``, a ``jax.profiler.TraceAnnotation``), at layer
  boundaries only: never inside a jitted function, never per batch or per
  hop.  They write into the profiler's own trace, on the clock its device
  events use, and do nothing when no profiler runs.  ``SPANS`` is the one
  list of them.
* **The compile counter**: a ``jax.monitoring`` listener on the backend
  compile event.  ``compiles()`` counts real XLA compilations since
  import, ``cache_hits()`` the programs loaded from the persistent
  compilation cache instead; an operator reads a difference across a step
  as "did this step recompile".

``ESTIMATES`` keeps the latest ``estimate`` calls' work (configurations,
#dist, the lockstep beam's counters, compilations), newest last, for a
caller that does not hold the records the calls returned.
"""
from __future__ import annotations

import collections

import jax

# Device scopes, in the order a beam hop runs them, then the build's
# per-batch stages.
FRONTIER = "beam/frontier"   # pick W unexpanded slots, read their rows, dedup
VISIT = "beam/visit"         # visited set / V_delta (ESO) probe and insert
DISTANCE = "beam/distance"   # row gather + gather-distance kernel
MERGE = "beam/merge"         # candidate top-k into the sorted pool
PRUNE = "prune"              # mPrune (EPO) of each batch's pools
COMMIT = "commit"            # forward + reverse edges, overflow re-prune
SCOPES = (FRONTIER, VISIT, DISTANCE, MERGE, PRUNE, COMMIT)

# Host spans.
GROUP = "estimator.group"            # one group's build + ef sweeps
VAMANA_INIT = "vamana.init"          # random KNNG, its distances, medoid
DRAIN = "counters.drain"             # the build's one counter host sync
EVAL_POINT = "eval.point"            # one ef of a configuration's sweep
RECALL_SEARCH = "eval.recall_search"
TIMED_SEARCH = "eval.timed_search"
RECOMMEND = "tuner.recommend"
ESTIMATE = "tuner.estimate"
SPANS = (GROUP, VAMANA_INIT, DRAIN, EVAL_POINT, RECALL_SEARCH, TIMED_SEARCH,
         RECOMMEND, ESTIMATE)

span = jax.profiler.TraceAnnotation

# BACKEND_COMPILE fires around every executable JAX asks the backend for,
# whether compiled or loaded from the persistent cache; a load also fires
# CACHE_HIT, so compiles are the difference.
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"
_counts = {BACKEND_COMPILE: 0, CACHE_HIT: 0}


def _on_duration(event: str, duration_secs: float, **kwargs) -> None:
    if event == BACKEND_COMPILE:
        _counts[BACKEND_COMPILE] += 1


def _on_event(event: str, **kwargs) -> None:
    if event == CACHE_HIT:
        _counts[CACHE_HIT] += 1


jax.monitoring.register_event_duration_secs_listener(_on_duration)
jax.monitoring.register_event_listener(_on_event)


def compiles() -> int:
    """Real XLA compilations since import (persistent-cache loads excluded)."""
    return _counts[BACKEND_COMPILE] - _counts[CACHE_HIT]


def cache_hits() -> int:
    """Executables loaded from the persistent compilation cache since import."""
    return _counts[CACHE_HIT]


# One dict per ``estimator.estimate`` call: ``configs``, every
# ``BuildCounters.as_dict()`` field, and ``compiles`` (XLA compilations
# during the call).
ESTIMATES: collections.deque = collections.deque(maxlen=256)
