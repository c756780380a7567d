"""Distance-computation accounting — the paper's #dist metric.

Counts are *logical* distance computations per the paper's model (DESIGN.md
§3): ``*_base`` is what independent per-graph builds (no ESO/EPO) would
compute, the unsuffixed field is what the shared build actually computed.
Beside them, the lockstep beam's work, outside the #dist totals: ``hops``
(beam-loop trips), ``expansions`` (pool slots expanded over the live rows)
and ``lane_hops`` (hops × live rows × graphs × W, the (row, graph) lanes
the loop carried), summed over ``batches`` logged search steps;
``expansions / lane_hops`` is the share of lanes that did work.
Totals live in Python ints (unbounded); per-step counts are int32 device
scalars that builders log on a ``CounterTape`` and sync to the host ONCE at
the end of the build loop (DESIGN.md §12) — a per-batch ``int(...)`` cast
would block the host on every dispatched batch.
"""
from __future__ import annotations

import dataclasses

import jax.numpy as jnp
import numpy as np

from repro.core import obs


@dataclasses.dataclass
class BuildCounters:
    search_base: int = 0   # Search phase, independent builds
    search: int = 0        # Search phase, with ESO sharing
    prune_base: int = 0    # Prune dominance checks, independent builds
    prune: int = 0         # with EPO sharing
    init_base: int = 0     # Initialization (KNNG) distances, independent
    init: int = 0          # shared across the group
    connect: int = 0       # connectivity-repair searches (NSG)
    hops: int = 0          # beam-loop trips, summed over the search steps
    expansions: int = 0    # pool slots expanded over the live rows
    lane_hops: int = 0     # hops x live rows x graphs x W
    batches: int = 0       # search steps logged

    @property
    def total_base(self) -> int:
        return self.search_base + self.prune_base + self.init_base + self.connect

    @property
    def total(self) -> int:
        return self.search + self.prune + self.init + self.connect

    def add(self, other: "BuildCounters") -> "BuildCounters":
        return BuildCounters(**{
            f.name: getattr(self, f.name) + getattr(other, f.name)
            for f in dataclasses.fields(self)})

    def as_dict(self) -> dict:
        return dict(dataclasses.asdict(self), total_base=self.total_base,
                    total=self.total)


# Per-step counter-row layout shared by the builders and the fused batch
# step (core/build.py): one int32[7] row per search step.
TAPE_FIELDS = ("search_base", "search", "prune_base", "prune",
               "hops", "expansions", "lane_hops")


def step_row(res, row_mask, expand_width, n_prune_base=0,
             n_prune=0) -> jnp.ndarray:
    """One CounterTape row (int32[7]) from a step's ``beam_search`` result
    ``res``, its live-row mask and expansion width, and the step's prune
    counts (0 for a search-only step)."""
    _, m, ef_max = res.pool_ids.shape
    lanes = jnp.sum(row_mask).astype(jnp.int32) * (m * min(expand_width,
                                                           ef_max))
    return jnp.stack([jnp.asarray(v, jnp.int32) for v in (
        res.n_fresh, res.n_computed, n_prune_base, n_prune, res.hops,
        res.expansions, res.hops * lanes)])


class CounterTape:
    """Device-side accumulation of per-step counter rows.

    Closing every batch with ``int(res.n_fresh)`` casts would cost host
    round-trips per dispatched batch, each blocking the host until the
    device drained its queue, which serializes dispatch.  The tape instead
    *logs* each step's int32[7] counter
    row as a device array (async, never blocks) and syncs exactly once:
    ``drain_into`` stacks the rows, fetches them in one transfer, and sums
    on the host in int64 (no int32 overflow however long the build ran).

    The fused device-resident pass (core/build.py) writes its rows into a
    preallocated int32[n_steps, 7] log inside ``lax.fori_loop`` and hands
    the whole log to ``drain_into`` via ``log_many`` — same sync contract,
    zero per-batch dispatches.
    """

    def __init__(self):
        self._rows: list = []

    def log(self, row) -> None:
        """Log one int32[7] ``step_row`` (dispatched, never synced here)."""
        self._rows.append(row)

    def log_many(self, rows) -> None:
        """Log an int32[k, 7] block (a device-resident pass's whole log).
        A narrower block, one that logs only the #dist fields, fills the
        leading fields and leaves the beam's at 0."""
        rows = jnp.atleast_2d(jnp.asarray(rows))
        if rows.shape[1] < len(TAPE_FIELDS):
            rows = jnp.pad(rows, ((0, 0),
                                  (0, len(TAPE_FIELDS) - rows.shape[1])))
        self._rows.append(rows)

    def drain_into(self, ctr: BuildCounters) -> None:
        """ONE host sync: fetch every logged row, add totals into ``ctr``.
        The host waits here for the build's device work (span
        ``counters.drain``)."""
        if not self._rows:
            return
        with obs.span(obs.DRAIN):
            stacked = jnp.concatenate(
                [jnp.atleast_2d(r) for r in self._rows], axis=0)
            totals = np.asarray(stacked).astype(np.int64).sum(axis=0)
        self._rows = []
        for name, v in zip(TAPE_FIELDS, totals):
            setattr(ctr, name, getattr(ctr, name) + int(v))
        ctr.batches += stacked.shape[0]
