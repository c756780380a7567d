"""Where one profiled stretch of the estimate path spent the device's time,
by the program's own names (``repro.core.obs``).

  PYTHONPATH=src python tools/scope_report.py <trace dir or .xplane.pb>

Reads a ``jax.profiler`` trace and prints one JSON object:

* ``scope_s``: device self time per named scope, split into ``build``
  (the fused build's compiled modules: ``fused_vamana_pass``,
  ``insert_batch``, ``nsg_insert_batch``) and ``eval`` (every other
  module); ``""`` is the time under no scope.  An op's scope is the
  innermost of ``obs.SCOPES`` in its HLO ``op_name``, read once per
  distinct (program, instruction) from the optimized HLO the trace's
  ``/host:metadata`` plane carries; its module is the ``XLA Modules`` run
  around it.
* ``coverage``: per side, the share (%) of its device self time under a
  scope.
* ``top_ops``: the device ops that took most self time, each with its
  module and scope.
* ``span_idle_s`` / ``idle_s_by_span``: device-idle seconds inside the
  program's host spans (``obs.SPANS``), in all and by innermost span
  (``"-"``: inside none).

The stretch read is the host span ``window`` where the trace has one
(the benchmark's), else the whole trace.  A device op's self time is its
length less that of the ops nested directly inside it (a ``while`` op's
time excludes its body's ops).
"""
from __future__ import annotations

import glob
import json
import mmap
import os
import re
import sys
from collections import defaultdict

import numpy as np

from repro.core import obs

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
DEVICE_LINE = "XLA Ops"
MODULE_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
METADATA_PLANE = "/host:metadata"
WINDOW_SPAN = "window"
BUILD_MODULE = re.compile(r"^jit_(fused_vamana_pass|insert_batch|"
                          r"nsg_insert_batch)\(")
MODULE_ID = re.compile(r"\((\d+)\)$")
OP_NAME = re.compile(r"^%?([^\s=]+)")


def xplane_file(path: str) -> str:
    if os.path.isfile(path):
        return path
    files = glob.glob(os.path.join(path, "**", "*.xplane.pb"), recursive=True)
    if len(files) != 1:
        raise SystemExit(f"expected one .xplane.pb under {path}, found "
                         f"{len(files)}")
    return files[0]


def load(path: str, device_plane=DEVICE_PLANE, device_line=DEVICE_LINE,
         module_line=MODULE_LINE) -> dict:
    """{"ops": {"plane|line": [(instruction, start_ns, end_ns)]},
    "modules": {plane: [(module, start_ns, end_ns)]},
    "host": [(name, start_ns, end_ns)]}.

    ``device_line`` is a prefix (or tuple of prefixes) of the op lines'
    names.  With ``module_line`` None each op's module comes from its own
    ``hlo_module``/``program_id`` stats (the CPU has no module line), a
    lookup per event that only a small trace affords; events without an
    ``hlo_op`` stat are the CPU runtime's, not ops, and are left out."""
    from jax.profiler import ProfileData
    ops, modules, host = {}, {}, []
    for plane in ProfileData.from_file(path).planes:
        if device_plane.match(plane.name):
            mods = modules.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name.startswith(device_line):
                    # one op line per device plane on the TPU; on the CPU
                    # each client thread's line apart, as its ops nest
                    evs = ops.setdefault(f"{plane.name}|{line.name}", [])
                    for e in line.events:
                        end = e.start_ns + e.duration_ns
                        if module_line is None:
                            st = dict(e.stats)
                            if "hlo_op" not in st:
                                # the CPU client's own events around the
                                # ops (ThunkExecutor::Execute, "end: <op>")
                                continue
                            mods.append((f"{st['hlo_module']}"
                                         f"({st['program_id']})",
                                         e.start_ns, end))
                        m = OP_NAME.match(e.name)
                        evs.append((m.group(1) if m else e.name, e.start_ns,
                                    end))
                elif line.name == module_line:
                    mods.extend((e.name, e.start_ns,
                                 e.start_ns + e.duration_ns)
                                for e in line.events)
        if plane.name == HOST_PLANE:
            for line in plane.lines:
                if not line.name.startswith(device_line):
                    host.extend((e.name, e.start_ns,
                                 e.start_ns + e.duration_ns)
                                for e in line.events)
    return {"ops": ops, "modules": modules, "host": host}


# -- the optimized HLO's op_name of every instruction ---------------------------

def _varint(b, i: int) -> tuple[int, int]:
    r = shift = 0
    while True:
        c = b[i]
        i += 1
        r |= (c & 0x7F) << shift
        if c < 0x80:
            return r, i
        shift += 7


def _fields(b, i: int, end: int):
    """(field number, value) of one protobuf message's wire fields: an int
    for a varint, the payload's (start, end) for a length-delimited
    field, None for a fixed-width one."""
    while i < end:
        tag, i = _varint(b, i)
        wire = tag & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 2:
            n, i = _varint(b, i)
            v, i = (i, i + n), i + n
        elif wire in (1, 5):
            v, i = None, i + (8 if wire == 1 else 4)
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield tag >> 3, v


def _sub(b, msg, field: int):
    return (v for f, v in _fields(b, *msg) if f == field)


def _text(b, span) -> str:
    return bytes(b[span[0]:span[1]]).decode("utf-8", "replace")


def scope_of(op_name: str, scopes=obs.SCOPES) -> str:
    """The innermost of ``scopes`` in an HLO ``op_name``, else ``""``."""
    path = "/" + op_name + "/"
    best, at = "", -1
    for s in scopes:
        i = path.rfind("/" + s + "/")
        if i > at:
            best, at = s, i
    return best


def op_scopes(path: str) -> dict:
    """{(program_id, instruction): scope} over every module's optimized HLO
    (XSpace.planes["/host:metadata"].event_metadata[program_id].stats
    "Hlo Proto" -> HloProto.hlo_module.computations.instructions
    .metadata.op_name), read off the file's bytes: the other planes are
    skipped by their length."""
    out = {}
    with open(path, "rb") as f, \
            mmap.mmap(f.fileno(), 0, access=mmap.ACCESS_READ) as b:
        for plane in _sub(b, (0, len(b)), 1):
            name = next(_sub(b, plane, 2), None)
            if name is None or _text(b, name) != METADATA_PLANE:
                continue
            for entry in _sub(b, plane, 4):
                for meta in _sub(b, entry, 2):
                    pid = next(_sub(b, meta, 1), None)
                    for stat in _sub(b, meta, 5):
                        for proto in _sub(b, stat, 6):
                            for module in _sub(b, proto, 1):
                                for comp in _sub(b, module, 3):
                                    for ins in _sub(b, comp, 2):
                                        _scope_entry(b, ins, pid, out)
    return out


def _scope_entry(b, ins, pid, out) -> None:
    name = op = None
    for fld, v in _fields(b, *ins):
        if fld == 1:
            name = v
        elif fld == 7:
            op = next(_sub(b, v, 2), None)
    if name is not None and op is not None:
        scope = scope_of(_text(b, op))
        if scope:
            out[(pid, _text(b, name))] = scope


# -- reduction ------------------------------------------------------------------

def self_ns(start, end):
    """Self time (ns) of events sorted by (start, -end): each event's length
    less that of the events nested directly inside it.  An event's depth
    is the number of earlier events still open at its start; its parent
    is the last event one level up before it."""
    n = len(start)
    length = end - start
    depth = np.arange(n) - np.searchsorted(np.sort(end), start, side="right")
    inner = np.zeros(n)
    for d in range(1, int(depth.max()) + 1 if n else 1):
        kids = np.flatnonzero(depth == d)
        up = np.flatnonzero(depth == d - 1)
        if len(kids) and len(up):
            parent = up[np.searchsorted(up, kids) - 1]
            inner += np.bincount(parent, weights=length[kids], minlength=n)
    return length - inner


def _idle(start, end, lo, hi):
    """Sorted idle gaps (starts, ends) of the device in [lo, hi]."""
    if not len(start):
        return np.array([lo]), np.array([hi])
    reach = np.maximum.accumulate(end)
    first = np.flatnonzero(np.r_[True, start[1:] > reach[:-1]])
    busy_s = start[first]
    busy_e = reach[np.r_[first[1:] - 1, len(start) - 1]]
    gs, ge = np.r_[lo, busy_e], np.r_[busy_s, hi]
    keep = ge > gs
    return gs[keep], ge[keep]


def _idle_in(gs, ge, cum, a: float, b: float) -> float:
    i = int(np.searchsorted(ge, a, side="right"))
    j = int(np.searchsorted(gs, b, side="left"))
    if j <= i:
        return 0.0
    return float(cum[j] - cum[i] - max(0.0, a - gs[i])
                 - max(0.0, ge[j - 1] - b))


def _span_idle(host, gs, ge, lo, hi):
    spans = sorted(((n, max(s, lo), min(e, hi)) for n, s, e in host
                    if n in obs.SPANS and e > lo and s < hi),
                   key=lambda t: (t[1], -t[2]))
    cum = np.r_[0.0, np.cumsum(ge - gs)]
    total, inner = defaultdict(float), defaultdict(float)
    inner["-"] = float(cum[-1]) / 1e9
    stack: list[tuple] = []                # (end, name) of open spans
    for name, s, e in spans:
        while stack and stack[-1][0] <= s:
            stack.pop()
        idle = _idle_in(gs, ge, cum, s, e) / 1e9
        total[name] += idle
        inner[name] += idle
        inner[stack[-1][1] if stack else "-"] -= idle
        stack.append((e, name))
    return dict(total), dict(inner)


def _line_times(evs, runs, lo, hi):
    """{(instruction, module run index or -1): self seconds} of one op line,
    and its ops' (start, end) clipped to [lo, hi]."""
    names = sorted({n for n, _, _ in evs})
    code = {n: i for i, n in enumerate(names)}
    start = np.array([max(s, lo) for _, s, _ in evs], float)
    end = np.array([min(e, hi) for _, _, e in evs], float)
    codes = np.array([code[n] for n, _, _ in evs], np.int64)
    keep = np.flatnonzero(end > start)
    order = keep[np.lexsort((-end[keep], start[keep]))]
    start, end, codes = start[order], end[order], codes[order]
    own = self_ns(start, end)
    run = np.full(len(start), -1)
    if runs:
        at = np.searchsorted(np.array([r[0] for r in runs]), start,
                             side="right") - 1
        inside = (at >= 0) & (start < np.array([r[1] for r in runs])[
            np.maximum(at, 0)])
        run = np.where(inside, at, -1)
    key = (run + 1) * len(names) + codes
    uniq, inv = np.unique(key, return_inverse=True)
    secs = np.bincount(inv, weights=own) / 1e9
    out = {}
    for k, sec in zip(uniq.tolist(), secs.tolist()):
        r, c = divmod(k, len(names))
        out[(names[c], r - 1)] = sec
    return out, start, end


def reduce(events: dict, scopes: dict, top: int = 10) -> dict:
    """The report of one loaded trace (see the module docstring).  Idle is
    the time no op line of any device runs an op."""
    host = events["host"]
    windows = [(s, e) for n, s, e in host if n == WINDOW_SPAN]
    every = [t for evs in events["ops"].values() for t in evs]
    lo, hi = windows[0] if windows else (min(t[1] for t in every),
                                         max(t[2] for t in every))
    scope_s = {"build": defaultdict(float), "eval": defaultdict(float)}
    ops = defaultdict(float)
    starts, ends = [], []
    for line, evs in events["ops"].items():
        plane = line.split("|")[0]
        runs = sorted((max(s, lo), min(e, hi), m)
                      for m, s, e in events["modules"].get(plane, ())
                      if e > lo and s < hi)
        times, start, end = _line_times(evs, runs, lo, hi)
        starts.append(start)
        ends.append(end)
        for (name, r), sec in times.items():
            module = runs[r][2] if r >= 0 else "-"
            mid = MODULE_ID.search(module)
            scope = scopes.get((int(mid.group(1)) if mid else None, name),
                               "")
            if r >= 0:
                side = "build" if BUILD_MODULE.match(module) else "eval"
                scope_s[side][scope] += sec
            ops[(name, MODULE_ID.sub("", module), scope)] += sec
    start, end = np.concatenate(starts), np.concatenate(ends)
    order = np.argsort(start, kind="stable")
    gs, ge = _idle(start[order], end[order], lo, hi)
    span_idle, by_span = _span_idle(host, gs, ge, lo, hi)
    coverage = {}
    for side, times in scope_s.items():
        tot = sum(times.values())
        if tot > 0:
            coverage[side] = 100.0 * (1.0 - times.get("", 0.0) / tot)
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": (hi - lo - float(np.sum(ge - gs))) / 1e9,
        "scope_s": {k: dict(v) for k, v in scope_s.items()},
        "coverage": coverage,
        "top_ops": [[n, m, s, sec] for (n, m, s), sec in
                    sorted(ops.items(), key=lambda kv: -kv[1])[:top]],
        "span_idle_s": span_idle,
        "idle_s_by_span": by_span,
    }


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) != 1:
        print(__doc__.split("\n\n")[1], file=sys.stderr)
        return 2
    path = xplane_file(argv[0])
    print(json.dumps(reduce(load(path), op_scopes(path)), indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
