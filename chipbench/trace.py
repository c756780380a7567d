"""The one reduction from a profiler trace to device numbers.

Reads the ``.xplane.pb`` that ``jax.profiler`` writes, through
``jax.profiler.ProfileData``, and reduces it to

* ``busy_s``: the union of the device-op intervals inside the window;
* ``window_s``: the window's length, from the harness's ``window`` span;
* ``idle_share``: 1 - busy / window;
* ``kernel_s``: device self time summed per op name (the HLO
  instruction's name: a Pallas kernel is named after its ``pallas_call``
  wrapper, e.g. ``gather_distance.3``; a ``while`` op's time excludes the
  ops of its body, which the trace nests inside it);
* ``breakdown``: the ten device ops that took most time, and the ten
  longest idle gaps labelled by what the host was doing: the innermost
  harness span (``setup``, ``estimate.build``, ``estimate.eval``,
  ``serve.call``, ``host.reference``) and the innermost host runtime
  event around the gap's midpoint.

``start`` records the device ops and the host's runtime events and
spans, not every Python call: the Python tracer would add an event per
function call of the host loop to a trace that a window of some minutes
already fills with millions of device events.

Timestamps are nanoseconds on the profiler's one clock; host and device
events of one trace share it.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
DEVICE_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
WINDOW_SPAN = "window"
SPANS = ("setup", "estimate.build", "estimate.eval", "serve.call",
         "host.reference")


OP_NAME = re.compile(r"^%?([^\s=]+)")


def op_name(event_name: str) -> str:
    """The HLO instruction's name: TPU op events carry the instruction's
    whole text, ``%fusion.12 = f32[...] fusion(...)``."""
    m = OP_NAME.match(event_name)
    return m.group(1) if m else event_name


def start(trace_dir: str) -> None:
    """Start the profiler into ``trace_dir``, the Python tracer off."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=opts)


def xplane_file(trace_dir: str) -> str:
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {trace_dir}, "
                           f"found {len(files)}")
    return files[0]


def load(path: str, device_plane=DEVICE_PLANE, device_line=DEVICE_LINE,
         host_plane=HOST_PLANE) -> dict:
    """{"device": {plane: [(name, start_ns, end_ns)]}, "host": [...]}.

    ``device_plane`` is a pattern over plane names and ``device_line`` a
    prefix of line names; the defaults pick the TPU's op lines (a test on
    the CPU points them at the CPU client's thread instead).
    """
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    device, host = {}, []
    for plane in pd.planes:
        if device_plane.match(plane.name):
            evs = device.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name.startswith(device_line):
                    evs.extend((op_name(e.name), e.start_ns,
                                e.start_ns + e.duration_ns)
                               for e in line.events)
        if plane.name == host_plane:
            # each host thread's events are on a line named after the
            # thread, and the main thread takes the program's name
            # ("python3", "python", ...): read every line but the ops
            for line in plane.lines:
                if not (device_plane.match(plane.name)
                        and line.name.startswith(device_line)):
                    host.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                                for e in line.events)
    return {"device": device, "host": host}


def union(intervals) -> list[tuple[float, float]]:
    """Merged, sorted (start, end) intervals."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def self_times(events) -> dict:
    """{name: seconds} of device self time: an event's duration less that
    of the events nested directly inside it on the same line."""
    out = defaultdict(float)
    stack: list[list] = []                 # [end, name, child_ns]

    def close(item):
        out[item[1]] += item[3] / 1e9 - item[2] / 1e9

    for name, s, e in sorted(events, key=lambda t: (t[1], -t[2])):
        while stack and stack[-1][0] <= s:
            close(stack.pop())
        if stack:
            stack[-1][2] += e - s
        stack.append([e, name, 0, e - s])
    while stack:
        close(stack.pop())
    return out


def _window(host) -> tuple[float, float]:
    spans = [(s, e) for name, s, e in host if name == WINDOW_SPAN]
    if len(spans) != 1:
        raise RuntimeError(f"expected one {WINDOW_SPAN!r} span in the "
                           f"trace, found {len(spans)}")
    return spans[0]


def _label(host, t: float) -> str:
    """What the host was doing at t: innermost harness span and innermost
    other host event."""
    around = [(e - s, name) for name, s, e in host if s <= t <= e]
    spans = sorted(x for x in around if x[1] in SPANS)
    other = sorted(x for x in around
                   if x[1] not in SPANS and x[1] != WINDOW_SPAN)
    parts = [spans[0][1] if spans else "-", other[0][1] if other else "-"]
    return " > ".join(parts)


def reduce(events: dict, top: int = 10) -> dict:
    """Device numbers of one traced window (see the module docstring)."""
    lo, hi = _window(events["host"])
    window_s = (hi - lo) / 1e9
    if not events["device"]:
        raise RuntimeError("the trace holds no device plane")
    busy, kernel, gaps = [], defaultdict(float), []
    for evs in events["device"].values():
        inside = [(n, max(s, lo), min(e, hi)) for n, s, e in evs
                  if e > lo and s < hi]
        merged = union((s, e) for _, s, e in inside)
        busy.append(sum(e - s for s, e in merged) / 1e9)
        for name, sec in self_times(inside).items():
            kernel[name] += sec
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        gaps += [(edges[i + 1] - edges[i], (edges[i] + edges[i + 1]) / 2)
                 for i in range(0, len(edges), 2) if edges[i + 1] > edges[i]]
    busy_s = sum(busy) / len(busy)
    gaps.sort(reverse=True)
    ops = sorted(kernel.items(), key=lambda kv: -kv[1])[:top]
    return {
        "busy_s": busy_s,
        "window_s": window_s,
        "idle_share": 1.0 - busy_s / window_s,
        "kernel_s": dict(kernel),
        "breakdown": {
            "device_ops": [[n, s] for n, s in ops],
            "idle_gaps": [[_label(events["host"], mid), g / 1e9]
                          for g, mid in gaps[:top]],
        },
    }


def kernel_seconds(reduced: dict, pattern: str) -> float:
    """Device seconds of the ops whose name matches ``pattern`` in full."""
    rx = re.compile(pattern)
    return sum(s for n, s in reduced["kernel_s"].items() if rx.fullmatch(n))
