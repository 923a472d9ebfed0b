"""From a profiler trace to the numbers the per-layer metrics read.

`start`/`stop` wrap `jax.profiler` around the measured window (Python
function tracing off: the benchmark's own `TraceAnnotation` spans, named
``bench.*``, mark what the host did). `reduce` reads the `.xplane.pb`
with `jax.profiler.ProfileData`:

* the window is the ``bench.window`` span on the host;
* busy time is the union of the intervals in which a program ran on
  the device (the ``XLA Modules`` line of each ``/device:TPU:<n>``
  plane), averaged over the devices that ran anything; the union of
  single ops (``XLA Ops``) is kept beside it as `ops_busy_s`, since a
  program's ops leave gaps while the chip's scalar unit steps its loops;
* each program's device time is the sum of its ``XLA Modules`` events,
  keyed by the jitted function's name (``jit_tape_exec_impl`` ->
  ``tape_exec_impl``);
* the top device ops are named ``<program>/<op>`` (``tape_exec_impl/
  %while.3``); an op inside a loop counts in the loop's time as well;
* each idle gap of the device is charged to the innermost host event of
  the window's thread that covers its middle.

The chip's trace buffers fill: a window of programs with long op loops
(the read path's) keeps only its first seconds of device events. Every
call the benchmark makes (a ``bench.insert``, ``bench.lookup``,
``bench.range`` or ``bench.pump`` span) runs a program on the device, so
a call span that starts after the last recorded device event shows the
trace cut short. The reduction then keeps the whole calls before the
last one with device events (that one may be cut): `truncated` is set
and `window_s` runs from the window's start to the end of the last call
kept, so shares and per-call times are those of whole calls, host work
between them included. Counts read from the program over the whole
window do not match such a trace.

A path ending in ``.gz`` is read as a gzipped `.xplane.pb`.
"""
from __future__ import annotations

import bisect
import collections
import glob
import gzip
import os
import re
from dataclasses import dataclass, field

WINDOW_SPAN = "bench.window"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
TOP = 10
CALL_SPANS = ("bench.insert", "bench.lookup", "bench.range", "bench.pump")


def start(log_dir) -> None:
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(log_dir), profiler_options=opts)


def stop() -> None:
    import jax

    jax.profiler.stop_trace()


def find(path) -> str:
    """The `.xplane.pb` file at `path` or anywhere below it."""
    if os.path.isfile(path):
        return str(path)
    hits = glob.glob(os.path.join(str(path), "**", "*.xplane.pb"),
                     recursive=True)
    if len(hits) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {path}, "
                                f"found {len(hits)}")
    return hits[0]


def program_name(module: str) -> str:
    """``jit_tape_exec_impl(123)`` -> ``tape_exec_impl``."""
    name = module.split("(", 1)[0].strip()
    return name[4:] if name.startswith("jit_") else name


def union(intervals) -> list[tuple[int, int]]:
    """Sorted, merged copy of (start, end) intervals."""
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


@dataclass
class Reduced:
    """One traced window, in seconds."""
    window_s: float = 0.0
    busy_s: float = 0.0
    ops_busy_s: float = 0.0
    devices: int = 0
    program_s: dict = field(default_factory=dict)   # name -> device s
    program_n: dict = field(default_factory=dict)   # name -> executions
    device_ops: list = field(default_factory=list)  # [[name, s]] top
    idle_gaps: list = field(default_factory=list)   # [[host span, s]] top
    spans: dict = field(default_factory=dict)       # bench.* -> [n, s]
    truncated: bool = False     # device events cover only part of it
    calls_kept: int = 0         # call spans in `window_s`
    calls: int = 0              # call spans in the whole window

    @property
    def idle_share(self) -> float | None:
        """None where the trace kept no whole call."""
        return 1.0 - self.busy_s / self.window_s if self.window_s > 0 \
            else None

    def program(self, name: str) -> tuple[float, int]:
        """(device seconds, executions) of the programs called `name`."""
        return self.program_s.get(name, 0.0), self.program_n.get(name, 0)


def load(path):
    from jax.profiler import ProfileData

    path = find(path)
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            return ProfileData.from_serialized_xspace(f.read())
    return ProfileData.from_file(path)


def short_op(name: str) -> str:
    """``%while.42 = (s32[] ...) while(...)`` -> ``%while.42``."""
    return name.split(" = ", 1)[0]


def reduce(path) -> Reduced:
    return reduce_profile(load(path))


def reduce_profile(pd) -> Reduced:
    """`reduce` of a loaded trace (anything with `ProfileData`'s planes,
    lines and events)."""
    host = [pl for pl in pd.planes if pl.name == "/host:CPU"]
    window = thread = None
    for line in (host[0].lines if host else []):
        for e in line.events:
            if e.name == WINDOW_SPAN:
                window, thread = (e.start_ns, e.end_ns), line
                break
        if window:
            break
    if window is None:
        raise ValueError(f"no {WINDOW_SPAN!r} span in the trace")
    w0, w1 = window
    planes = []
    for plane in pd.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        lines = {ln.name: ln for ln in plane.lines}
        mods = sorted((max(e.start_ns, w0), min(e.end_ns, w1),
                       program_name(e.name))
                      for e in (lines[MODULES_LINE].events
                                if MODULES_LINE in lines else ())
                      if e.end_ns > w0 and e.start_ns < w1)
        ops = [(max(e.start_ns, w0), min(e.end_ns, w1), e.name)
               for e in (lines[OPS_LINE].events if OPS_LINE in lines
                         else ())
               if e.end_ns > w0 and e.start_ns < w1]
        if mods or ops:
            planes.append((mods, ops))
    out = Reduced()
    calls = sorted((e.start_ns, e.end_ns) for e in thread.events
                   if e.name in CALL_SPANS and e.start_ns >= w0
                   and e.end_ns <= w1)
    out.calls = out.calls_kept = len(calls)
    last = max((t for p in planes for iv in p for _, t, _ in iv),
               default=w0)
    unrecorded = [i for i, (s, _) in enumerate(calls) if s >= last]
    if unrecorded:
        out.truncated = True
        out.calls_kept = max(unrecorded[0] - 1, 0)
        w1 = calls[out.calls_kept - 1][1] if out.calls_kept else w0
    out.window_s = (w1 - w0) * 1e-9

    # host events of the window's thread, for labelling idle gaps
    host_ev = []
    for e in thread.events:
        if e.end_ns > w0 and e.start_ns < w1 and e.duration_ns > 0:
            host_ev.append((e.start_ns, -e.end_ns, e.name))
            if (e.name.startswith("bench.") and e.name != WINDOW_SPAN
                    and e.start_ns >= w0):
                n_s = out.spans.setdefault(e.name, [0, 0.0])
                n_s[0] += 1
                n_s[1] += e.duration_ns * 1e-9
    host_ev.sort()

    busy_total = ops_total = 0.0
    ops_s: collections.Counter = collections.Counter()
    gaps_s: collections.Counter = collections.Counter()
    for mods, ops in planes:
        mods = [(max(s, w0), min(t, w1), n) for s, t, n in mods
                if t > w0 and s < w1]
        for s, t, name in mods:
            out.program_s[name] = (out.program_s.get(name, 0.0)
                                   + (t - s) * 1e-9)
            out.program_n[name] = out.program_n.get(name, 0) + 1
        starts = [m[0] for m in mods]
        iv = []
        for s, t, name in ops:
            s, t = max(s, w0), min(t, w1)
            if t > s:
                iv.append((s, t))
                i = bisect.bisect_right(starts, s) - 1
                prog = mods[i][2] if i >= 0 and s < mods[i][1] else "?"
                ops_s[f"{prog}/{short_op(name)}"] += (t - s) * 1e-9
        ops_total += sum(t - s for s, t in union(iv)) * 1e-9
        busy = union([(s, t) for s, t, _ in mods] or iv)
        out.devices += 1
        busy_total += sum(t - s for s, t in busy) * 1e-9
        edges = [w0] + [x for se in busy for x in se] + [w1]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        for label, secs in _label_gaps(gaps, host_ev):
            gaps_s[label] += secs
    if out.devices:
        out.busy_s = busy_total / out.devices
        out.ops_busy_s = ops_total / out.devices
        n = out.devices
        out.device_ops = [[k, v / n] for k, v in ops_s.most_common(TOP)]
        out.idle_gaps = [[k, v / n] for k, v in gaps_s.most_common(TOP)]
    return out


def _label_gaps(gaps, host_ev):
    """(label, seconds) per gap: the innermost host event covering the
    gap's middle (events of one thread nest, so a stack sweep finds it)."""
    stack: list[tuple[int, str]] = []
    j = 0
    for s, t in gaps:
        mid = (s + t) // 2
        while j < len(host_ev) and host_ev[j][0] <= mid:
            start, neg_end, name = host_ev[j]
            stack.append((-neg_end, name))
            j += 1
        while stack and stack[-1][0] <= mid:
            stack.pop()
        # an enclosing event may end before an inner one was pushed
        live = [name for end, name in stack if end > mid]
        yield (live[-1] if live else "outside host events"), (t - s) * 1e-9


def describe(path) -> str:
    """Planes, lines and event counts of a trace (for reading one by
    hand)."""
    pd = load(path)
    rows = []
    for plane in pd.planes:
        rows.append(f"plane {plane.name!r}")
        for line in plane.lines:
            evs = list(line.events)
            names = sorted({e.name for e in evs})
            rows.append(f"  line {line.name!r}: {len(evs)} events, "
                        f"{len(names)} names, e.g. {names[:8]}")
            if evs:
                e = evs[0]
                rows.append(f"    first {e.name!r} at {e.start_ns} for "
                            f"{e.duration_ns} ns, stats "
                            f"{[k for k, _ in e.stats][:8]}")
    return "\n".join(rows)
