"""Profiler capture and the reduction from a device trace to numbers.

``capture`` runs a callable under ``jax.profiler.trace`` (Python tracer
off) and returns ``extract``'s summary of the trace it wrote:

``window``   [start, end] ns of the harness's ``cb.window`` span,
``devices``  {device plane: [[op name, start ns, duration ns], ...]},
``spans``    [[name, start ns, end ns], ...] of the harness's ``cb.*``
             host spans,

all on the profiler's one clock, relative to the window's start.
``reduce`` turns a summary into the numbers the per-layer readers use:
``busy_s`` (union of device op intervals inside the window, averaged
over the devices), ``window_s``, each span's device-busy overlap, the
device ops that took most time and the idle gaps by the harness span open
at the time.  The reduction is pure so that it can be checked on a small
recorded summary (``tests/data``).
"""

from __future__ import annotations

import bisect
import glob
import os
import shutil
import tempfile

WINDOW = "cb.window"
PREFIX = "cb."


def op_name(hlo: str) -> str:
    """An op's HLO instruction name (``stream_topk_pallas.1``) from the
    trace's full HLO text (``%stream_topk_pallas.1 = (f32[...]) ...``)."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def _device_line(plane):
    """The line of a device plane that holds one event per operation."""
    lines = {line.name: line for line in plane.lines}
    for name in ("XLA Ops", "Ops"):
        if name in lines:
            return lines[name]
    return None


def extract(path: str) -> dict:
    """The summary of one ``.xplane.pb`` file (see the module doc)."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices, spans = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and \
                not plane.name.startswith("/device:CUSTOM"):
            line = _device_line(plane)
            if line is None:
                continue
            evs = [[op_name(ev.name), int(ev.start_ns), int(ev.duration_ns)]
                   for ev in line.events]
            if evs:
                devices[plane.name] = evs
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(PREFIX):
                        s = int(ev.start_ns)
                        spans.append([ev.name, s, s + int(ev.duration_ns)])
    windows = [s for s in spans if s[0] == WINDOW]
    if len(windows) != 1:
        raise RuntimeError(f"expected one {WINDOW} span, found "
                           f"{len(windows)}")
    w0, w1 = windows[0][1], windows[0][2]
    return {
        "window": [0, w1 - w0],
        "devices": {p: [[n, s - w0, d] for n, s, d in evs]
                    for p, evs in devices.items()},
        "spans": [[n, s - w0, e - w0] for n, s, e in spans if n != WINDOW],
    }


def capture(fn):
    """(fn's result, the trace summary) of ``fn()`` run under the profiler;
    ``fn`` must open the ``cb.window`` span around the window itself."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    d = tempfile.mkdtemp(prefix="chipbench-trace-")
    try:
        with jax.profiler.trace(d, profiler_options=opts):
            out = fn()
        files = glob.glob(os.path.join(d, "**", "*.xplane.pb"),
                          recursive=True)
        if len(files) != 1:
            raise RuntimeError(f"expected one trace file, found {files}")
        return out, extract(files[0])
    finally:
        shutil.rmtree(d, ignore_errors=True)


# ------------------------------------------------------------- reduction
def union(intervals) -> list:
    """Merged, sorted [start, end] intervals."""
    out: list = []
    for s, e in sorted((s, e) for s, e in intervals if e > s):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def overlap(merged: list, s: float, e: float) -> float:
    """Length of [s, e] covered by merged, sorted intervals."""
    i = bisect.bisect_right(merged, [s, float("inf")]) - 1
    total = 0.0
    for a, b in merged[max(i, 0):]:
        if a >= e:
            break
        total += max(0.0, min(e, b) - max(s, a))
    return total


def intersect(a: list, b: list) -> float:
    """Total length covered by both of two merged, sorted interval lists."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def busy_intervals(summary: dict) -> dict:
    """{device plane: merged busy intervals clipped to the window}."""
    w0, w1 = summary["window"]
    return {p: union((max(s, w0), min(s + d, w1)) for _, s, d in evs)
            for p, evs in summary["devices"].items()}


def reduce(summary: dict, top: int = 10) -> dict:
    """Numbers from a trace summary (times in seconds)."""
    w0, w1 = summary["window"]
    busy = busy_intervals(summary)
    if not busy:
        raise RuntimeError("the trace holds no device operation")
    n_dev = len(busy)
    busy_ns = sum(b - a for iv in busy.values() for a, b in iv) / n_dev
    # the device ops that took most time, summed over devices / n_dev
    per_op: dict = {}
    for evs in summary["devices"].values():
        for name, s, d in evs:
            cut = max(0, min(s + d, w1) - max(s, w0))
            per_op[name] = per_op.get(name, 0) + cut
    ops = sorted(((n, t / n_dev / 1e9) for n, t in per_op.items()),
                 key=lambda x: -x[1])[:top]
    # each harness span with the device-busy time inside it
    spans = []
    for name, s, e in summary["spans"]:
        inside = sum(overlap(iv, s, e) for iv in busy.values()) / n_dev
        spans.append([name, (e - s) / 1e9, inside / 1e9])
    # idle time, by the harness span open at the time (the first device;
    # spans of different threads may overlap, so the shares can add up
    # to more than the idle time)
    first = busy[sorted(busy)[0]]
    gaps, t = [], w0
    for a, b in first + [[w1, w1]]:
        if a > t:
            gaps.append([t, a])
        t = max(t, b)
    idle_ns = sum(b - a for a, b in gaps)
    names = sorted({n for n, _, _ in summary["spans"]})
    by_span = {n: intersect(union((s, e) for m, s, e in summary["spans"]
                                  if m == n), gaps) for n in names}
    by_span["(no harness span)"] = idle_ns - intersect(
        union((s, e) for _, s, e in summary["spans"]), gaps)
    idle = sorted(((n, t / 1e9) for n, t in by_span.items()),
                  key=lambda x: -x[1])[:top]
    return {"busy_s": busy_ns / 1e9, "window_s": (w1 - w0) / 1e9,
            "devices": n_dev, "device_ops": [list(x) for x in ops],
            "idle_gaps": [list(x) for x in idle], "spans": spans}
