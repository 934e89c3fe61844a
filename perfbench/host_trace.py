"""The host side of a profiler trace: what the program's threads were doing
while the device sat idle.

In mode ``on`` the program enters every span of ``observability/trace.py``
as a ``jax.profiler.TraceAnnotation`` too, so a traced run's ``.xplane.pb``
holds them in its host plane (``/host:CPU``, one line per OS thread, the
event named by the span, its attributes as stats) on the same time axis as
the device planes ``trace_reduce.py`` reads — up to a constant: on the TPU
the device planes of one session sit 0.4 to 2.2 ms early against the host
plane (a decode program "starts" before the host has enqueued it), which
would move that much of every gap from the launch that ends it into the
read-back that begins it. The TPU runtime's own host events repair it:
``DoEnqueueProgram`` carries the ``run_id`` of the device program it
enqueues, no program starts before its enqueue returned, and over the
dozens of programs of a slice the latest such enqueue brackets the offset
to some 50 us against the runtime's completion events (PERF.md, PR 25).
Three steps, the last two on plain lists so that the arithmetic can be
checked without a chip:

* ``find(record)`` locates this run's ``.xplane.pb`` under
  ``harness.OUT_DIR``: the record carries the reduced device planes but no
  path, so the file is recognised by its first device op, never taken for
  being the newest (another cell's run may have left one there);
* ``load(xplane_path)`` keeps, per host thread, the annotation events whose
  name starts with ``serving.``, ``jit.`` or ``train.`` as
  ``[name, start_ns, duration_ns]``, and for the clock every
  ``DoEnqueueProgram`` as ``[run_id, end_ns]`` and every program of the
  first chip as ``[run_id, start_ns]``;
* ``device_offset(host)`` is what to add to a device time to set it on the
  host's axis; ``attribute(gaps, spans)`` splits every device idle gap
  among the spans of one thread that cover it, the innermost span winning,
  the rest unnamed (``None``). ``tests/host_trace_sample.json`` is such a record cut from a
  real trace taken on a TPU v5e.

A program without the mirror (the parent of the PR that added it) leaves no
such events: ``step_thread`` finds none and every reader built on this
returns nothing. So does a trace without the runtime's enqueue events: a
split known to be off by milliseconds is not reported.
"""

from __future__ import annotations

import functools
import glob
import os
from typing import Dict, List, Optional, Sequence, Tuple

from . import harness, trace_reduce

PREFIXES = ("serving.", "jit.", "train.")
STEP_SPAN = "serving.decode"      # the span only the step thread opens
_HOST_PLANE = "/host:"
_ENQUEUE = "DoEnqueueProgram"     # the TPU runtime's, with a run_id stat

Interval = Tuple[float, float]


@functools.lru_cache(maxsize=2)
def _profile(xplane_path: str):
    from jax.profiler import ProfileData
    return ProfileData.from_file(xplane_path)


def _device_lines(xplane_path: str, line_name: str):
    """The events of one line of the first device plane."""
    for plane in _profile(xplane_path).planes:
        if trace_reduce._DEVICE_PLANE.match(plane.name):
            return [e for line in plane.lines if line.name == line_name
                    for e in line.events]
    return []


@functools.lru_cache(maxsize=2)
def load(xplane_path: str) -> Dict:
    """Parsed once per file: every reader over it shares the result, and
    none changes it."""
    threads, enqueues = [], []
    for plane in _profile(xplane_path).planes:
        if not plane.name.startswith(_HOST_PLANE):
            continue
        for line in plane.lines:
            spans = []
            for e in line.events:
                if e.name.startswith(PREFIXES):
                    spans.append([e.name, float(e.start_ns),
                                  float(e.duration_ns)])
                elif e.name == _ENQUEUE:
                    enqueues.append([dict(e.stats).get("run_id"),
                                     float(e.start_ns + e.duration_ns)])
            if spans:
                threads.append({"name": line.name, "spans": spans})
    programs = [[dict(e.stats).get("run_id"), float(e.start_ns)]
                for e in _device_lines(xplane_path,
                                       trace_reduce._MODULES_LINE)]
    return {"threads": threads, "enqueues": enqueues, "programs": programs}


def _first_device_op(xplane_path: str) -> Optional[List]:
    """``[start_ns, duration_ns]`` of the first op of the first device
    plane, as ``trace_reduce.load`` would list it."""
    for e in _device_lines(xplane_path, trace_reduce._OPS_LINE):
        return [float(e.start_ns), float(e.duration_ns)]
    return None


def find(record: Dict, out_dir: Optional[str] = None) -> Optional[str]:
    """The ``.xplane.pb`` this record's device trace was reduced from, or
    ``None`` (no traced slice, no file, or only files of other runs)."""
    trace = record.get("trace")
    if not trace or not trace["planes"] or not trace["planes"][0]["ops"]:
        return None
    want = trace["planes"][0]["ops"][0][1:3]
    files = glob.glob(os.path.join(
        out_dir or harness.OUT_DIR, "*.trace", "plugins", "profile", "*",
        "*.xplane.pb"))
    for path in sorted(files, key=os.path.getmtime, reverse=True):
        if _first_device_op(path) == want:
            return path
    return None


# ---------------------------------------------------------------------------
# arithmetic on the plain form
# ---------------------------------------------------------------------------

def device_offset(host: Dict) -> Optional[float]:
    """Nanoseconds to add to the device planes' times to set them on the
    host plane's axis: the least shift after which no program starts before
    the runtime enqueued it. ``None`` without a matching pair."""
    started = {run: t for run, t in host.get("programs", ())
               if run is not None}
    late = [end - started[run] for run, end in host.get("enqueues", ())
            if run in started]
    return max(late) if late else None


def step_thread(host: Dict) -> Optional[Dict]:
    """The thread that carries ``serving.decode`` (most of them, should a
    second engine ever run in the process)."""
    best, n_best = None, 0
    for th in host["threads"]:
        n = sum(1 for s in th["spans"] if s[0] == STEP_SPAN)
        if n > n_best:
            best, n_best = th, n
    return best


def device_gaps(ops: Sequence[Sequence]) -> List[Interval]:
    """The idle intervals between the busy intervals of one chip."""
    busy = trace_reduce.busy_intervals(ops)
    return [(a[1], b[0]) for a, b in zip(busy, busy[1:])]


def innermost(spans: Sequence[Sequence]) -> List[Tuple[float, float, str]]:
    """One thread's nested spans flattened to disjoint ``(start, end,
    name)`` segments, each named by the innermost span open there."""
    out: List[Tuple[float, float, str]] = []
    stack: List[Tuple[str, float]] = []          # (name, end)
    at = 0.0

    def close_until(t: float) -> None:
        nonlocal at
        while stack and stack[-1][1] <= t:
            name, end = stack.pop()
            if end > at:
                out.append((at, end, name))
                at = end

    for name, start, dur in sorted(spans, key=lambda s: (s[1], -s[2])):
        close_until(start)
        if stack and start > at:
            out.append((at, start, stack[-1][0]))
        at = max(at, start) if stack else start
        stack.append((name, start + dur))
    close_until(float("inf"))
    return out


def attribute(gaps: Sequence[Interval], spans: Sequence[Sequence]
              ) -> Dict[Optional[str], float]:
    """Nanoseconds of ``gaps`` under each innermost span name; under no
    span at all, ``None``. The values sum to the gaps' total length."""
    segs = innermost(spans)
    out: Dict[Optional[str], float] = {}
    i = 0
    for g0, g1 in sorted(gaps):
        while i < len(segs) and segs[i][1] <= g0:
            i += 1
        named, j = 0.0, i
        while j < len(segs) and segs[j][0] < g1:
            part = min(g1, segs[j][1]) - max(g0, segs[j][0])
            if part > 0:
                out[segs[j][2]] = out.get(segs[j][2], 0.0) + part
                named += part
            j += 1
        if g1 - g0 > named:
            out[None] = out.get(None, 0.0) + (g1 - g0) - named
    return out


def split(ops: Sequence[Sequence], spans: Sequence[Sequence],
          offset_ns: float = 0.0) -> Dict:
    """``{"by_span": {name or None: ns}, "steps": n}``: the first chip's
    idle gaps, moved by ``offset_ns`` onto the host's axis, attributed to
    one thread's spans, and how many decode steps that thread began in the
    slice."""
    gaps = [(a + offset_ns, b + offset_ns) for a, b in device_gaps(ops)]
    return {"by_span": attribute(gaps, spans),
            "steps": sum(1 for s in spans if s[0] == STEP_SPAN)}


def split_of(record: Dict) -> Optional[Dict]:
    """``split`` over the step thread for a run's record, on the repaired
    clock; ``None`` where there is nothing to read. A record that carries
    ``host`` (the tests' sample) is used as it is; otherwise the run's
    trace file is found and parsed once."""
    host = record.get("host")
    if host is None:
        path = find(record)
        host = load(path) if path else None
    th = step_thread(host) if host else None
    offset = device_offset(host) if th else None
    if offset is None:
        return None
    return split(record["trace"]["planes"][0]["ops"], th["spans"], offset)
