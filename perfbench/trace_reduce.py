"""From a profiler trace to numbers: busy and idle time, op times, idle gaps.

Two steps, so that the arithmetic can be checked without a chip:

* ``load(xplane_path)`` reads a ``.xplane.pb`` with ``jax.profiler.
  ProfileData`` and keeps, for every device plane (``/device:TPU:<n>``), the
  events of its ``XLA Ops`` line (one per executed HLO op or Pallas kernel)
  and of its ``XLA Modules`` line (one per executed program), as plain lists
  ``[name, start_ns, duration_ns]``.
* everything below works on that plain form; ``tests/trace_sample.json`` is
  such a record cut from a real trace taken on a TPU v5e.

Busy time is the union of the op intervals of one chip; idle share is one
minus busy over the traced slice. A gap is named by the program that ran
before it and that program's length — naming it by what the *host* was doing
needs host annotations inside the program (PERF.md, Open questions).
"""

from __future__ import annotations

import bisect
import re
from typing import Dict, List, Optional, Sequence, Tuple

_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
_OPS_LINE, _MODULES_LINE = "XLA Ops", "XLA Modules"
_HLO = re.compile(r"^%?([\w.\-]+) = \(?([a-z]+\d*\[[\d,]*\])?")
_NAME_LIMIT = 64


def load(xplane_path: str) -> Dict:
    from jax.profiler import ProfileData

    planes = []
    for plane in ProfileData.from_file(xplane_path).planes:
        if not _DEVICE_PLANE.match(plane.name):
            continue
        rec = {"name": plane.name, "ops": [], "modules": []}
        for line in plane.lines:
            if line.name == _OPS_LINE:
                rec["ops"] = [[_op_name(e), float(e.start_ns),
                               float(e.duration_ns)] for e in line.events]
            elif line.name == _MODULES_LINE:
                rec["modules"] = [[e.name, float(e.start_ns),
                                   float(e.duration_ns)] for e in line.events]
        planes.append(rec)
    return {"planes": planes}


def _op_name(event) -> str:
    """An ``XLA Ops`` event is named by its HLO text, ``%copy.7 =
    bf16[1025,7,...]{...} copy(...)``: keep the instruction's name and its
    result shape (eight ``copy`` ops differ only by what they copy)."""
    m = _HLO.match(event.name)
    if not m:
        return event.name
    return f"{m.group(1)} {m.group(2)}" if m.group(2) else m.group(1)


# ---------------------------------------------------------------------------
# arithmetic on the plain form
# ---------------------------------------------------------------------------

def busy_intervals(ops: Sequence[Sequence]) -> List[Tuple[float, float]]:
    """Union of the op intervals, as sorted disjoint ``(start, end)`` ns."""
    out: List[Tuple[float, float]] = []
    for start, end in sorted((o[1], o[1] + o[2]) for o in ops):
        if out and start <= out[-1][1]:
            if end > out[-1][1]:
                out[-1] = (out[-1][0], end)
        else:
            out.append((start, end))
    return out


def busy_seconds(trace: Dict) -> List[float]:
    """Seconds in which an op ran, one number per chip."""
    return [sum(e - s for s, e in busy_intervals(p["ops"])) * 1e-9
            for p in trace["planes"]]


def kernel_seconds(trace: Dict, prefixes: Sequence[str]) -> List[float]:
    """Per chip, the summed device time of the ops whose name starts with
    one of ``prefixes`` (a Pallas kernel keeps its ``name`` in the trace)."""
    return [sum(o[2] for o in p["ops"]
                if o[0].lstrip("%").startswith(tuple(prefixes))) * 1e-9
            for p in trace["planes"]]


def _clean(text: str) -> str:
    return re.sub(r"[^A-Za-z0-9_.]+", "_", text).strip("_")


def _module_at(modules: Sequence[Sequence], starts: Sequence[float],
               t: float) -> Optional[Sequence]:
    """The program that was running at, or last began before, time ``t``
    (``modules`` sorted by start, ``starts`` their start times)."""
    i = bisect.bisect_right(starts, t)
    return modules[i - 1] if i else None


def _sorted_modules(plane: Dict):
    modules = sorted(plane["modules"], key=lambda m: m[1])
    return modules, [m[1] for m in modules]


def self_times(ops: Sequence[Sequence]) -> List[float]:
    """Each op's duration less what the ops nested inside it take (a
    ``while`` spans its whole body), in the order of ``ops``."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i][1], -ops[i][2]))
    own = [float(o[2]) for o in ops]
    stack: List[int] = []
    for i in order:
        start, end = ops[i][1], ops[i][1] + ops[i][2]
        while stack and ops[stack[-1]][1] + ops[stack[-1]][2] <= start:
            stack.pop()
        if stack and end <= ops[stack[-1]][1] + ops[stack[-1]][2]:
            own[stack[-1]] -= ops[i][2]
        stack.append(i)
    return own


def top_ops(trace: Dict, n: int = 10) -> List[List]:
    """The ops with most device time of their own on the first chip:
    ``[name, seconds]``, the name carrying how often the op ran and in which
    program."""
    if not trace["planes"]:
        return []
    plane = trace["planes"][0]
    modules, starts = _sorted_modules(plane)
    total: Dict[Tuple[str, str], List[float]] = {}
    for (name, start, _dur), own in zip(plane["ops"],
                                         self_times(plane["ops"])):
        mod = _module_at(modules, starts, start)
        acc = total.setdefault((name, mod[0] if mod else ""), [0.0, 0])
        acc[0] += own
        acc[1] += 1
    rows = sorted(total.items(), key=lambda kv: -kv[1][0])[:n]
    return [[_clean(f"{_clean(op)[:40]}_x{int(cnt)}_in_{mod}")[:_NAME_LIMIT],
             dur * 1e-9] for (op, mod), (dur, cnt) in rows]


def idle_gaps(trace: Dict, n: int = 5) -> List[List]:
    """The longest gaps between ops on the first chip: ``[name, seconds]``,
    named ``after_<program>__<its length>_ms``."""
    if not trace["planes"]:
        return []
    plane = trace["planes"][0]
    modules, starts = _sorted_modules(plane)
    busy = busy_intervals(plane["ops"])
    gaps = sorted(((b[0] - a[1], a[1]) for a, b in zip(busy, busy[1:])),
                  reverse=True)[:n]
    out = []
    for length, at in gaps:
        mod = _module_at(modules, starts, at - 1.0)
        label = f"after_{mod[0]}__{mod[2] * 1e-6:.1f}_ms" if mod \
            else "after_unknown_program"
        out.append([_clean(label)[:_NAME_LIMIT], length * 1e-9])
    return out


def summary(trace: Dict) -> Dict:
    """What the result line's ``device`` and ``breakdown`` carry."""
    busy = busy_seconds(trace)
    return {"busy_s": sum(busy) / len(busy) if busy else 0.0,
            "busy_s_per_chip": busy, "window_s": trace["window_s"],
            "device_ops": top_ops(trace), "idle_gaps": idle_gaps(trace)}
