"""The two set-up readers on a hand-made record where the answer is known,
and the five ``setup_*`` metrics read from the tiny serving context's
record (ISSUE 36)."""

import json
import os

import pytest

from perfbench import run as prun
from perfbench.readers import counter_at_window_start as at_start
from perfbench.readers import span_union_before_window as union
from perfbench.tests import tiny

SETUP_METRICS = ("setup_trace_s", "setup_lower_s", "setup_compile_s",
                 "setup_warmup_s", "setup_cache_misses")


def _pair(name, t0, t1, sid):
    return [{"kind": "B", "name": name, "ts": t0, "span": sid, "attrs": {}},
            {"kind": "E", "name": name, "ts": t1, "span": sid, "attrs": {}}]


RECORD = {
    "window": [100.0, 130.0],
    # written in the order phase_done writes them: a pair at a time, the
    # enclosing trace after the kernel bodies it holds
    "spans": [
        *_pair("jit.trace", 11.0, 12.0, 1),        # a kernel body ...
        *_pair("jit.trace", 12.5, 13.0, 2),        # ... and another, inside
        *_pair("jit.trace", 10.0, 14.0, 3),        # the program's trace
        *_pair("jit.lower", 14.0, 15.5, 4),
        *_pair("jit.trace", 20.0, 20.25, 5),       # the next program's
        *_pair("jit.trace", 13.5, 14.5, 6),        # overlaps 3 by half
        *_pair("jit.trace", 99.5, 100.5, 7),       # ends inside the window
        *_pair("jit.trace", 110.0, 111.0, 8),      # a tail met in traffic
        {"kind": "B", "name": "jit.trace", "ts": 90.0, "span": 9,
         "attrs": {}},                             # never ended
        *_pair("serving.warmup", 9.0, 21.0, 10),
    ],
    "counters": {"start": {"jit.persistent_cache_misses_total": 3.0,
                           "jit.compile_seconds_total": {"phase=trace": 4.0}},
                 "end": {"jit.persistent_cache_misses_total": 4.0}},
}


@pytest.mark.parametrize("span,want", [
    ("jit.trace", 4.0 + 0.5 + 0.25),     # nested counted once, overlap once
    ("jit.lower", 1.5),
    ("serving.warmup", 12.0),
    ("jit.compile", None),               # a program that opens none
])
def test_union_before_window(span, want):
    got = union.read(RECORD, span=span)
    assert got == (None if want is None else pytest.approx(want))


def test_union_reads_zero_when_every_span_ends_after_the_window_began():
    rec = dict(RECORD, spans=_pair("jit.trace", 99.0, 101.0, 1))
    assert union.read(rec, span="jit.trace") == 0.0


@pytest.mark.parametrize("name,want", [
    ("jit.persistent_cache_misses_total", 3.0),      # the start, not the end
    ("jit.persistent_cache_hits_total", None),       # a missing counter
    ("jit.compile_seconds_total", None),             # a labelled family
])
def test_counter_at_window_start(name, want):
    assert at_start.read(RECORD, name=name) == want


def test_manifest_lists_the_five_for_the_serving_cells():
    with open(os.path.join(prun.ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    mine = [m for m in manifest["per_layer"] if m["name"] in SETUP_METRICS]
    assert [m["name"] for m in mine] == list(SETUP_METRICS)
    assert manifest["per_layer"][-5:] == mine        # appended, at the end
    serving = [w["name"] for w in manifest["workloads"]
               if "serve" in w["config"]]
    for m in mine:
        assert (m["moves"], m["better"], m["layer"]) == (
            "setup_s", "lower", "compiled programs")
        assert m["workloads"] == serving


def test_tiny_serving_record_reads_all_five():
    from paddle_tpu.observability import trace as ptrace
    from perfbench.runners import serve_open_loop
    ptrace.clear()          # a run is a new process; a test session is not
    try:
        rec = serve_open_loop.run(tiny.ctx(tiny.SERVE, tiny.CHAT,
                                           seconds=2.0, trace=1))
    finally:
        ptrace.set_mode("off")
        ptrace.clear()
    assert rec["correct"] and rec["failed"] == 0
    # the whole buffer, set-up included, is a well-formed forest
    assert ptrace.span_problems(rec["spans"]) == []
    with open(os.path.join(prun.ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    line = prun.read_metrics(manifest, "per_layer", "chat-decode", rec)
    got = {k: line[k]["value"] for k in SETUP_METRICS}
    setup_s = rec["values"]["setup_s"]
    for k in SETUP_METRICS[:4]:
        assert 0 < got[k] < setup_s, (k, got, setup_s)
    assert got["setup_cache_misses"] == 0     # the CPU runs without a cache
    assert rec["values"]["compiles_in_window"] == 0
    # one compiled call a decode bucket and a prompt length, under the one
    # warm-up, each dispatch naming its program
    begins = {e["span"]: e for e in rec["spans"] if e["kind"] == "B"}
    warm, = [b for b in begins.values() if b["name"] == "serving.warmup"]
    calls = [b["span"] for b in begins.values()
             if b["name"] == "jit.call" and b["parent"] == warm["span"]]
    assert len(calls) == warm["attrs"]["programs"] == len(
        tiny.SERVE["serve"]["buckets"]) + len(tiny.CHAT["prompt_lens"])
    asked = [b["attrs"]["program"] for b in begins.values()
             if b["name"] == "jit.dispatch" and b["parent"] in calls]
    assert len(asked) == len(calls) and all(asked)
