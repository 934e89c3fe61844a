"""The schedule is a pure function of the traffic file: the same requests
at the same offsets whatever ``--seed``; only the token ids follow it."""

import copy

from perfbench import harness, schedule
from perfbench.tests import tiny


def _shape(reqs):
    return [(r["due"], r["session"], r["question"], r["prompt_len"],
             r["new_tokens"], r["counted"]) for r in reqs]


def test_plan_is_a_pure_function_of_the_file():
    for traffic in (tiny.CHAT, tiny.DOCQA):
        a, b = schedule.plan(traffic, 5.0), schedule.plan(traffic, 5.0)
        assert a and _shape(a) == _shape(b)
        other = schedule.plan(dict(traffic, schedule_seed=99), 5.0)
        assert _shape(other) != _shape(a)


def test_seed_changes_tokens_only():
    plan = schedule.plan(tiny.DOCQA, 5.0)
    a = schedule.fill(copy.deepcopy(plan), 1, 128)
    b = schedule.fill(copy.deepcopy(plan), 2 ** 31 + 5, 128)
    assert _shape(a) == _shape(b)
    assert [r["prompt"] for r in a] != [r["prompt"] for r in b]
    again = schedule.fill(copy.deepcopy(plan), 1, 128)
    assert [r["prompt"] for r in a] == [r["prompt"] for r in again]


def test_longer_window_extends_and_never_reshuffles():
    for traffic in (tiny.CHAT, tiny.DOCQA):
        short, long = schedule.plan(traffic, 3.0), schedule.plan(traffic, 9.0)
        assert len(long) > len(short)
        assert [s[:5] for s in _shape(short)] == \
            [s[:5] for s in _shape(long)[:len(short)]]


def test_counted_is_the_window_and_sessions_share_a_prefix():
    reqs = schedule.fill(schedule.plan(tiny.DOCQA, 4.0), 3, 128)
    lead = tiny.DOCQA["lead_in_s"]
    for r in reqs:
        assert r["counted"] == (lead <= r["due"] < lead + 4.0)
        assert len(r["prompt"]) == r["prompt_len"]
    by_session = {}
    for r in reqs:
        by_session.setdefault(r["session"], []).append(r)
    shared = [rs for rs in by_session.values() if len(rs) > 1]
    assert shared
    for rs in shared:
        d = rs[0]["doc_len"]
        assert all(r["prompt"][:d] == rs[0]["prompt"][:d] for r in rs)
        assert rs[0]["prompt"][d:] != rs[1]["prompt"][d:]


def test_real_traffic_files_plan():
    for name in ("chat_short_unshared", "docqa_shared_prefix"):
        traffic = harness.load_json("traffic", name + ".json")
        reqs = schedule.plan(traffic, 30.0)
        n = sum(r["counted"] for r in reqs)
        assert abs(n - 30.0 * traffic["rate_rps"]) < 0.5 * 30.0 * \
            traffic["rate_rps"]
        shapes = schedule.prompt_shapes(reqs)
        assert max(shapes["prompt_lens"]) + max(traffic["answer_lens"]) <= 4096
