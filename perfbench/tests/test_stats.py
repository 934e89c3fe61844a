"""Metric arithmetic on hand-made stamps. Run by hand:

    JAX_PLATFORMS=cpu python3 -m pytest perfbench/tests -q
"""

import pytest

from perfbench import stats


def _req(due, sent, tokens, *, counted=True, ok=True, new=None):
    return {"due": due, "sent": sent, "tokens": tokens, "counted": counted,
            "ok": ok, "new_tokens": len(tokens) if new is None else new,
            "prompt_len": 8}


REQS = [
    _req(10.0, 10.001, [10.1, 10.2, 10.3, 10.4]),          # tpot 100 ms
    _req(11.0, 11.004, [11.05, 11.25, 11.45]),             # tpot 200 ms
    _req(12.0, 12.0, [12.5], counted=False),               # lead-out
    _req(13.0, 13.002, [13.1, 13.2], new=4),               # cut short
    _req(14.0, 14.0, [], ok=False, new=4),                 # errored
]


def test_percentile_interpolates():
    assert stats.percentile([1, 2, 3, 4], 50) == 2.5
    assert stats.percentile([5], 95) == 5
    assert stats.percentile([], 50) is None
    assert stats.percentile(range(101), 95) == 95


def test_counted_request_rule():
    assert len(stats.counted(REQS)) == 4
    assert stats.failed_count(REQS) == 2      # cut short + errored


def test_tpot_is_per_request_and_from_first_token():
    assert stats.quantity(REQS, "tpot_ms") == pytest.approx([100.0, 200.0])
    assert stats.percentile(stats.quantity(REQS, "tpot_ms"), 50) == \
        pytest.approx(150.0)


def test_itl_pools_every_gap():
    gaps = stats.quantity(REQS, "itl_ms")
    assert gaps == pytest.approx([100.0] * 3 + [200.0] * 2)
    assert stats.percentile(gaps, 95) == pytest.approx(200.0)


def test_latencies_are_from_due_time():
    assert stats.quantity(REQS, "ttft_ms") == pytest.approx([100.0, 50.0])
    assert stats.quantity(REQS, "late_ms") == pytest.approx([1.0, 4.0])


def test_spread_is_the_contracts():
    assert stats.spread([100, 101, 102, 103, 104, 105]) == \
        pytest.approx(3.5 / 102.5)
