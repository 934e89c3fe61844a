"""Seeded chaos/soak for the serving engine (ISSUE 8 satellite).

A randomized-but-SEEDED ``FaultSchedule`` sweep over every serving fault
site — ``serving.admit`` / ``serving.step`` / ``serving.watchdog`` /
``serving.drain`` — driving the toy-LM engine from ``test_serving``
through admission faults, per-slot step faults, whole-batch device
faults, hung-step watchdog trips with bounded replay, and injected drain
faults, while asserting the liveness invariants that make "serving under
fire" trustworthy:

* **every submitted Future resolves** — with a result or a typed error
  (``FaultInjected`` / ``WatchdogTimeout`` / ``DeadlineExceeded`` /
  ``DrainTimeout`` / ``EngineStopped``), never stranded;
* **the page pool returns to empty** — free-list back to full, zero
  outstanding pages: no leak on ANY recovery path;
* **terminal accounting is exact** — each resolved request is counted
  under exactly one ``serving.requests_total`` status, and the counters
  are monotone across the sweep;
* requests that DO complete under fire decode exactly the no-fault
  reference sequence (faults may delay or kill a request, never corrupt
  one — a step writes only its own slots' pages, and a replay's
  re-prefill rewrites them; ISSUE 26's tests at the end pin the donated
  pool's side of it).

The per-seed schedules are deterministic (``FaultSchedule``'s own seeded
RNG); wall-clock timing (the watchdog thread) decides only WHEN a hung
step trips, never the invariants asserted here. Scripted bit-identical
trace pins live in ``test_serving.py``.
"""

import numpy as np
import pytest

import paddle_tpu  # noqa: F401  (backend pin via conftest)
from paddle_tpu import observability as obs
from paddle_tpu import serving
from paddle_tpu.resilience import DeadlineExceeded, faults

from test_serving import (PROMPTS, TIERS, _request, dense_reference,
                          make_engine, one_at_a_time, tier_prompts)

EXPECTED_ERRORS = (faults.FaultInjected, serving.WatchdogTimeout,
                   DeadlineExceeded, serving.DrainTimeout,
                   serving.EngineStopped)

# statuses a successfully-submitted request may terminally resolve under
# (submit-time rejections raise on the caller thread and never get here)
TERMINAL_STATUSES = ("completed", "failed", "shed", "cancelled")


def _chaos_schedule(seed: int) -> faults.FaultSchedule:
    """All four serving sites, seeded probabilities. The watchdog-site
    delay is rare and long (vs. a generous budget) so a trip is
    unambiguous without stretching the soak's wall clock."""
    sched = faults.FaultSchedule(seed)
    sched.error("serving.admit", prob=0.15)
    sched.error("serving.step", prob=0.06)
    sched.delay("serving.watchdog", prob=0.04, times=1, seconds=0.8)
    sched.error("serving.watchdog", prob=0.05)
    sched.error("serving.drain", prob=0.5)
    return sched


# the shared ``metrics`` fixture (fresh enabled obs registry) lives in
# tests/conftest.py


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_chaos_sweep_invariants(seed, metrics):
    sched = _chaos_schedule(seed)
    # warmup() precompiles every decode bucket BEFORE the fault window:
    # with no persistent compile cache (conftest stopped sharing one — it
    # was unsound on CPU), a cold decode-program compile inside the run
    # would trip the 0.2 s watchdog as a phantom hung step and distort
    # the seeded accounting the invariants below pin
    eng = make_engine(max_batch=4, watchdog_s=0.2, max_replays=2,
                      max_queue=16).warmup()
    n_new = [4, 3, 5, 4, 3]
    futs = []
    with faults.installed(sched):
        for i, (p, n) in enumerate(zip(PROMPTS, n_new)):
            # a mix of unbounded requests and generous deadlines: the
            # deadline paths stay live without making shedding the
            # dominant outcome
            kw = {"deadline_s": 30.0} if i % 2 else {}
            futs.append(eng.submit(serving.GenerationRequest(
                p, max_new_tokens=n, **kw)))
        eng.run()
        eng.stop(drain=True, timeout=10)
    eng.stop(drain=True, timeout=1)        # idempotent under fire

    # 1) no stranded futures: everything resolved, typed
    completed = 0
    for p, n, f in zip(PROMPTS, n_new, futs):
        assert f.done(), "stranded future after drain"
        try:
            res = f.result(timeout=0)
        except EXPECTED_ERRORS:
            continue
        completed += 1
        # survivors decode the exact no-fault sequence
        assert res.tokens == dense_reference(p, n)
        assert res.finish_reason in ("length", "eos")

    # 2) no leaked pages, no residual slots/queue
    assert eng.kv.outstanding_pages == 0
    assert eng.kv.free_pages == eng.kv.config.num_pages - 1
    assert eng.active_requests == 0 and eng.queue_depth == 0

    # 3) terminal accounting: every submitted request counted exactly once
    snap = obs.snapshot()
    req_counts = snap.get("serving.requests_total", {})
    resolved = sum(req_counts.get(f"status={s}", 0)
                   for s in TERMINAL_STATUSES)
    assert resolved == len(futs)
    assert req_counts.get("status=completed", 0) == completed

    # 4) monotone/consistent counters: tokens were only ever added, and
    #    replays never exceeded the budget x submissions
    assert snap.get("serving.tokens_total", 0) >= completed * min(n_new)
    assert snap.get("serving.replays_total", 0) <= 2 * len(futs)


def test_chaos_same_seed_same_terminal_state(metrics):
    """Two sweeps under the same seed agree on every per-request outcome
    (result tokens or exception type) — the FaultSchedule determinism
    contract holds through the full engine, with the timing-driven
    watchdog excluded from the schedule."""
    def run_once():
        sched = faults.FaultSchedule(7)
        sched.error("serving.admit", prob=0.2)
        sched.error("serving.step", prob=0.08)
        outcomes = []
        eng = make_engine(max_batch=4, max_replays=1)
        with faults.installed(sched):
            futs = [eng.submit(serving.GenerationRequest(
                p, max_new_tokens=4)) for p in PROMPTS[:4]]
            eng.run()
            eng.stop(drain=True, timeout=10)
        for f in futs:
            try:
                outcomes.append(("ok", tuple(f.result(timeout=0).tokens)))
            except EXPECTED_ERRORS as exc:
                outcomes.append(("err", type(exc).__name__))
        return outcomes, list(sched.trace)

    first, trace1 = run_once()
    second, trace2 = run_once()
    assert first == second
    assert trace1 == trace2 and len(trace1) >= 1


@pytest.mark.parametrize("seed", [0, 2])
def test_chaos_sweep_trace_invariants(seed, metrics, tracing, tmp_path):
    """ISSUE 12: under the same seeded sweep, every span is balanced (each
    start has exactly one end — spans are context managers, so this holds
    through faults, watchdog trips, replays, and the drain), every
    request that RESOLVED with a fault carries the fault event on its own
    trace, and any crash-recovery that fired left a parseable flight
    dump whose tail names the fault site."""
    import json
    import os
    sched = _chaos_schedule(seed)
    # warmup before the fault window: a cold decode compile would trip
    # the watchdog as a phantom hung step (see test_chaos_sweep_invariants)
    eng = make_engine(max_batch=4, watchdog_s=0.2, max_replays=2,
                      max_queue=16).warmup()
    n_new = [4, 3, 5, 4, 3]
    reqs, futs = [], []
    with faults.installed(sched):
        for i, (p, n) in enumerate(zip(PROMPTS, n_new)):
            kw = {"deadline_s": 30.0} if i % 2 else {}
            r = serving.GenerationRequest(p, max_new_tokens=n, **kw)
            reqs.append(r)
            futs.append(eng.submit(r))
        eng.run()
        eng.stop(drain=True, timeout=10)

    evs = tracing.events()
    # 1) every span balanced, tree well-formed, on every recovery path
    assert tracing.span_problems(evs) == []

    # 2) every fault-resolved request's trace carries the fault event
    for r, f in zip(reqs, futs):
        exc = f.exception(timeout=0)
        if not isinstance(exc, (faults.FaultInjected,
                                serving.WatchdogTimeout)):
            continue
        mine = [e for e in evs
                if (e.get("attrs") or {}).get("rid") == r.request_id]
        assert any(e["name"] == "serving.fault" for e in mine), \
            f"request {r.request_id} failed with {type(exc).__name__} " \
            f"but its trace has no fault event"

    # 3) crash-recovery (unrecoverable batched step) left a parseable
    #    dump whose tail names the fault site
    recovered = any(e["name"] == "serving.recover" for e in evs)
    dump = os.path.join(str(tmp_path),
                        f"flight-{os.getpid()}-serving_recover.json")
    assert recovered == os.path.exists(dump)
    if recovered:
        doc = json.load(open(dump))
        assert doc["reason"] == "serving_recover"
        sites = [e["attrs"].get("site") for e in doc["events"]
                 if e["name"] == "fault"]
        assert sites and sites[-1].startswith("serving.")

    # 4) the chrome export of the whole chaos run still loads
    json.dumps(tracing.export_chrome())


def test_soak_continuous_load_with_faults(metrics):
    """Longer horizon: three waves of submissions against a live engine
    (background thread) with step/admit faults and replays enabled; the
    drain at the end must still resolve the world and return every
    page."""
    rng = np.random.default_rng(42)
    sched = faults.FaultSchedule(99)
    sched.error("serving.admit", prob=0.1)
    sched.error("serving.step", prob=0.05)
    sched.error("serving.watchdog", prob=0.03)
    eng = make_engine(max_batch=4, max_queue=32, max_replays=2)
    futs = []
    with faults.installed(sched):
        eng.start()
        try:
            for _ in range(3):
                for _ in range(6):
                    p = rng.integers(0, 31, (int(rng.integers(3, 12)),),
                                     dtype=np.int32)
                    futs.append(eng.submit(serving.GenerationRequest(
                        p, max_new_tokens=int(rng.integers(2, 6)))))
                # wait for the wave to mostly drain before the next
                for f in futs:
                    try:
                        f.result(timeout=60)
                    except EXPECTED_ERRORS:
                        pass
        finally:
            eng.stop(drain=True, timeout=10)
    assert len(futs) == 18
    for f in futs:
        assert f.done()
    assert eng.kv.outstanding_pages == 0
    assert eng.active_requests == 0 and eng.queue_depth == 0
    snap = obs.snapshot()
    resolved = sum(snap["serving.requests_total"].get(f"status={s}", 0)
                   for s in TERMINAL_STATUSES)
    assert resolved == len(futs)


# ---------------------------------------------------------------------------
# ISSUE 26: the fault contract under a donated pool — the pool a program
# returns is always adopted, only its tokens may be abandoned; a call that
# consumed the pool and raised ends in a fresh pool and everyone replayed
# ---------------------------------------------------------------------------

def _spy_recoveries(eng, monkeypatch):
    """Record, at every ``_recover_slots``, whether ``kv.pool`` is live."""
    seen = []
    real = eng._recover_slots

    def spy(included, exc):
        seen.append((type(exc).__name__, eng.kv.pool.is_deleted(),
                     len(included)))
        return real(included, exc)

    monkeypatch.setattr(eng, "_recover_slots", spy)
    return seen


def test_tripped_step_adopts_its_pool_and_replays_bit_identically(
        metrics, monkeypatch):
    sched = faults.FaultSchedule().delay("serving.watchdog", on=(2,),
                                         seconds=1.0)
    eng = make_engine(watchdog_s=0.25, max_replays=1).warmup()
    seen = _spy_recoveries(eng, monkeypatch)
    adopted = []
    real_adopt = eng.programs._adopt
    monkeypatch.setattr(eng.programs, "_adopt", lambda outs: (
        real_adopt(outs), adopted.append(eng.kv.pool))[0])
    with faults.installed(sched):
        futs = [eng.submit(serving.GenerationRequest(
            p, max_new_tokens=4)) for p in PROMPTS[:2]]
        eng.run()
    eng.stop()
    # the tripped step's pool was adopted before its tokens were dropped:
    # at recovery the engine held a live pool, the very one the call gave
    assert seen == [("WatchdogTimeout", False, 2)]
    assert all(a.is_deleted() for a in adopted[:-1])     # each consumed
    assert adopted[-1] is eng.kv.pool and not eng.kv.pool.is_deleted()
    for p, f in zip(PROMPTS, futs):
        assert f.result(timeout=5).tokens == dense_reference(p, 4)
    assert eng.kv.outstanding_pages == 0
    snap = obs.snapshot()
    assert snap["serving.replays_total"] == 2
    assert "serving.pool_resets_total" not in snap


def test_step_abandoned_by_a_budgeted_stop_adopts_its_pool(metrics):
    """A step wedged past a budgeted ``stop()``: the stragglers are
    requeued from the caller's thread, the step returns late, its tokens
    reach nobody — and the pool it returns is the engine's pool, so the
    restarted loop re-prefills and continues bit-identically."""
    import time as _t
    sched = faults.FaultSchedule().delay("serving.watchdog", on=(3,),
                                         seconds=1.8)
    eng = make_engine(max_batch=1).warmup()
    with faults.installed(sched):
        eng.start()
        fut = eng.submit(serving.GenerationRequest(PROMPTS[0],
                                                   max_new_tokens=8))
        while eng.active_requests == 0:
            _t.sleep(0.005)
        t0 = _t.monotonic()
        eng.stop(drain=True, timeout=0.05, on_timeout="requeue")
        assert _t.monotonic() - t0 < 1.7          # gave up on the wedge
        assert not fut.done() and eng.queue_depth == 1
        held = eng.kv.pool                        # what the wedged call has
        _t.sleep(1.0)                             # the wedged step returns
    assert held.is_deleted()                      # consumed by that call
    assert not eng.kv.pool.is_deleted()           # and its return adopted
    assert eng.kv.outstanding_pages == 0
    eng.run()                                     # resumes the requeued work
    assert fut.result(timeout=5).tokens == dense_reference(PROMPTS[0], 8)
    assert eng.kv.free_pages == eng.kv.config.num_pages - 1


def _consume_and_raise_on(eng, attr, nth):
    """Make the ``nth`` call of program ``attr`` behave like a device
    fault AFTER donation: the pool it was given is deleted, nothing comes
    back."""
    real, calls = getattr(eng.programs, attr), []

    def program(*args):
        calls.append(args)
        if len(calls) == nth:
            for i in eng.programs._donate:
                args[i]._data.delete()
            raise RuntimeError("device fault after the pool was consumed")
        return real(*args)

    setattr(eng.programs, attr, program)
    return calls


def test_decode_call_that_consumed_the_pool_and_raised(metrics):
    from test_prefix_sharing import SHARED_PROMPTS, make_engine3
    ref_eng = make_engine3("off", max_batch=4)
    futs = [ref_eng.submit(serving.GenerationRequest(p, max_new_tokens=5))
            for p in SHARED_PROMPTS]
    ref_eng.run()
    ref = [f.result(timeout=30).tokens for f in futs]

    eng = make_engine3(max_batch=4)
    _consume_and_raise_on(eng, "decode_program", nth=2)
    # the third slot sits the second step out (its serving.step seam
    # faults): running, not included — and replayed all the same
    sched = faults.FaultSchedule().error("serving.step", on=(6,))
    futs = [eng.submit(serving.GenerationRequest(p, max_new_tokens=5))
            for p in SHARED_PROMPTS]
    with faults.installed(sched):
        assert eng.step()                         # 3 prefills + 1 decode
        assert eng.active_requests == 3 and eng.kv.prefix_summary()
        assert eng.step()                         # the consuming fault
        pool = eng.kv.pool
        assert not pool.is_deleted()
        assert pool.shape == ref_eng.kv.pool.shape \
            and pool.dtype == ref_eng.kv.pool.dtype
        assert not np.asarray(pool).any()         # fresh
        assert eng.kv.prefix_summary() == frozenset()
        assert eng.active_requests == 0 and eng.queue_depth == 3
        assert eng.kv.outstanding_pages == 0
        snap = obs.snapshot()
        assert snap["serving.replays_total"] == 3     # every running slot
        assert snap["serving.pool_resets_total"] == 1
        eng.run()
    assert [f.result(timeout=30).tokens for f in futs] == ref
    assert eng.kv.free_pages == eng.kv.config.num_pages - 1
    assert sched.trace == [("serving.step", 6, "error")]


def test_prefill_call_that_consumed_the_pool_and_raised(metrics):
    from test_prefix_sharing import SHARED_PROMPTS, make_engine3
    ref_eng = make_engine3("off", max_batch=4)
    f0 = ref_eng.submit(serving.GenerationRequest(SHARED_PROMPTS[0],
                                                  max_new_tokens=6))
    ref_eng.run()
    ref = f0.result(timeout=30).tokens

    eng = make_engine3("off", max_batch=4)
    _consume_and_raise_on(eng, "prefill_program", nth=2)
    fut = eng.submit(serving.GenerationRequest(SHARED_PROMPTS[0],
                                               max_new_tokens=6))
    assert eng.step() and eng.active_requests == 1
    late = eng.submit(serving.GenerationRequest(SHARED_PROMPTS[1],
                                                max_new_tokens=3))
    eng.step()                                    # its prefill faults
    # the admission that raised fails alone; the running slot lost its
    # pages with the pool and goes through replay
    with pytest.raises(RuntimeError, match="pool was consumed"):
        late.result(timeout=1)
    assert not eng.kv.pool.is_deleted()
    assert obs.snapshot()["serving.replays_total"] == 1
    eng.run()
    assert fut.result(timeout=30).tokens == ref
    assert eng.kv.free_pages == eng.kv.config.num_pages - 1


# ---------------------------------------------------------------------------
# ISSUE 28: a fault with a decode step in flight, on both decode tiers
# ---------------------------------------------------------------------------

def _run_with_step_in_flight(eng, prompts, n_new, streamed):
    """Submit, then step until the pipe is full: a step launched and not
    read, every request running."""
    reqs = [_request(p, n_new, streamed) for p in prompts]
    futs = [eng.submit(r) for r in reqs]
    for _ in range(3):
        eng.step()
    assert eng._flight is not None and eng._flight.ahead == 1
    assert all(s.ahead == 1 for s in eng._slots)
    return reqs, futs


@pytest.mark.parametrize("tier", TIERS)
class TestFaultsWithAStepInFlight:
    def test_cancel(self, tier, tier_engine, metrics):
        """The cancelled request's row of the step in flight is discarded
        on read; its partial transcript is a prefix of the reference, its
        batchmate's stream is whole, every page returns."""
        prompts = tier_prompts(tier_engine.vocab)[:2]
        eng = tier_engine(tier)
        ref = one_at_a_time(eng, prompts, [10, 10])
        obs.reset()
        streamed = {}
        reqs, futs = _run_with_step_in_flight(eng, prompts, 10, streamed)
        eng.cancel(reqs[0].request_id)
        eng.run()
        res = [f.result(timeout=0) for f in futs]
        assert res[0].finish_reason == "cancelled"
        assert 1 <= len(res[0].tokens) < 10
        assert res[0].tokens == ref[0][:len(res[0].tokens)]
        assert streamed[reqs[0].request_id] == res[0].tokens
        assert res[1].tokens == ref[1] == streamed[reqs[1].request_id]
        assert obs.snapshot()["serving.decode_discarded_rows_total"] == 1
        assert eng._flight is None and eng.kv.outstanding_pages == 0

    @pytest.mark.parametrize("fault", ["error", "trip"])
    def test_whole_batch_fault_abandons_every_step_in_flight(
            self, tier, tier_engine, fault, metrics):
        """A second ``serving.watchdog`` error, or a watchdog trip, at the
        launch of step n+1 with step n unread: both steps' tokens are
        abandoned, every slot replays from what was EMITTED, and the
        streams come out bit-identical — no token twice, none missing."""
        prompts = tier_prompts(tier_engine.vocab)[:3]
        watchdog_s = 1.0 if fault == "trip" else None
        eng = tier_engine(tier, watchdog_s=watchdog_s, max_replays=1)
        eng.warmup()            # no compile inside the watchdog's window
        ref = one_at_a_time(eng, prompts, [9, 9, 9])
        obs.reset()
        sched = faults.FaultSchedule()
        if fault == "error":
            sched.error("serving.watchdog", on=(1, 2))
        else:
            sched.delay("serving.watchdog", on=(1,), seconds=2.5)
        streamed = {}
        reqs, futs = _run_with_step_in_flight(eng, prompts, 9, streamed)
        emitted = [len(s.tokens) for s in eng._slots]
        with faults.installed(sched):
            eng.step()          # launch attempt(s) fail: replay
            assert eng._flight is None and eng.active_requests == 0
            assert eng.queue_depth == 3 and eng.kv.outstanding_pages == 0
            assert [len(streamed[r.request_id]) for r in reqs] == emitted
            eng.run()
        assert [f.result(timeout=0).tokens for f in futs] == ref
        assert [streamed[r.request_id] for r in reqs] == ref
        snap = obs.snapshot()
        assert snap["serving.replays_total"] == 3
        assert snap["serving.tokens_total"] == 3 * 9    # none twice
        assert eng._flight is None and eng.kv.outstanding_pages == 0
        eng.stop()
