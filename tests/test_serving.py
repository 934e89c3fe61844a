"""paddle_tpu.serving — continuous batching over the paged KV cache.

All CPU-deterministic (no chip): the engine is driven with a tiny pure-jnp
toy LM whose next token is a *cache-dependent* greedy argmax — position-
weighted so paging mistakes (page permutation, stale bytes, wrong
write-back page) change the decoded sequence, not just some hidden state.
The dense single-sequence loop over the same two callables is the parity
oracle, exactly the role the bs=1 per-token loop plays for
``bench_generation.py --serving``.

Covers the ISSUE 7 acceptance surface:
* kv_cache unit behavior (alloc/free, page math, absmax-int8 grid) and
  the dense-vs-int8 logits-tolerance parity test;
* scheduler edge cases: queue overflow, FIFO no-slip-ahead, prefill
  token budget, cancel (queued and active), admission at full batch,
  page-pool gating, the zero-active-slot idle step;
* engine end-to-end greedy parity (batched == sequential) incl.
  continuous admission across evictions, on every kv dtype leg;
* deterministic fault injection through the existing
  ``resilience.FaultSchedule`` seams: a faulted slot fails ALONE —
  co-batched requests complete with bit-identical tokens.

ISSUE 8 ("serving under fire") adds the overload/containment surface:
* per-request deadlines + TTFT budgets: expired-in-queue requests shed
  with a typed ``DeadlineExceeded`` at the admission boundary, batchmates
  bit-identical to the no-fault run;
* load shedding: queue-wait-aware reject-on-arrival, the
  ``PADDLE_TPU_SERVING_MAX_QUEUE_WAIT`` hard cap, and
  ``serving.rejected_total{reason}`` visibility;
* the step watchdog: a hung compiled step (delay fault at
  ``serving.watchdog``) trips, its outputs are abandoned, and its slots
  recover via bounded prefill replay — zero stranded futures, zero
  leaked pages;
* graceful drain: ``stop(drain=True)`` finishes in-flight work, is
  idempotent, and ``on_timeout="requeue"`` resumes bit-identically after
  a restart.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu  # noqa: F401  (backend pin via conftest)
from paddle_tpu import observability as obs
from paddle_tpu import serving
from paddle_tpu.core.tensor import Tensor as T
from paddle_tpu.resilience import faults
from paddle_tpu.serving import kv_cache as kvc


# ---------------------------------------------------------------------------
# toy LM over the stacked-cache layout (L, 2, B, H, M, D)
# ---------------------------------------------------------------------------

V = 31
L, H, D, M = 2, 2, 4, 64

_W = jnp.asarray(np.linspace(-1.0, 1.0, D * V).reshape(D, V)
                 .astype(np.float32))
_POSW = (jnp.arange(M, dtype=jnp.float32) + 1.0) / M   # order-sensitivity


def _kv_of(tok_f):
    """token value -> (…, H, D) K/V payload; head- and dim-ramped so every
    cache axis carries signal."""
    ramp_d = (jnp.arange(D, dtype=jnp.float32) + 1.0) / D
    ramp_h = (jnp.arange(H, dtype=jnp.float32) + 1.0) / H
    base = (tok_f[..., None, None] + 1.0) / V
    return base * ramp_h[:, None] * ramp_d[None, :]


def _readout(cache00, valid):
    """(…, H, M, D) x (…, M) -> (…, V): the position-weighted "attention"
    readout. Masking by the write position mirrors the span mask of the
    real decode step — scratch-page garbage beyond ``t`` must never leak
    into logits."""
    feat = jnp.einsum("...hmd,...m,m->...d", cache00.astype(jnp.float32),
                      valid.astype(jnp.float32), _POSW)
    return feat @ _W


def toy_step(tok, cache, t):
    """(B, 1) int32, (L, 2, B, H, M, D), (B,) int32 -> next tok + cache."""
    tok_d, c, td = tok._data, cache._data, t._data.astype(jnp.int32)
    kv = _kv_of(tok_d[:, 0].astype(jnp.float32))         # (B, H, D)

    def wr(cb, kvb, tb):                                 # cb (L, 2, H, M, D)
        page = jnp.broadcast_to(kvb[None, None, :, None, :],
                                (L, 2, H, 1, D)).astype(cb.dtype)
        return jax.lax.dynamic_update_slice(cb, page, (0, 0, 0, tb, 0))

    c2 = jax.vmap(wr, in_axes=(2, 0, 0), out_axes=2)(c, kv, td)
    valid = jnp.arange(M)[None, :] <= td[:, None]        # (B, M)
    logits = _readout(c2[0, 0], valid)
    nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
    return T(nxt), T(c2)


def toy_prefill(ids, cache):
    """(1, Lp) int32, zeroed (L, 2, 1, H, M, D) -> first tok + cache."""
    idsd, c = ids._data, cache._data
    lp = idsd.shape[1]
    kv = jnp.transpose(_kv_of(idsd[0].astype(jnp.float32)), (1, 0, 2))
    c = c.at[:, :, 0, :, :lp, :].set(
        jnp.broadcast_to(kv, (L, 2, H, lp, D)).astype(c.dtype))
    valid = (jnp.arange(M) < lp)[None, :]
    logits = _readout(c[0, 0], valid)
    nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
    return T(nxt), T(c)


def dense_reference(prompt, n_new):
    """The bs=1 dense loop — same callables, no paging. Greedy oracle."""
    cache = T(jnp.zeros((L, 2, 1, H, M, D), jnp.float32))
    tok, cache = toy_prefill(T(jnp.asarray(prompt[None, :], jnp.int32)),
                             cache)
    toks = [int(np.asarray(tok._data)[0, 0])]
    t = int(prompt.size)
    for _ in range(n_new - 1):
        tok, cache = toy_step(tok, cache, T(jnp.asarray([t], jnp.int32)))
        toks.append(int(np.asarray(tok._data)[0, 0]))
        t += 1
    return toks


def make_engine(max_batch=4, page_size=16, kv_dtype="native", **kw):
    cfg = serving.ServingConfig(
        num_layers=L, num_heads=H, head_dim=D, max_len=M,
        max_batch=max_batch,
        buckets=tuple(b for b in (1, 4, 16) if b <= max_batch) or (max_batch,),
        page_size=page_size, kv_dtype=kv_dtype, **kw)
    return serving.Engine(toy_prefill, toy_step, cfg)


_RNG = np.random.default_rng(0)
PROMPTS = [_RNG.integers(0, V, (n,), dtype=np.int32)
           for n in (8, 8, 8, 5, 11)]


# the shared ``metrics`` fixture (fresh enabled obs registry) lives in
# tests/conftest.py


# ---------------------------------------------------------------------------
# kv_cache: page math + the int8 grid
# ---------------------------------------------------------------------------

class TestKVCache:
    def test_config_validation(self):
        with pytest.raises(ValueError, match="multiple"):
            kvc.KVCacheConfig(num_layers=L, num_heads=H, head_dim=D,
                              max_len=60, page_size=16)
        with pytest.raises(ValueError, match="num_pages"):
            kvc.PagedKVCache(kvc.KVCacheConfig(
                num_layers=L, num_heads=H, head_dim=D, max_len=M,
                page_size=16))
        with pytest.raises(ValueError, match="scratch"):
            kvc.PagedKVCache(kvc.KVCacheConfig(
                num_layers=L, num_heads=H, head_dim=D, max_len=M,
                page_size=16, num_pages=1))

    def test_alloc_free_accounting(self):
        pool = kvc.PagedKVCache(kvc.KVCacheConfig(
            num_layers=L, num_heads=H, head_dim=D, max_len=M,
            page_size=16, num_pages=5))
        assert pool.free_pages == 4           # page 0 reserved
        ids = pool.alloc(3)
        assert len(ids) == 3 and 0 not in ids
        assert pool.alloc(2) is None          # partial claims never escape
        assert pool.free_pages == 1
        pool.free(ids)
        assert pool.free_pages == 4
        with pytest.raises(ValueError):
            pool.free(ids[:1])                # double free
        with pytest.raises(ValueError):
            pool.free([0])                    # scratch is not freeable

    def test_pages_for_rounding(self):
        pool = kvc.PagedKVCache(kvc.KVCacheConfig(
            num_layers=L, num_heads=H, head_dim=D, max_len=M,
            page_size=16, num_pages=5))
        assert pool.pages_for(1) == 1
        assert pool.pages_for(16) == 1
        assert pool.pages_for(17) == 2
        assert pool.pages_for(10_000) == 4    # capped at pages_per_slot

    def test_quantize_pages_absmax_grid(self):
        rng = np.random.default_rng(1)
        pages = jnp.asarray(rng.standard_normal(
            (3, L, 2, H, 16, D)).astype(np.float32)) * 4.0
        q, scale = kvc.quantize_pages(pages)
        assert q.dtype == jnp.int8 and scale.shape == (3, L, 2, H)
        absmax = np.max(np.abs(np.asarray(pages)), axis=(-2, -1))
        np.testing.assert_allclose(np.asarray(scale), absmax / 127.0,
                                   rtol=1e-6)
        # reconstruction error bounded by half a quantization step
        recon = np.asarray(q, np.float32) * np.asarray(scale)[..., None, None]
        err = np.abs(recon - np.asarray(pages))
        assert (err <= np.asarray(scale)[..., None, None] * 0.5 + 1e-6).all()
        # all-zero page quantizes with scale 1 (no 0/0)
        qz, sz = kvc.quantize_pages(jnp.zeros((1, L, 2, H, 16, D)))
        assert (np.asarray(sz) == 1.0).all() and (np.asarray(qz) == 0).all()

    def _roundtrip(self, kv_dtype):
        cfg = kvc.KVCacheConfig(num_layers=L, num_heads=H, head_dim=D,
                                max_len=M, page_size=16, num_pages=5,
                                kv_dtype=kv_dtype)
        pool = kvc.PagedKVCache(cfg)
        rng = np.random.default_rng(2)
        lp = 40                                # 3 pages, last partial
        dense = jnp.asarray(rng.standard_normal(
            (L, 2, 1, H, M, D)).astype(np.float32))
        dense = dense.at[:, :, :, :, lp:, :].set(0.0)
        page_ids = pool.alloc(pool.pages_for(lp))
        row = pool.table_row(page_ids)   # 3 real pages + 1 scratch entry;
        # the engine passes the FULL row — trailing scratch entries absorb
        # the masked-to-zero pages past the prompt
        p2, s2 = kvc.scatter_prefill_pages(
            dense, pool.pool, pool.scales, jnp.asarray(row),
            jnp.asarray(lp, jnp.int32), 16)
        back = kvc.gather_pages(p2, s2, jnp.asarray(row[None, :]),
                                jnp.float32)
        return np.asarray(dense[:, :, 0]), np.asarray(back[:, :, 0]), lp

    def test_gather_scatter_roundtrip_native(self):
        dense, back, lp = self._roundtrip("native")
        np.testing.assert_array_equal(back[..., :lp, :], dense[..., :lp, :])

    def test_int8_roundtrip_tolerance(self):
        dense, back, lp = self._roundtrip("int8")
        absmax = np.abs(dense).max()
        assert np.abs(back[..., :lp, :] - dense[..., :lp, :]).max() \
            <= absmax / 127.0 * 0.5 + 1e-6

    def test_int8_logits_tolerance_parity(self):
        """The ISSUE-named parity gate: logits computed off the paged-int8
        cache match the dense-cache logits within the absmax grid's error
        budget — and are NOT trivially identical."""
        dense, back, lp = self._roundtrip("int8")
        valid = (np.arange(M) < lp)[None, :]
        ref = np.asarray(_readout(jnp.asarray(dense[0, 0][None]),
                                  jnp.asarray(valid)))
        got = np.asarray(_readout(jnp.asarray(back[0, 0][None]),
                                  jnp.asarray(valid)))
        delta = np.abs(got - ref).max()
        assert 0.0 < delta <= 0.05 * np.abs(ref).max(), delta

    def test_scatter_token_masks_future_positions(self):
        """A freshly claimed page must not inherit stale pool bytes: the
        single-token write-back zeroes positions > t inside its page."""
        cfg = kvc.KVCacheConfig(num_layers=L, num_heads=H, head_dim=D,
                                max_len=M, page_size=16, num_pages=5)
        pool = jnp.full((5,) + cfg.page_shape(), 7.0, jnp.float32)  # stale
        tables = jnp.asarray([[1, 2, 3, 4]], jnp.int32)
        dense = jnp.asarray(np.random.default_rng(3).standard_normal(
            (L, 2, 1, H, M, D)).astype(np.float32))
        t = jnp.asarray([17], jnp.int32)       # page 1 of the slot
        p2, _ = kvc.scatter_token_page(dense, pool, None, tables, t, 16)
        page = np.asarray(p2)[2]               # pool page id 2
        np.testing.assert_array_equal(page[:, :, :, 2:, :], 0.0)
        np.testing.assert_array_equal(
            page[:, :, :, :2, :], np.asarray(dense)[:, :, 0, :, 16:18, :])


# ---------------------------------------------------------------------------
# scheduler
# ---------------------------------------------------------------------------

class TestScheduler:
    def test_queue_overflow_rejects(self, metrics):
        s = serving.Scheduler(max_queue=2)
        s.submit(serving.GenerationRequest(PROMPTS[0]))
        s.submit(serving.GenerationRequest(PROMPTS[1]))
        with pytest.raises(serving.QueueFull):
            s.submit(serving.GenerationRequest(PROMPTS[2]))
        snap = obs.snapshot()
        assert snap["serving.requests_total"]["status=rejected"] == 1
        assert s.queue_depth == 2

    def test_fifo_no_slip_ahead(self):
        s = serving.Scheduler()
        big = serving.GenerationRequest(PROMPTS[4])     # head
        small = serving.GenerationRequest(PROMPTS[3])
        s.submit(big), s.submit(small)
        # head does not fit -> nothing admitted, even though `small` would
        taken = s.next_admissions(
            2, lambda r: r.request_id != big.request_id)
        assert taken == [] and s.queue_depth == 2

    def test_budget_policy_bounds_prefill_tokens(self):
        s = serving.Scheduler(policy="budget", prefill_token_budget=12)
        for p in PROMPTS[:3]:                           # 8 + 8 + 8 tokens
            s.submit(serving.GenerationRequest(p))
        taken = s.next_admissions(3, lambda r: True)
        assert len(taken) == 1                          # 8 + 8 > 12
        taken = s.next_admissions(3, lambda r: True)
        assert len(taken) == 1
        # the first request always passes, even over budget: progress
        s2 = serving.Scheduler(policy="budget", prefill_token_budget=4)
        s2.submit(serving.GenerationRequest(PROMPTS[0]))
        assert len(s2.next_admissions(1, lambda r: True)) == 1

    def test_budget_policy_validation(self):
        with pytest.raises(ValueError):
            serving.Scheduler(policy="budget")
        with pytest.raises(ValueError):
            serving.Scheduler(policy="wrfq")

    def test_cancel_queued_resolves_future(self, metrics):
        s = serving.Scheduler()
        req = serving.GenerationRequest(PROMPTS[0])
        fut = s.submit(req)
        assert s.cancel(req.request_id) is True
        res = fut.result(timeout=1)
        assert res.finish_reason == "cancelled" and res.tokens == []
        assert s.queue_depth == 0

    def test_cancel_active_is_deferred_to_engine(self):
        s = serving.Scheduler()
        assert s.cancel(12345) is True                  # flagged, not lost
        assert s.take_cancelled_active() == {12345}
        assert s.take_cancelled_active() == set()       # drained

    def test_requeue_preserves_order(self):
        s = serving.Scheduler()
        reqs = [serving.GenerationRequest(p) for p in PROMPTS[:3]]
        for r in reqs:
            s.submit(r)
        taken = s.next_admissions(2, lambda r: True)
        s.requeue(taken)
        order = [p.request.request_id
                 for p in s.next_admissions(3, lambda r: True)]
        assert order == [r.request_id for r in reqs]


# ---------------------------------------------------------------------------
# engine end-to-end
# ---------------------------------------------------------------------------

class TestEngine:
    def test_batched_matches_sequential(self, metrics):
        """5 requests (> max_batch=4, mixed prompt lengths and budgets)
        through the continuously-batched engine decode the exact sequences
        of the dense bs=1 loop — the scan_greedy_parity gate, on CPU."""
        n_new = [6, 4, 6, 5, 3]
        eng = make_engine()
        futs = [eng.submit(serving.GenerationRequest(p, max_new_tokens=n))
                for p, n in zip(PROMPTS, n_new)]
        eng.run()
        for p, n, f in zip(PROMPTS, n_new, futs):
            res = f.result(timeout=5)
            assert res.finish_reason == "length"
            assert res.tokens == dense_reference(p, n)
            assert res.ttft_s is not None and res.tpot_s is not None
        # all pages returned to the pool
        assert eng.kv.free_pages == eng.kv.config.num_pages - 1
        snap = obs.snapshot()
        assert snap["serving.requests_total"]["status=completed"] == 5
        assert snap["serving.tokens_total"] == sum(n_new)
        for hist in ("serving.ttft_seconds", "serving.tpot_seconds"):
            assert snap[hist]["count"] >= 1
        assert "serving.batch_utilization" in snap

    @pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
    def test_quantized_legs_match_reference(self, kv_dtype):
        """The storage-dtype legs keep greedy parity on the toy LM (logit
        gaps here dwarf the absmax grid error — the tolerance-level parity
        is pinned in test_int8_logits_tolerance_parity)."""
        eng = make_engine(kv_dtype=kv_dtype)
        assert eng.kv.pool.dtype == (jnp.int8 if kv_dtype == "int8"
                                     else jnp.bfloat16)
        assert (eng.kv.scales is not None) == (kv_dtype == "int8")
        futs = [eng.submit(serving.GenerationRequest(p, max_new_tokens=5))
                for p in PROMPTS[:3]]
        eng.run()
        for p, f in zip(PROMPTS, futs):
            assert f.result(timeout=5).tokens == dense_reference(p, 5)

    def test_env_knob_selects_kv_dtype(self, monkeypatch):
        """The field is the one way to say it (ISSUE 29): the environment
        name that mirrored it is not read, and a bogus value raises."""
        monkeypatch.setenv("PADDLE_TPU_KV_DTYPE", "int8")
        assert not make_engine().kv.config.quantized
        assert make_engine(kv_dtype="int8").kv.config.quantized
        for bogus in ("bogus", ""):
            with pytest.raises(ValueError, match="kv_dtype must be"):
                make_engine(kv_dtype=bogus)

    def test_admission_at_full_batch(self):
        """max_batch=1: the second request waits queued, joins the moment
        the first evicts, and still decodes its exact reference sequence
        — continuous batching across an eviction boundary."""
        eng = make_engine(max_batch=1)
        f0 = eng.submit(serving.GenerationRequest(PROMPTS[0],
                                                  max_new_tokens=3))
        f1 = eng.submit(serving.GenerationRequest(PROMPTS[1],
                                                  max_new_tokens=3))
        eng.step()
        assert eng.active_requests == 1 and eng.queue_depth == 1
        eng.run()
        assert f0.result(timeout=5).tokens == dense_reference(PROMPTS[0], 3)
        assert f1.result(timeout=5).tokens == dense_reference(PROMPTS[1], 3)

    def test_page_pool_gating(self):
        """A pool sized for ONE resident request serializes two: the
        second is admitted only after the first's pages free."""
        eng = make_engine(max_batch=4, num_pages=5)   # 4 usable = 1 slot
        n = M // 16                                    # whole-lifetime claim
        futs = [eng.submit(serving.GenerationRequest(
            PROMPTS[i], max_new_tokens=M - PROMPTS[i].size))
            for i in range(2)]
        eng.step()
        assert eng.active_requests == 1 and eng.queue_depth == 1
        assert eng.kv.free_pages == 4 - n
        eng.run()
        for f in futs:
            assert f.result(timeout=5).finish_reason == "length"
        assert eng.kv.free_pages == 4

    def test_admission_batch_no_overcommit_no_slip_ahead(self):
        """Pages must be reserved WITHIN one boundary's admission batch:
        6 usable pages, A and B need 4 each, C needs 2. B must stay
        queued (pool can't cover it beside A) and C must NOT slip past B
        even though C alone would fit — strict FIFO survives admission."""
        eng = make_engine(max_batch=4, num_pages=7)    # 6 usable
        fa = eng.submit(serving.GenerationRequest(      # 8+56=64 -> 4 pages
            PROMPTS[0], max_new_tokens=56))
        fb = eng.submit(serving.GenerationRequest(
            PROMPTS[1], max_new_tokens=56))
        fc = eng.submit(serving.GenerationRequest(      # 8+24=32 -> 2 pages
            PROMPTS[2], max_new_tokens=24))
        eng.step()
        assert eng.active_requests == 1                 # A alone
        assert eng.queue_depth == 2                     # B then C, in order
        assert eng.kv.free_pages == 2                   # no over-commit
        eng.run()
        assert fa.result(timeout=5).tokens == \
            dense_reference(PROMPTS[0], 56)
        assert fb.result(timeout=5).tokens == \
            dense_reference(PROMPTS[1], 56)
        assert fc.result(timeout=5).tokens == \
            dense_reference(PROMPTS[2], 24)
        assert eng.kv.free_pages == 6

    def test_submit_validation(self):
        eng = make_engine(max_queue=1)
        with pytest.raises(ValueError, match="max_len"):
            eng.submit(serving.GenerationRequest(
                np.zeros(M, np.int32), max_new_tokens=1))
        eng.submit(serving.GenerationRequest(PROMPTS[0], max_new_tokens=4))
        with pytest.raises(serving.QueueFull):
            eng.submit(serving.GenerationRequest(PROMPTS[1],
                                                 max_new_tokens=4))

    def test_zero_active_idle_step(self, metrics):
        eng = make_engine()
        assert eng.step() is False              # no device touch
        snap = obs.snapshot()
        assert snap.get("serving.steps_total") is None
        assert snap["serving.active_slots"] == 0

    def test_eviction_on_eos(self):
        ref = dense_reference(PROMPTS[0], 6)
        eos = ref[2]
        k = ref.index(eos)              # first occurrence stops the decode
        eng = make_engine()
        fut = eng.submit(serving.GenerationRequest(
            PROMPTS[0], max_new_tokens=6, eos_token_id=eos))
        eng.run()
        res = fut.result(timeout=5)
        assert res.finish_reason == "eos" and res.tokens == ref[:k + 1]
        assert eng.kv.free_pages == eng.kv.config.num_pages - 1

    def test_cancel_active_mid_flight(self):
        eng = make_engine()
        req0 = serving.GenerationRequest(PROMPTS[0], max_new_tokens=8)
        f0 = eng.submit(req0)
        f1 = eng.submit(serving.GenerationRequest(PROMPTS[1],
                                                  max_new_tokens=8))
        eng.step()                              # both admitted + 1 token
        eng.step()
        eng.cancel(req0.request_id)
        eng.run()
        res0 = f0.result(timeout=5)
        assert res0.finish_reason == "cancelled"
        assert 1 <= len(res0.tokens) < 8        # partial transcript kept
        assert res0.tokens == dense_reference(PROMPTS[0], 8)[:len(res0.tokens)]
        assert f1.result(timeout=5).tokens == dense_reference(PROMPTS[1], 8)
        assert eng.kv.free_pages == eng.kv.config.num_pages - 1

    def test_streaming_callback(self):
        seen = []
        eng = make_engine()
        req = serving.GenerationRequest(
            PROMPTS[0], max_new_tokens=4,
            stream=lambda rid, tok: seen.append((rid, tok)))
        fut = eng.submit(req)
        eng.run()
        assert [t for _, t in seen] == fut.result(timeout=5).tokens
        assert {rid for rid, _ in seen} == {req.request_id}

    def test_raising_stream_callback_fails_request_alone(self):
        """A raising callback is the REQUEST's failure: its Future gets
        the exception and its pages free; batchmates are untouched (the
        step loop — incl. the start() thread — must not unwind)."""
        class CbErr(RuntimeError):
            pass

        def bad(rid, tok):
            raise CbErr("user callback exploded")

        eng = make_engine()
        f0 = eng.submit(serving.GenerationRequest(
            PROMPTS[0], max_new_tokens=4, stream=bad))
        f1 = eng.submit(serving.GenerationRequest(PROMPTS[1],
                                                  max_new_tokens=4))
        eng.run()
        with pytest.raises(CbErr):
            f0.result(timeout=5)
        assert f1.result(timeout=5).tokens == dense_reference(PROMPTS[1], 4)
        assert eng.kv.free_pages == eng.kv.config.num_pages - 1

    def test_background_thread_serving(self):
        eng = make_engine()
        eng.start()
        try:
            fut = eng.submit(serving.GenerationRequest(PROMPTS[0],
                                                       max_new_tokens=4))
            assert fut.result(timeout=30).tokens == \
                dense_reference(PROMPTS[0], 4)
        finally:
            eng.stop()

    def test_warmup_compiles_every_bucket(self):
        eng = make_engine().warmup(prompt_lens=[8])
        # warmup must leave the pool allocatable and the engine clean
        assert eng.kv.free_pages == eng.kv.config.num_pages - 1
        fut = eng.submit(serving.GenerationRequest(PROMPTS[0],
                                                   max_new_tokens=3))
        eng.run()
        assert fut.result(timeout=5).tokens == dense_reference(PROMPTS[0], 3)


# ---------------------------------------------------------------------------
# fault injection: a faulted slot fails alone
# ---------------------------------------------------------------------------

class TestFaults:
    def _run_with_schedule(self, sched, n_new=5):
        eng = make_engine()
        with faults.installed(sched):
            futs = [eng.submit(serving.GenerationRequest(
                p, max_new_tokens=n_new)) for p in PROMPTS[:3]]
            eng.run()
        return eng, futs

    def test_faulted_slot_fails_alone(self, metrics):
        """serving.step fires once per (step, slot) in admission order:
        calls 2 and 5 target slot B at two consecutive boundaries — one
        retry, then failure. A and C must complete bit-identically."""
        sched = faults.FaultSchedule().error("serving.step", on=(2, 5))
        eng, (fa, fb, fc) = self._run_with_schedule(sched)
        with pytest.raises(faults.FaultInjected):
            fb.result(timeout=5)
        assert fa.result(timeout=5).tokens == dense_reference(PROMPTS[0], 5)
        assert fc.result(timeout=5).tokens == dense_reference(PROMPTS[2], 5)
        assert eng.kv.free_pages == eng.kv.config.num_pages - 1  # B freed
        snap = obs.snapshot()
        assert snap["serving.requests_total"]["status=failed"] == 1
        assert snap["serving.requests_total"]["status=completed"] == 2
        # determinism: same schedule => same (site, call, kind) trace
        trace = [t for t in sched.trace if t[0] == "serving.step"]
        assert trace == [("serving.step", 2, "error"),
                         ("serving.step", 5, "error")]

    def test_step_fault_retries_once_then_completes(self, metrics):
        """A single fault only delays its slot one boundary; the transcript
        is still exact (functional cache state — nothing half-written)."""
        sched = faults.FaultSchedule().error("serving.step", on=(2,))
        _, futs = self._run_with_schedule(sched)
        for p, f in zip(PROMPTS, futs):
            assert f.result(timeout=5).tokens == dense_reference(p, 5)
        assert obs.snapshot()["serving.step_retries_total"] == 1

    def test_admit_fault_retry_then_success(self, metrics):
        sched = faults.FaultSchedule().error("serving.admit", on=(1,))
        eng, futs = self._run_with_schedule(sched)
        for p, f in zip(PROMPTS, futs):
            assert f.result(timeout=5).tokens == dense_reference(p, 5)
        assert obs.snapshot()["serving.admit_retries_total"] == 1

    def test_admit_double_fault_fails_request_frees_pages(self, metrics):
        sched = faults.FaultSchedule().error("serving.admit", on=(1, 2))
        eng, (fa, fb, fc) = self._run_with_schedule(sched)
        with pytest.raises(faults.FaultInjected):
            fa.result(timeout=5)
        assert fb.result(timeout=5).tokens == dense_reference(PROMPTS[1], 5)
        assert fc.result(timeout=5).tokens == dense_reference(PROMPTS[2], 5)
        assert eng.kv.free_pages == eng.kv.config.num_pages - 1


# ---------------------------------------------------------------------------
# ISSUE 18: admission/drain containment — fixes found by the
# resource-discipline lint pass. An unexpected raise cutting through
# admission or drain must not strand futures, leak pages, or drop
# queued requests.
# ---------------------------------------------------------------------------

class TestAdmissionContainment:
    def test_admit_one_raise_fails_current_and_requeues_tail(self, metrics):
        eng = make_engine()
        futs = [eng.submit(serving.GenerationRequest(p, max_new_tokens=3))
                for p in PROMPTS[:3]]
        real = eng._admit_one
        calls = []

        def flaky(pending):
            calls.append(pending)
            if len(calls) == 2:
                raise RuntimeError("admission bug")
            return real(pending)

        eng._admit_one = flaky
        try:
            with pytest.raises(RuntimeError, match="admission bug"):
                eng._admit()
        finally:
            eng._admit_one = real
        # first admitted, second's future carries the bug, third went
        # back in order — nothing stranded, nothing dropped
        assert len(eng._slots) == 1 and not futs[0].done()
        with pytest.raises(RuntimeError, match="admission bug"):
            futs[1].result(timeout=1)
        assert not futs[2].done()
        assert eng._admit() is True
        assert len(eng._slots) == 2

    def test_host_tail_raise_is_contained_as_failed_admission(
            self, metrics, monkeypatch):
        eng = make_engine()
        free0 = eng.kv.free_pages

        def wedged(*a, **k):
            raise RuntimeError("host sync wedged")

        monkeypatch.setattr(eng.programs, "_adopt", wedged)
        fut = eng.submit(serving.GenerationRequest(PROMPTS[0],
                                                   max_new_tokens=3))
        # the pool swap / first-token host read raising is just another
        # failed admission: pages freed, future resolved, no slot
        assert eng._admit() is False
        with pytest.raises(RuntimeError, match="host sync wedged"):
            fut.result(timeout=1)
        assert eng.kv.free_pages == free0 and eng._slots == []
        assert obs.snapshot()["serving.requests_total"][
            "status=failed"] == 1.0

    def test_drain_fail_settles_futures_before_telemetry(
            self, metrics, monkeypatch):
        from paddle_tpu.serving import engine as engine_mod
        eng = make_engine()
        fut = eng.submit(serving.GenerationRequest(PROMPTS[0],
                                                   max_new_tokens=3))

        class _DownObs:
            def __getattr__(self, name):
                return getattr(obs, name)

            def inc(self, *a, **k):
                raise RuntimeError("metrics sink down")

        monkeypatch.setattr(engine_mod, "_obs", _DownObs())
        # the straggler sweep's contract is "no Future stays stranded":
        # the queued request's future is settled even though the very
        # first telemetry call blows up
        with pytest.raises(RuntimeError, match="metrics sink down"):
            eng._resolve_stragglers("fail")
        assert isinstance(fut.exception(timeout=1), serving.EngineStopped)


# ---------------------------------------------------------------------------
# ISSUE 8: deadlines, load shedding, queue-wait accounting
# ---------------------------------------------------------------------------

class TestDeadlinesAndShedding:
    def test_request_budget_validation(self):
        with pytest.raises(ValueError, match="deadline_s"):
            serving.GenerationRequest(PROMPTS[0], deadline_s=0.0)
        with pytest.raises(ValueError, match="ttft_budget_s"):
            serving.GenerationRequest(PROMPTS[0], ttft_budget_s=-1.0)

    def test_queue_full_message_has_depth_and_capacity(self, metrics):
        s = serving.Scheduler(max_queue=2)
        s.submit(serving.GenerationRequest(PROMPTS[0]))
        s.submit(serving.GenerationRequest(PROMPTS[1]))
        with pytest.raises(serving.QueueFull, match=r"2/2"):
            s.submit(serving.GenerationRequest(PROMPTS[2]))
        snap = obs.snapshot()
        assert snap["serving.rejected_total"]["reason=queue_full"] == 1

    def test_queue_wait_histogram_recorded_on_every_admission(self, metrics):
        eng = make_engine()
        futs = [eng.submit(serving.GenerationRequest(p, max_new_tokens=3))
                for p in PROMPTS[:3]]
        eng.run()
        for f in futs:
            f.result(timeout=5)
        snap = obs.snapshot()
        assert snap["serving.queue_wait_seconds"]["count"] == 3

    def test_expired_in_queue_sheds_batchmates_bit_identical(self, metrics):
        """Acceptance (a): under a scripted schedule, the expired request
        sheds with a typed DeadlineExceeded at the admission boundary —
        never mid-batch — and its batchmates' outputs are bit-identical
        to the no-fault run."""
        ref = {i: dense_reference(PROMPTS[i], 5) for i in (0, 2)}
        # the scripted delay holds admission long enough for B's TTFT
        # budget to expire while it queues behind A (max_batch=1)
        sched = faults.FaultSchedule().delay("serving.admit", on=(1,),
                                             seconds=0.15)
        eng = make_engine(max_batch=1)
        fa = eng.submit(serving.GenerationRequest(PROMPTS[0],
                                                  max_new_tokens=5))
        fb = eng.submit(serving.GenerationRequest(
            PROMPTS[1], max_new_tokens=5, ttft_budget_s=0.05))
        fc = eng.submit(serving.GenerationRequest(PROMPTS[2],
                                                  max_new_tokens=5))
        with faults.installed(sched):
            eng.run()
        with pytest.raises(serving.DeadlineExceeded, match="expired in "
                                                           "queue"):
            fb.result(timeout=5)
        assert fa.result(timeout=5).tokens == ref[0]
        assert fc.result(timeout=5).tokens == ref[2]
        assert eng.kv.free_pages == eng.kv.config.num_pages - 1
        snap = obs.snapshot()
        assert snap["serving.rejected_total"]["reason=deadline"] == 1
        assert snap["serving.requests_total"]["status=shed"] == 1
        assert snap["serving.requests_total"]["status=completed"] == 2
        # determinism: same scripted schedule => same trace
        assert sched.trace == [("serving.admit", 1, "delay")]

    def test_shed_on_arrival_when_estimated_wait_exceeds_budget(
            self, metrics):
        import time as _t
        s = serving.Scheduler()
        s._ewma_interval = 5.0              # recent drain: 5 s per pop
        s.submit(serving.GenerationRequest(PROMPTS[0]),
                 submit_time=_t.monotonic())   # no budget: queued
        with pytest.raises(serving.DeadlineExceeded, match="shed on "
                                                           "arrival"):
            s.submit(serving.GenerationRequest(PROMPTS[1], deadline_s=1.0),
                     submit_time=_t.monotonic())
        assert s.queue_depth == 1
        snap = obs.snapshot()
        assert snap["serving.rejected_total"]["reason=shed"] == 1
        # a request with headroom still queues
        s.submit(serving.GenerationRequest(PROMPTS[2], deadline_s=60.0),
                 submit_time=_t.monotonic())
        assert s.queue_depth == 2

    def test_max_queue_wait_hard_cap_sheds(self, metrics):
        import time as _t
        s = serving.Scheduler(max_queue_wait_s=0.01)
        fut = s.submit(serving.GenerationRequest(PROMPTS[0]),
                       submit_time=_t.monotonic() - 1.0)   # waited 1 s
        assert s.next_admissions(4, lambda r: True) == []
        with pytest.raises(serving.DeadlineExceeded, match="max_queue_wait"):
            fut.result(timeout=1)
        assert obs.snapshot()["serving.rejected_total"]["reason=shed"] == 1

    def test_requeued_replay_not_shed_by_met_ttft_or_queue_cap(
            self, metrics):
        """Queue-wait accounting must not charge a replayed request for
        its time DECODING: a met TTFT budget cannot expire retroactively,
        and max_queue_wait_s measures this queue stint (queued_at resets
        on requeue), not request age."""
        import time as _t
        from concurrent.futures import Future
        from paddle_tpu.serving.scheduler import _Pending
        s = serving.Scheduler(max_queue_wait_s=0.5)
        old = _t.monotonic() - 10.0       # "admitted 10 s ago, decoding"
        p = _Pending(serving.GenerationRequest(PROMPTS[0],
                                               ttft_budget_s=1.0),
                     Future(), submit_time=old, queued_at=old,
                     ttft_done=True, replays=1, replay_tokens=[3, 4])
        s.requeue([p])                    # crash-recovery re-queue NOW
        assert s.shed_expired() == 0      # neither budget fires
        assert s.queue_depth == 1
        # an end-to-end deadline_s, by contrast, still counts total age
        q = _Pending(serving.GenerationRequest(PROMPTS[1], deadline_s=5.0),
                     Future(), submit_time=old, queued_at=old,
                     ttft_done=True, replays=1)
        s.requeue([q])
        assert s.shed_expired() == 1
        with pytest.raises(serving.DeadlineExceeded):
            q.future.result(timeout=1)

    def test_ewma_wait_model_not_poisoned_by_idle_gap(self):
        """Draining the queue drops the pop-interval reference: the first
        admission after an idle lull must not fold the idle time into the
        drain-rate estimate and shed healthy traffic."""
        s = serving.Scheduler()
        for p in PROMPTS[:2]:
            s.submit(serving.GenerationRequest(p))
        s.next_admissions(2, lambda r: True)   # queue drained
        # BOTH halves of the wait model reset: a drain rate learned under
        # an earlier load regime must not shed the next burst's first
        # requests against an empty queue
        assert s._last_pop_t is None and s._ewma_interval is None
        # ... idle lull happens here; next burst starts a fresh estimate
        s.submit(serving.GenerationRequest(PROMPTS[2]))
        s.next_admissions(1, lambda r: True)
        assert s._ewma_interval is None or s._ewma_interval < 1.0

    def test_ewma_measures_per_request_interval_on_batched_pops(self):
        """One EWMA sample per boundary, dt divided by the pop count: a
        4-wide admission 8 s after the last boundary means ~2 s per
        request — NOT one 8 s sample followed by three dt=0 samples that
        collapse the estimate and disarm shed-on-arrival under exactly
        the batched admission the engine is built for."""
        import time as _t
        s = serving.Scheduler()
        for p in PROMPTS:                       # 5 queued; pop 4, 1 stays
            s.submit(serving.GenerationRequest(p))
        s._last_pop_t = _t.monotonic() - 8.0    # last boundary: 8 s ago
        taken = s.next_admissions(4, lambda r: True)
        assert len(taken) == 4 and s.queue_depth == 1
        assert 1.5 < s._ewma_interval < 2.5     # ~8/4, not ~0

    def test_withdraw_removes_silently(self, metrics):
        s = serving.Scheduler()
        req = serving.GenerationRequest(PROMPTS[0])
        fut = s.submit(req)
        pend = s.withdraw(req.request_id)
        assert pend is not None and pend.future is fut
        assert not fut.done() and s.queue_depth == 0
        assert s.withdraw(req.request_id) is None      # already gone
        snap = obs.snapshot()
        assert "serving.requests_total" not in snap    # no accounting

    def test_env_knobs_resolve_into_config(self, monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_SERVING_MAX_QUEUE_WAIT", "0.25")
        monkeypatch.setenv("PADDLE_TPU_SERVING_WATCHDOG_S", "1.5")
        cfg = serving.ServingConfig(num_layers=L, num_heads=H, head_dim=D,
                                    max_len=M, max_batch=1, buckets=(1,))
        assert cfg.max_queue_wait_s == 0.25 and cfg.watchdog_s == 1.5
        # explicit 0 forces OFF even with the env set
        cfg0 = serving.ServingConfig(num_layers=L, num_heads=H, head_dim=D,
                                     max_len=M, max_batch=1, buckets=(1,),
                                     watchdog_s=0, max_queue_wait_s=0)
        assert cfg0.max_queue_wait_s is None and cfg0.watchdog_s is None

    def test_deadline_scope_propagates_request_deadline(self):
        from concurrent.futures import Future
        from paddle_tpu.resilience import current_deadline
        from paddle_tpu.serving.scheduler import _Pending
        eng = make_engine()
        p = _Pending(serving.GenerationRequest(PROMPTS[0], deadline_s=5.0),
                     Future(), submit_time=100.0)
        with eng._deadline_ctx([p]):
            assert current_deadline() == pytest.approx(105.0)
        q = _Pending(serving.GenerationRequest(PROMPTS[1]), Future(),
                     submit_time=100.0)
        with eng._deadline_ctx([q]):
            assert current_deadline() is None
        # batched: the tightest deadline governs
        r = _Pending(serving.GenerationRequest(PROMPTS[2], deadline_s=2.0),
                     Future(), submit_time=100.0)
        with eng._deadline_ctx([p, q, r]):
            assert current_deadline() == pytest.approx(102.0)


# ---------------------------------------------------------------------------
# ISSUE 8: watchdog + bounded prefill replay
# ---------------------------------------------------------------------------

class TestWatchdogRecovery:
    def test_watchdog_unit_trip_and_zombie(self, metrics):
        import time as _t
        wd = serving.StepWatchdog(0.03)
        try:
            gen = wd.arm()
            _t.sleep(0.15)                   # > 2x budget: hung then zombie
            assert wd.disarm(gen) == "zombie"
            gen2 = wd.arm()
            assert wd.disarm(gen2) is None   # came back in time
        finally:
            wd.stop()
        snap = obs.snapshot()
        assert snap["serving.watchdog_trips_total"]["kind=hung"] == 1
        assert snap["serving.watchdog_trips_total"]["kind=zombie"] == 1

    def test_watchdog_trip_recovers_via_replay(self, metrics):
        """Acceptance (b): a hung step (scripted delay at the
        serving.watchdog seam) trips the watchdog; its outputs are
        abandoned and BOTH slots recover through bounded prefill replay —
        the full transcripts stay bit-identical, no future strands, the
        pool free-list returns to full."""
        # budget generous vs CPU scheduling noise (a GC pause must not
        # look hung), delay 4x the budget so the trip is unambiguous;
        # warmup precompiles the decode buckets so a cold compile (no
        # shared disk cache since the conftest change) cannot read as a
        # phantom hung step
        sched = faults.FaultSchedule().delay("serving.watchdog", on=(2,),
                                             seconds=1.0)
        eng = make_engine(watchdog_s=0.25, max_replays=1).warmup()
        with faults.installed(sched):
            futs = [eng.submit(serving.GenerationRequest(
                p, max_new_tokens=4)) for p in PROMPTS[:2]]
            eng.run()
        eng.stop()                        # reap the watchdog poll thread
        for p, f in zip(PROMPTS, futs):
            assert f.result(timeout=5).tokens == dense_reference(p, 4)
        assert eng.active_requests == 0 and eng.queue_depth == 0
        assert eng.kv.free_pages == eng.kv.config.num_pages - 1
        assert eng.kv.outstanding_pages == 0
        snap = obs.snapshot()
        assert snap["serving.watchdog_trips_total"]["kind=hung"] >= 1
        assert snap["serving.replays_total"] == 2
        assert snap["serving.requests_total"]["status=completed"] == 2
        assert sched.trace == [("serving.watchdog", 2, "delay")]

    def test_device_fault_single_retry_still_succeeds(self, metrics):
        sched = faults.FaultSchedule().error("serving.watchdog", on=(1,))
        eng = make_engine()
        with faults.installed(sched):
            futs = [eng.submit(serving.GenerationRequest(
                p, max_new_tokens=4)) for p in PROMPTS[:2]]
            eng.run()
        for p, f in zip(PROMPTS, futs):
            assert f.result(timeout=5).tokens == dense_reference(p, 4)
        assert obs.snapshot()["serving.step_retries_total"] == 1
        assert obs.snapshot().get("serving.replays_total") is None

    def test_device_double_fault_replays_not_fails(self, metrics):
        """The crash-recovery contract change: an unrecoverable batched
        step (fault + failed retry) used to fail EVERY in-flight request;
        now the slots replay (prompt + tokens so far) and complete
        bit-identically."""
        sched = faults.FaultSchedule().error("serving.watchdog", on=(2, 3))
        eng = make_engine(max_replays=1)
        with faults.installed(sched):
            futs = [eng.submit(serving.GenerationRequest(
                p, max_new_tokens=4)) for p in PROMPTS[:2]]
            eng.run()
        for p, f in zip(PROMPTS, futs):
            assert f.result(timeout=5).tokens == dense_reference(p, 4)
        snap = obs.snapshot()
        assert snap["serving.replays_total"] == 2
        assert snap["serving.requests_total"]["status=completed"] == 2
        assert eng.kv.free_pages == eng.kv.config.num_pages - 1

    def test_replay_budget_exhausted_fails_with_pages_reclaimed(
            self, metrics):
        sched = faults.FaultSchedule().error("serving.watchdog",
                                             on=(2, 3, 4, 5))
        eng = make_engine(max_replays=0)      # no replay budget at all
        with faults.installed(sched):
            futs = [eng.submit(serving.GenerationRequest(
                p, max_new_tokens=4)) for p in PROMPTS[:2]]
            eng.run()
        for f in futs:
            with pytest.raises(faults.FaultInjected):
                f.result(timeout=5)
        assert eng.kv.free_pages == eng.kv.config.num_pages - 1
        snap = obs.snapshot()
        assert snap["serving.requests_total"]["status=failed"] == 2
        assert snap.get("serving.replays_total") is None


# ---------------------------------------------------------------------------
# ISSUE 8: graceful drain
# ---------------------------------------------------------------------------

class TestDrain:
    def test_stop_drain_completes_inflight_and_is_idempotent(self, metrics):
        """Acceptance (c): drain finishes the admitted sequences, resolves
        everything, returns every page, and a second stop is a no-op."""
        eng = make_engine()
        futs = [eng.submit(serving.GenerationRequest(p, max_new_tokens=5))
                for p in PROMPTS[:3]]
        eng.step()                        # all three admitted
        assert eng.active_requests == 3
        eng.stop(drain=True, timeout=30)
        for p, f in zip(PROMPTS, futs):
            assert f.result(timeout=1).tokens == dense_reference(p, 5)
        assert eng.active_requests == 0 and eng.queue_depth == 0
        assert eng.kv.free_pages == eng.kv.config.num_pages - 1
        eng.stop(drain=True, timeout=1)   # idempotent: nothing to resolve
        eng.stop()
        snap = obs.snapshot()
        assert snap["serving.requests_total"]["status=completed"] == 3

    def test_stop_drain_from_background_thread(self):
        import threading as _th
        seen = _th.Event()
        eng = make_engine()
        eng.start()
        fut = eng.submit(serving.GenerationRequest(
            PROMPTS[0], max_new_tokens=4,
            stream=lambda rid, tok: seen.set()))
        assert seen.wait(timeout=30)      # admitted before we drain
        eng.stop(drain=True, timeout=30)
        assert fut.result(timeout=1).tokens == dense_reference(PROMPTS[0], 4)
        assert eng.kv.free_pages == eng.kv.config.num_pages - 1

    def test_submit_while_draining_raises(self, metrics):
        eng = make_engine()
        eng.stop(drain=True, timeout=1)
        with pytest.raises(serving.EngineStopped):
            eng.submit(serving.GenerationRequest(PROMPTS[0]))
        assert obs.snapshot()["serving.rejected_total"]["reason=shed"] == 1

    def test_drain_timeout_fail_resolves_every_future(self, metrics):
        """timeout=0 with work in flight: the straggler fails with
        DrainTimeout, the never-admitted request with EngineStopped — no
        stranded futures, no leaked pages."""
        eng = make_engine(max_batch=1)
        f0 = eng.submit(serving.GenerationRequest(PROMPTS[0],
                                                  max_new_tokens=40))
        f1 = eng.submit(serving.GenerationRequest(PROMPTS[1],
                                                  max_new_tokens=40))
        eng.step()                        # A admitted, B queued
        eng.stop(drain=True, timeout=0)
        with pytest.raises(serving.DrainTimeout):
            f0.result(timeout=1)
        with pytest.raises(serving.EngineStopped):
            f1.result(timeout=1)
        assert eng.kv.free_pages == eng.kv.config.num_pages - 1
        snap = obs.snapshot()
        assert snap["serving.requests_total"]["status=failed"] == 1
        assert snap["serving.requests_total"]["status=shed"] == 1

    def test_stop_without_budget_is_bounded_despite_wedged_loop(
            self, monkeypatch):
        """ISSUE 19 regression (surfaced by the unbounded-wait lint rule):
        ``stop()`` with NO drain budget must still return when the loop
        thread is wedged inside a hung compiled call — the join is
        bounded by PADDLE_TPU_STOP_JOIN_S and the zombie abandoned,
        exactly as the budgeted path always promised."""
        import threading as _th
        import time as _t
        monkeypatch.setenv("PADDLE_TPU_STOP_JOIN_S", "0.2")
        eng = make_engine()
        release = _th.Event()
        wedged = _th.Thread(target=release.wait, daemon=True)
        wedged.start()
        eng._thread = wedged        # stands in for a wedged loop thread
        t0 = _t.monotonic()
        eng.stop()                  # timeout=None: used to join forever
        assert _t.monotonic() - t0 < 5.0
        assert eng._thread is None  # the zombie was abandoned
        release.set()
        wedged.join(timeout=1)

    def test_run_after_requeue_drain_resumes_not_spins(self):
        """run() clears the draining latch like start() does: the offline
        drive mode after stop(drain=True, on_timeout='requeue') must
        resume the requeued work, not refuse admission forever."""
        eng = make_engine(max_batch=1)
        f0 = eng.submit(serving.GenerationRequest(PROMPTS[0],
                                                  max_new_tokens=6))
        eng.step()
        eng.stop(drain=True, timeout=0, on_timeout="requeue")
        assert not f0.done() and eng.queue_depth == 1
        eng.run()                         # would busy-spin if still latched
        assert f0.result(timeout=1).tokens == dense_reference(PROMPTS[0], 6)
        assert eng.kv.outstanding_pages == 0

    def test_drain_timeout_requeue_then_restart_resumes_bit_identical(self):
        eng = make_engine(max_batch=1)
        f0 = eng.submit(serving.GenerationRequest(PROMPTS[0],
                                                  max_new_tokens=6))
        f1 = eng.submit(serving.GenerationRequest(PROMPTS[1],
                                                  max_new_tokens=6))
        eng.step()                        # A admitted + 1 token
        eng.stop(drain=True, timeout=0, on_timeout="requeue")
        assert not f0.done() and not f1.done()
        assert eng.kv.free_pages == eng.kv.config.num_pages - 1
        assert eng.queue_depth == 2      # A (with its replay token) then B
        eng.start()                       # clears the draining latch
        try:
            assert f0.result(timeout=30).tokens == \
                dense_reference(PROMPTS[0], 6)
            assert f1.result(timeout=30).tokens == \
                dense_reference(PROMPTS[1], 6)
        finally:
            eng.stop()
        assert eng.kv.free_pages == eng.kv.config.num_pages - 1

    def test_drain_readmits_crash_recovery_requeues(self, metrics):
        """A double-faulted step DURING a graceful drain must not turn an
        admitted, recoverable request into a never-admitted EngineStopped:
        the drain re-admits crash-recovery requeues (replay_only
        admission) and finishes the sequence."""
        # call 1 fires at the first decode attempt; 2 at its retry — the
        # slot is requeued with replay tokens while the drain is running
        sched = faults.FaultSchedule().error("serving.watchdog", on=(1, 2))
        eng = make_engine(max_batch=1, max_replays=1)
        fut = eng.submit(serving.GenerationRequest(PROMPTS[0],
                                                   max_new_tokens=5))
        eng.step()                        # admitted (prefill + 1st token)
        with faults.installed(sched):
            eng.stop(drain=True, timeout=30)
        assert fut.result(timeout=1).tokens == dense_reference(PROMPTS[0], 5)
        assert eng.kv.outstanding_pages == 0
        snap = obs.snapshot()
        assert snap["serving.replays_total"] == 1
        assert snap["serving.requests_total"]["status=completed"] == 1
        assert sched.trace == [("serving.watchdog", 1, "error"),
                               ("serving.watchdog", 2, "error")]

    def test_drain_zero_budget_fails_replay_as_drain_timeout(self, metrics):
        """If the drain budget runs out before a crash-recovery requeue
        re-admits, its Future fails with DrainTimeout / status=failed —
        it was admitted once, so reporting it as never-admitted overload
        shed (EngineStopped / status=shed) would lie to the operator."""
        sched = faults.FaultSchedule().error("serving.watchdog", on=(1, 2))
        eng = make_engine(max_batch=1, max_replays=1)
        fut = eng.submit(serving.GenerationRequest(PROMPTS[0],
                                                   max_new_tokens=5))
        eng.step()
        with faults.installed(sched):
            eng.step()                    # fault + failed retry: requeued
        assert eng.queue_depth == 1 and eng.active_requests == 0
        eng.stop(drain=True, timeout=0)
        with pytest.raises(serving.DrainTimeout, match="replay"):
            fut.result(timeout=1)
        snap = obs.snapshot()
        assert snap["serving.requests_total"]["status=failed"] == 1
        assert "status=shed" not in snap.get("serving.requests_total", {})
        assert eng.kv.outstanding_pages == 0

    def test_stop_from_stream_callback_raises_not_wedges(self):
        """stop() on the engine step thread would be the loop asking
        itself to drain — with no timeout it would hang forever. The
        guard raises instead; per the stream-callback contract the error
        fails THAT request alone and the loop survives."""
        eng = make_engine()
        eng.start()
        try:
            fut = eng.submit(serving.GenerationRequest(
                PROMPTS[0], max_new_tokens=4,
                stream=lambda rid, tok: eng.stop(drain=True)))
            with pytest.raises(RuntimeError, match="step thread"):
                fut.result(timeout=30)
            # the loop survived the callback's failure: new work completes
            f2 = eng.submit(serving.GenerationRequest(PROMPTS[1],
                                                      max_new_tokens=4))
            assert f2.result(timeout=30).tokens == \
                dense_reference(PROMPTS[1], 4)
        finally:
            eng.stop()
        assert eng.kv.outstanding_pages == 0

    @pytest.mark.slow
    def test_stop_join_bounded_when_step_wedged(self, metrics):
        """Acceptance hardening: stop(drain=True, timeout=...) must
        return even when the loop thread is wedged inside a hung compiled
        call (the exact zombie case the watchdog classifies) — bounded
        join, stragglers resolved without it, late return abandoned
        without double-free."""
        import time as _t
        sched = faults.FaultSchedule().delay("serving.watchdog", on=(2,),
                                             seconds=3.0)
        eng = make_engine(max_batch=1)
        with faults.installed(sched):
            eng.start()
            fut = eng.submit(serving.GenerationRequest(PROMPTS[0],
                                                       max_new_tokens=40))
            while not fut.done() and eng.active_requests == 0:
                _t.sleep(0.01)            # admitted before we drain
            t0 = _t.monotonic()
            eng.stop(drain=True, timeout=0.2)
            # returned well before the 3 s hang released (0.2 budget +
            # 1 s join grace + slack)
            assert _t.monotonic() - t0 < 2.5
            with pytest.raises(serving.DrainTimeout):
                fut.result(timeout=1)
            assert eng.kv.outstanding_pages == 0
            _t.sleep(3.2)                 # let the wedged step return
        # the late return was abandoned: no double-free, no re-resolution
        assert eng.kv.outstanding_pages == 0
        assert fut.exception(timeout=0) is not None

    def test_injected_drain_fault_degrades_to_immediate_stop(self, metrics):
        """An error at the serving.drain seam must not strand anything:
        the drain degrades to an immediate stop and still resolves every
        future."""
        sched = faults.FaultSchedule().error("serving.drain", on=(1,))
        eng = make_engine(max_batch=1)
        f0 = eng.submit(serving.GenerationRequest(PROMPTS[0],
                                                  max_new_tokens=40))
        eng.step()
        with faults.installed(sched):
            eng.stop(drain=True, timeout=30)
        with pytest.raises(serving.DrainTimeout):
            f0.result(timeout=1)
        assert eng.kv.free_pages == eng.kv.config.num_pages - 1
        assert sched.trace == [("serving.drain", 1, "error")]


# ---------------------------------------------------------------------------
# ISSUE 28: one decode step in flight ahead of the host's read
# ---------------------------------------------------------------------------

TIERS = ["dense", "kernel"]


def _request(prompt, n_new, streamed=None, **kw):
    stream = None if streamed is None else \
        (lambda rid, tok: streamed.setdefault(rid, []).append(tok))
    return serving.GenerationRequest(np.asarray(prompt, np.int32),
                                     max_new_tokens=n_new, stream=stream,
                                     **kw)


def one_at_a_time(eng, prompts, n_new):
    """Each request alone through ``eng``, the next only when the one
    before has finished: the streams a batched run must reproduce."""
    out = []
    for p, n in zip(prompts, n_new):
        fut = eng.submit(_request(p, n))
        eng.run()
        out.append(fut.result(timeout=0).tokens)
    return out


def decode_programs(eng):
    """How many decode executables the engine has compiled."""
    return sum(entry[0]._jitted._cache_size()
               for entry in eng.programs.decode_program.program_cache.values())


def tier_prompts(vocab, lens=(8, 5, 11, 7)):
    rng = np.random.default_rng(28)
    return [rng.integers(0, vocab, (n,), dtype=np.int32) for n in lens]


@pytest.mark.parametrize("tier", TIERS)
class TestDecodeAhead:
    def test_mixed_run_streams_equal_one_at_a_time(self, tier, tier_engine,
                                                   metrics):
        """Admissions mid-flight, different lengths, the bucket going
        1 -> 4 -> 2 -> 1 -> 2 -> 1: every stream is the one the request
        decodes alone, nearly every step was launched before the step
        ahead of it was read, and the run compiled one decode program per
        bucket."""
        prompts = tier_prompts(tier_engine.vocab)
        n_new = [16, 4, 7, 5]
        eng = tier_engine(tier)
        streamed = {}
        reqs = [_request(p, n, streamed) for p, n in zip(prompts, n_new)]
        futs = [eng.submit(reqs[0])]
        for _ in range(3):                  # A alone: bucket 1, pipe full
            eng.step()
        assert eng._flight is not None and eng._flight.bucket == 1
        futs += [eng.submit(reqs[1]), eng.submit(reqs[2])]
        eng.step()                          # B and C join: bucket 4
        assert eng._flight.bucket == 4 and eng._flight.ahead == 1
        while eng.active_requests > 1:      # B, then C, leave: 2, then 1
            eng.step()
        assert eng._flight.bucket == 1
        futs.append(eng.submit(reqs[3]))    # D joins: up to 2 again
        eng.run()
        assert eng._flight is None
        tokens = [f.result(timeout=0).tokens for f in futs]
        assert tokens == one_at_a_time(eng, prompts, n_new)
        assert [streamed[r.request_id] for r in reqs] == tokens
        assert decode_programs(eng) == len(eng.config.buckets) == 3
        snap = obs.snapshot()
        # every batched step but the pipe's first was launched ahead (the
        # one-at-a-time runs after it add one first boundary each)
        assert snap["serving.decode_ahead_steps_total"] == \
            snap["serving.steps_total"] - 1 - len(prompts)
        assert snap.get("serving.decode_discarded_rows_total") is None
        assert eng.kv.outstanding_pages == 0

    def test_eos_with_a_step_in_flight(self, tier, tier_engine, metrics):
        """A request that ends by ``eos_token_id`` is already in the next
        step: that row is discarded on read — nothing past the eos is
        streamed or counted, no page leaks, and its batchmate is
        untouched."""
        prompts = tier_prompts(tier_engine.vocab)[:2]
        eng = tier_engine(tier)
        ref = one_at_a_time(eng, prompts, [10, 10])
        k = next(i for i in range(2, 9) if ref[0][i] not in ref[0][:i])
        obs.reset()
        streamed = {}
        reqs = [_request(prompts[0], 10, streamed, eos_token_id=ref[0][k]),
                _request(prompts[1], 10, streamed)]
        futs = [eng.submit(r) for r in reqs]
        eng.run()
        res = [f.result(timeout=0) for f in futs]
        assert res[0].finish_reason == "eos"
        assert res[0].tokens == ref[0][:k + 1]
        assert res[1].tokens == ref[1]
        assert [streamed[r.request_id] for r in reqs] == \
            [r.tokens for r in res]
        snap = obs.snapshot()
        assert snap["serving.decode_discarded_rows_total"] == 1
        assert snap["serving.tokens_total"] == k + 1 + 10
        assert eng._flight is None and eng.kv.outstanding_pages == 0

    def test_nothing_outstanding_after_run_and_stop(self, tier,
                                                    tier_engine):
        """``run()`` returning, ``stop()`` pausing a live loop and a
        drained ``stop()`` each leave no program unread and a live pool;
        a paused engine resumes where it stood."""
        prompts = tier_prompts(tier_engine.vocab)[:2]
        eng = tier_engine(tier)
        ref = one_at_a_time(eng, prompts, [12, 12])
        assert eng._flight is None and not eng.kv.pool.is_deleted()

        import threading
        seen = threading.Event()
        futs = [eng.submit(_request(prompts[0], 12)),
                eng.submit(serving.GenerationRequest(
                    prompts[1], max_new_tokens=12,
                    stream=lambda rid, tok: seen.set()))]
        eng.start()
        assert seen.wait(timeout=60)
        eng.stop()                          # pause: slots stay
        assert eng._flight is None and not eng.kv.pool.is_deleted()
        assert all(s.ahead == 0 for s in eng._slots)
        np.asarray(eng.kv.pool)             # readable: nothing holds it
        eng.start()
        try:
            assert [f.result(timeout=60).tokens for f in futs] == ref
        finally:
            eng.stop(drain=True, timeout=30)
        assert eng._flight is None and not eng.kv.pool.is_deleted()
        assert eng.kv.outstanding_pages == 0

    def test_a_slot_that_sat_a_step_out_takes_its_token_from_the_host(
            self, tier, tier_engine, metrics):
        """A ``serving.step`` fault keeps one slot out of one step: its
        next input token has been read by then and comes from the host
        while its batchmate's stays on the device — same streams."""
        prompts = tier_prompts(tier_engine.vocab)[:2]
        eng = tier_engine(tier)
        ref = one_at_a_time(eng, prompts, [8, 8])
        sched = faults.FaultSchedule().error("serving.step", on=(6,))
        futs = [eng.submit(_request(p, 8)) for p in prompts]
        with faults.installed(sched):
            eng.run()
        assert [f.result(timeout=0).tokens for f in futs] == ref
        assert sched.trace == [("serving.step", 6, "error")]
        assert obs.snapshot()["serving.step_retries_total"] == 1
