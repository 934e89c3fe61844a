"""Paged-attention decode kernel (ISSUE 13) — stream live pages, never
materialize the dense cache.

Covers the acceptance surface:

* kernel-vs-dense parity at the ops level on every kv storage leg
  (native/bf16 near-ulp in fp32 accumulation, int8 on the identical
  dequant grid) across page-boundary-straddling lengths
  ``t = page_size-1, page_size, page_size+1``, plus GQA and the t=0
  edge, all under the CPU Pallas interpreter;
* the in-place token write, one a step for all layers (ISSUE 26): a
  single-position write on the float legs, BIT-IDENTICAL pool bytes +
  scales versus the legacy dense ``scatter_token_page`` round-trip on
  the int8 leg, and the handle that collects the layers for it;
* the structural no-materialize proof: ``compiled_text()`` of the
  engine's kernel-tier bucketed decode program contains NO dense
  ``(L, 2, B, H, max_len, D)`` stacked-cache buffer (and the dense-tier
  program does — the positive control that the pin can fail);
* engine end-to-end greedy parity (paged == dense == the toy/model
  reference) on all kv legs over a real ``FusedMultiTransformer`` stack,
  and over ``LlamaForCausalLM.serving_callables`` (GQA + per-row RoPE)
  against ``generate``;
* the tiering knob (``PADDLE_TPU_PAGED_ATTENTION`` /
  ``ServingConfig.paged_attention``) and the kernel-eligibility table;
* serving-under-fire composition: the chaos fault sites behave
  identically with the kernel path enabled (replay recovery stays
  bit-identical, a faulted slot still fails alone);
* the ``gather_pages`` conditional-cast satellite.
"""

import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu import nn, serving
from paddle_tpu import observability as obs
from paddle_tpu.incubate.nn import FusedMultiTransformer
from paddle_tpu.ops import paged_attention as pa
from paddle_tpu.resilience import faults
from paddle_tpu.serving import kv_cache as kvc


# ---------------------------------------------------------------------------
# ops-level fixtures: a random pool with live pages
# ---------------------------------------------------------------------------

B, H, D, PS, S, L = 3, 2, 8, 16, 4, 2
P = 12                                # pool pages (page 0 scratch)


def _make_pool(kv_dtype: str, rng):
    poolf = jnp.asarray(rng.standard_normal((P, L, 2, H, PS, D)),
                        jnp.float32)
    if kv_dtype == "int8":
        q, sc = kvc.quantize_pages(poolf)
        return q, sc
    if kv_dtype == "bf16":
        return poolf.astype(jnp.bfloat16), None
    return poolf, None


def _qkv(rng, heads=H):
    q = jnp.asarray(rng.standard_normal((B, H, D)), jnp.float32)
    kn = jnp.asarray(rng.standard_normal((B, heads, D)), jnp.float32)
    vn = jnp.asarray(rng.standard_normal((B, heads, D)), jnp.float32)
    return q, kn, vn


TABLES = jnp.asarray([[1, 2, 3, 0], [4, 5, 0, 0], [6, 7, 8, 9]], jnp.int32)


class TestKernelParity:
    """The interpret-mode kernel against the per-layer dense reference:
    the same fp32 accumulation reordered, so near-ulp on every leg."""

    @pytest.mark.parametrize("kv_dtype", ["native", "bf16", "int8"])
    def test_page_boundary_lengths(self, kv_dtype):
        # the ISSUE-named straddle: t = ps-1 (page about to fill), ps
        # (first write into a fresh page), ps+1 — one per batch row
        rng = np.random.default_rng(0)
        pool, scales = _make_pool(kv_dtype, rng)
        q, kn, vn = _qkv(rng)
        t = jnp.asarray([PS - 1, PS, PS + 1], jnp.int32)
        for layer in range(L):
            got = pa.paged_attention(q, kn, vn, pool, scales, TABLES, t,
                                     jnp.asarray(layer), page_size=PS,
                                     impl="kernel", interpret=True)
            want = pa.paged_attention_dense(q, kn, vn, pool, scales,
                                            TABLES, t, jnp.asarray(layer),
                                            page_size=PS)
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       rtol=2e-6, atol=2e-6)

    def test_t_zero_and_full_context(self):
        rng = np.random.default_rng(1)
        pool, scales = _make_pool("native", rng)
        q, kn, vn = _qkv(rng)
        for tv in (0, S * PS - 1):
            t = jnp.full((B,), tv, jnp.int32)
            got = pa.paged_attention(q, kn, vn, pool, scales, TABLES, t,
                                     jnp.asarray(0), page_size=PS,
                                     impl="kernel", interpret=True)
            want = pa.paged_attention_dense(q, kn, vn, pool, scales,
                                            TABLES, t, jnp.asarray(0),
                                            page_size=PS)
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       rtol=2e-6, atol=2e-6)
        # t=0 attends ONLY the (unquantized) current token: out == v_new
        t0 = jnp.zeros((B,), jnp.int32)
        out0 = pa.paged_attention(q, kn, vn, pool, scales, TABLES, t0,
                                  jnp.asarray(1), page_size=PS,
                                  impl="kernel", interpret=True)
        np.testing.assert_allclose(np.asarray(out0), np.asarray(vn),
                                   rtol=1e-6, atol=1e-6)

    def test_dead_pages_never_leak(self):
        """Pool bytes outside the slot's live span — stale pages, the
        scratch page, OTHER layers — must not move the output: poison
        them with a huge constant and compare against the clean pool."""
        rng = np.random.default_rng(2)
        pool, _ = _make_pool("native", rng)
        q, kn, vn = _qkv(rng)
        t = jnp.asarray([PS + 3, 5, 2 * PS], jnp.int32)
        clean = pa.paged_attention(q, kn, vn, pool, None, TABLES, t,
                                   jnp.asarray(1), page_size=PS,
                                   impl="kernel", interpret=True)
        poisoned = np.array(pool)
        poisoned[0] = 1e9                        # scratch page
        poisoned[10:] = 1e9                      # never-allocated pages
        poisoned[:, 0] = 1e9                     # a different layer
        # positions at/after each slot's t inside its containing page
        for b in range(B):
            tb = int(t[b])
            pid = int(TABLES[b, tb // PS])
            poisoned[pid, 1, :, :, tb % PS:, :] = 1e9
        got = pa.paged_attention(q, kn, vn, jnp.asarray(poisoned), None,
                                 TABLES, t, jnp.asarray(1), page_size=PS,
                                 impl="kernel", interpret=True)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(clean))

    def test_gqa_broadcast(self):
        rng = np.random.default_rng(3)
        h_kv = 1                                  # rep = H // 1
        poolf = jnp.asarray(rng.standard_normal((P, L, 2, h_kv, PS, D)),
                            jnp.float32)
        q, _, _ = _qkv(rng)
        kn = jnp.asarray(rng.standard_normal((B, h_kv, D)), jnp.float32)
        vn = jnp.asarray(rng.standard_normal((B, h_kv, D)), jnp.float32)
        t = jnp.asarray([PS - 1, PS, PS + 1], jnp.int32)
        got = pa.paged_attention(q, kn, vn, poolf, None, TABLES, t,
                                 jnp.asarray(0), page_size=PS,
                                 impl="kernel", interpret=True)
        want = pa.paged_attention_dense(q, kn, vn, poolf, None, TABLES, t,
                                        jnp.asarray(0), page_size=PS)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-6, atol=2e-6)

    def test_int8_reads_exact_dequant_grid(self):
        """Kernel and dense tier read the SAME int8 bytes and scales —
        the established absmax-grid logits tolerance transfers unchanged
        (pinned in test_serving.py); here pin that both tiers agree with
        each other far below that tolerance."""
        rng = np.random.default_rng(4)
        pool, scales = _make_pool("int8", rng)
        q, kn, vn = _qkv(rng)
        t = jnp.asarray([40, 33, 17], jnp.int32)
        got = pa.paged_attention(q, kn, vn, pool, scales, TABLES, t,
                                 jnp.asarray(1), page_size=PS,
                                 impl="kernel", interpret=True)
        want = pa.paged_attention_dense(q, kn, vn, pool, scales, TABLES,
                                        t, jnp.asarray(1), page_size=PS)
        assert np.abs(np.asarray(got) - np.asarray(want)).max() < 1e-5


class TestScatterInplace:
    def test_float_leg_single_position_write(self):
        # one write for every layer (ISSUE 26): position t of each row's
        # containing page, in all L layers, and not one byte besides
        rng = np.random.default_rng(5)
        pool, _ = _make_pool("native", rng)
        kn = jnp.asarray(rng.standard_normal((L, B, H, D)), jnp.float32)
        vn = jnp.asarray(rng.standard_normal((L, B, H, D)), jnp.float32)
        t = jnp.asarray([17, 15, 32], jnp.int32)
        p2, sc2 = pa.scatter_token_inplace(pool, None, TABLES, t, kn, vn,
                                           page_size=PS)
        assert sc2 is None
        ref = np.array(pool)
        for b in range(B):
            tb = int(t[b])
            pid = int(TABLES[b, tb // PS])
            ref[pid, :, 0, :, tb % PS, :] = np.asarray(kn)[:, b]
            ref[pid, :, 1, :, tb % PS, :] = np.asarray(vn)[:, b]
        np.testing.assert_array_equal(np.asarray(p2), ref)

    @pytest.mark.parametrize("kv_dtype", ["native", "int8"])
    def test_handle_collects_layers_and_commits_once(self, kv_dtype):
        """``paged_decode_attention`` reads the pool and leaves it alone;
        ``commit_pending`` is the step's one write, equal to the write of
        every layer's token — whether the layers came one by one (the
        llama loop) or stacked (the FusedMultiTransformer scan)."""
        from paddle_tpu.core.tensor import Tensor as T
        rng = np.random.default_rng(9)
        pool, scales = _make_pool(kv_dtype, rng)
        t = jnp.asarray([PS - 1, PS, PS + 1], jnp.int32)
        qs = [_qkv(rng) for _ in range(L)]
        view = pa.PagedDecodeCache(
            pool=T(pool), tables=T(TABLES), t=T(t), page_size=PS,
            scales=T(scales) if scales is not None else None,
            impl="dense")
        for layer, (q, kn, vn) in enumerate(qs):
            out, view = pa.paged_decode_attention(
                T(q), T(kn), T(vn), view.at_layer(layer))
            want = pa.paged_attention_dense(q, kn, vn, pool, scales, TABLES,
                                            t, layer, page_size=PS)
            np.testing.assert_array_equal(np.asarray(out._data),
                                          np.asarray(want))
        assert view.pool._data is pool and view.pending_layers == L
        done = pa.commit_pending(view)
        k_all = jnp.stack([kn for _, kn, _ in qs])
        v_all = jnp.stack([vn for _, _, vn in qs])
        p_ref, sc_ref = pa.scatter_token_inplace(pool, scales, TABLES, t,
                                                 k_all, v_all, page_size=PS)
        np.testing.assert_array_equal(np.asarray(done.pool._data),
                                      np.asarray(p_ref))
        if scales is not None:
            np.testing.assert_array_equal(np.asarray(done.scales._data),
                                          np.asarray(sc_ref))
        assert done.pending == ()
        # the stacked form of the same layers commits the same bytes
        stacked = pa.commit_pending(pa.PagedDecodeCache(
            pool=T(pool), tables=T(TABLES), t=T(t), page_size=PS,
            scales=T(scales) if scales is not None else None,
            pending=((T(k_all), T(v_all)),)))
        np.testing.assert_array_equal(np.asarray(stacked.pool._data),
                                      np.asarray(p_ref))

    def test_handle_refuses_a_layer_out_of_order_or_missing(self):
        from paddle_tpu.core.tensor import Tensor as T
        rng = np.random.default_rng(10)
        pool, _ = _make_pool("native", rng)
        q, kn, vn = _qkv(rng)
        view = pa.PagedDecodeCache(
            pool=T(pool), tables=T(TABLES), t=T(jnp.zeros((B,), jnp.int32)),
            page_size=PS, impl="dense")
        with pytest.raises(ValueError, match="in order"):
            pa.paged_decode_attention(T(q), T(kn), T(vn), view.at_layer(1))
        _, view = pa.paged_decode_attention(T(q), T(kn), T(vn),
                                            view.at_layer(0))
        with pytest.raises(ValueError, match="every layer once"):
            pa.commit_pending(view)              # one of L=2 pending

    def test_int8_leg_matches_dense_scatter_bitwise(self):
        """The requantization contract: writing through the pool directly
        must produce the EXACT bytes + scales the legacy dense round-trip
        (gather -> write into dense -> scatter_token_page) produces."""
        rng = np.random.default_rng(6)
        pool, scales = _make_pool("int8", rng)
        t = jnp.asarray([PS - 1, PS, PS + 1], jnp.int32)
        k_new = jnp.asarray(rng.standard_normal((L, B, H, D)), jnp.float32)
        v_new = jnp.asarray(rng.standard_normal((L, B, H, D)), jnp.float32)

        # legacy path: reconstruct dense, write the token, scatter back
        dense = kvc.gather_pages(pool, scales, TABLES, jnp.float32)
        for b in range(B):
            dense = dense.at[:, 0, b, :, int(t[b]), :].set(k_new[:, b])
            dense = dense.at[:, 1, b, :, int(t[b]), :].set(v_new[:, b])
        pool_a, scales_a = kvc.scatter_token_page(dense, pool, scales,
                                                  TABLES, t, PS)
        # paged path: the one in-place write of all layers
        pool_b, scales_b = pa.scatter_token_inplace(
            pool, scales, TABLES, t, k_new, v_new, page_size=PS)
        np.testing.assert_array_equal(np.asarray(pool_a),
                                      np.asarray(pool_b))
        np.testing.assert_array_equal(np.asarray(scales_a),
                                      np.asarray(scales_b))


class TestGatherCastSatellite:
    def test_same_dtype_leg_emits_no_convert(self):
        """bf16 storage + bf16 compute: the gather must not cast (the
        old code converted the whole gathered cache unconditionally)."""
        pool = jnp.zeros((P, L, 2, H, PS, D), jnp.bfloat16)
        jaxpr = jax.make_jaxpr(
            lambda p, tb: kvc.gather_pages(p, None, tb, jnp.bfloat16))(
                pool, TABLES)
        assert "convert_element_type" not in str(jaxpr)

    def test_int8_leg_dequantizes_into_compute_dtype(self):
        rng = np.random.default_rng(7)
        pool, scales = _make_pool("int8", rng)
        out = kvc.gather_pages(pool, scales, TABLES, jnp.bfloat16)
        assert out.dtype == jnp.bfloat16
        out32 = kvc.gather_pages(pool, scales, TABLES, jnp.float32)
        assert out32.dtype == jnp.float32
        # fp32 leg semantics unchanged: exact dequant product
        recon = np.asarray(pool, np.float32) * \
            np.asarray(scales)[..., None, None]
        taken = recon[np.asarray(TABLES)]        # (B, S, L, 2, H, ps, D)
        want = taken.transpose(2, 3, 0, 4, 1, 5, 6).reshape(
            L, 2, B, H, S * PS, D)
        np.testing.assert_array_equal(np.asarray(out32), want)


class TestModeResolution:
    def test_env_knob(self, monkeypatch):
        """``auto`` follows the platform, ``on`` / ``off`` force a tier,
        anything else raises — and the environment name that used to
        mirror the field (ISSUE 29) decides nothing."""
        monkeypatch.setenv("PADDLE_TPU_PAGED_ATTENTION", "on")
        assert pa.decode_path() == "dense"       # CPU backend in tier-1
        assert pa.decode_path("auto") == "dense"
        monkeypatch.setattr(pa.jax, "default_backend", lambda: "tpu")
        assert pa.decode_path("auto") == "kernel"
        monkeypatch.undo()
        assert pa.decode_path("on") == "kernel"
        assert pa.decode_path("off") == "dense"
        # a typo must fail loudly, not silently flip the tier via "auto"
        for typo in ("dense", "", "1", "ON"):
            with pytest.raises(ValueError, match="auto|on|off"):
                pa.decode_path(typo)

    def test_serving_config_resolution(self, monkeypatch):
        def config(**kw):
            return serving.ServingConfig(
                num_layers=1, num_heads=1, head_dim=8, max_len=32,
                max_batch=1, buckets=(1,), page_size=16, **kw)

        monkeypatch.setenv("PADDLE_TPU_PAGED_ATTENTION", "on")
        monkeypatch.setenv("PADDLE_TPU_PREFIX_SHARING", "off")
        monkeypatch.setenv("PADDLE_TPU_PREFIX_MIN_PAGES", "7")
        cfg = config()
        assert (cfg.paged_attention, cfg.prefix_sharing, cfg.kv_dtype,
                cfg.min_shared_pages) == ("auto", "auto", "native", 1)
        assert config(paged_attention=" Off ").paged_attention == "off"
        for field in ("paged_attention", "prefix_sharing"):
            for bogus in ("bogus", ""):
                with pytest.raises(ValueError,
                                   match=f"{field} must be auto"):
                    config(**{field: bogus})

    def test_kernel_eligibility_tiling_table(self):
        # the edge of what Mosaic was given on the chip (and took): page
        # rows in 8s, head dims in 64s, any storage dtype, blocks in VMEM
        for ps in (8, 16, 24, 32, 64, 128):
            for dt in (jnp.float32, jnp.bfloat16, jnp.int8):
                assert pa.kernel_eligible(ps, 128, dt, 32)
        assert pa.kernel_eligible(64, 64, jnp.bfloat16)
        assert pa.kernel_eligible(64, 256, jnp.bfloat16, 8)
        assert not pa.kernel_eligible(12, 128, jnp.bfloat16)
        assert not pa.kernel_eligible(64, 8, jnp.float32)   # the toy shapes
        assert not pa.kernel_eligible(64, 96, jnp.float32)
        # 4 x 64 heads x 256 rows x 128 x 2 B = 16 MiB of page blocks
        assert not pa.kernel_eligible(256, 128, jnp.bfloat16, 64)

    def test_ineligible_shapes_fall_back_to_dense_math(self):
        # compiled-kernel path demotes to the dense tier instead of
        # tripping Mosaic — correctness is never gated on tiling
        rng = np.random.default_rng(8)
        pool, _ = _make_pool("bf16", rng)        # D=8: no whole lane tile
        q, kn, vn = _qkv(rng)
        t = jnp.asarray([5, 7, 9], jnp.int32)
        got = pa.paged_attention(q, kn, vn, pool, None, TABLES, t,
                                 jnp.asarray(0), page_size=PS,
                                 impl="kernel", interpret=False)
        want = pa.paged_attention_dense(q, kn, vn, pool, None, TABLES, t,
                                        jnp.asarray(0), page_size=PS)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_ineligible_shapes_demote_engine_to_dense_path(
            self, monkeypatch):
        """On a real chip (non-interpret), a Mosaic-ineligible config
        must demote the WHOLE engine to the dense tier — the
        paged_attention_steps_total{path} metric and the bench's
        all-dense-on-TPU suspect rule must tell the truth about which
        tier ran."""
        import paddle_tpu.ops.paged_attention as pamod
        monkeypatch.setattr(pamod, "kernel_interpret", lambda: False)
        cfg = serving.ServingConfig(       # head_dim 8: no whole lane tile
            num_layers=1, num_heads=1, head_dim=8, max_len=32,
            max_batch=1, buckets=(1,), page_size=16, kv_dtype="int8",
            paged_attention="on")
        eng = serving.Engine(lambda *a: None, lambda *a: None, cfg)
        assert eng._paged_path == "dense"
        cfg_ok = serving.ServingConfig(    # head_dim 128 at page_size 16
            num_layers=1, num_heads=1, head_dim=128, max_len=32,
            max_batch=1, buckets=(1,), page_size=16, kv_dtype="int8",
            paged_attention="on")
        eng_ok = serving.Engine(lambda *a: None, lambda *a: None, cfg_ok)
        assert eng_ok._paged_path == "kernel"

    def test_cross_host_sync_root_registered(self):
        # the decode fast path joins the whole-program reachability roots:
        # a .item()/.numpy() anywhere the kernel launch can reach is a
        # per-token, per-layer stall now (0 baseline entries)
        import os
        import sys
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        if repo not in sys.path:
            sys.path.insert(0, repo)
        from tools.lint.engine import DEFAULT_CONFIG
        assert "paddle_tpu/ops/paged_attention.py::paged_decode_attention" \
            in DEFAULT_CONFIG["fast_path_roots"]


# ---------------------------------------------------------------------------
# engine end-to-end over a real FusedMultiTransformer stack
# ---------------------------------------------------------------------------

FV, FE, FH, FL, FINTER, FM = 64, 16, 2, 3, 32, 64


@pytest.fixture(scope="module")
def fmt_stack():
    """(prefill_fn, step_fn) over a tiny FusedMultiTransformer LM.

    Module-scoped WITH teardown, never a module global: the models'
    parameters live in the weakref state registry, and any LATER test's
    mesh-committed to_static program would thread still-alive foreign
    tensors into its carried state and rebind them committed/sharded —
    the exact leak class the conftest gc pass exists for. Dropping the
    closures at module end lets that pass reclaim the registry entries
    before the placement-sensitive suites run."""
    paddle.seed(7)
    embed = nn.Embedding(FV, FE)
    fmt = FusedMultiTransformer(FE, FH, FINTER, num_layers=FL,
                                activation="gelu")
    final_ln = nn.LayerNorm(FE)
    head = nn.Linear(FE, FV, bias_attr=False)
    for layer in (embed, fmt, final_ln, head):
        layer.eval()
    fmt.prepare_decode()

    def lm_step(tok, cache, t):
        x = embed(tok)
        x, cache = fmt(x, caches=cache, time_step=t)
        x = final_ln(x)
        nxt = paddle.argmax(head(x), axis=-1)
        return nxt.astype("int32"), cache

    def prefill_raw(ids, cache):
        x = embed(ids)
        x, cache = fmt(x, caches=cache, time_step=None)
        x = final_ln(x)
        nxt = paddle.argmax(head(x[:, -1:]), axis=-1)
        return nxt.astype("int32"), cache

    yield prefill_raw, lm_step
    import gc
    del prefill_raw, lm_step, embed, fmt, final_ln, head
    gc.collect()


_RNG = np.random.default_rng(0)
FMT_PROMPTS = [_RNG.integers(0, FV, (n,), dtype=np.int32)
               for n in (8, 5, 11)]


def _fmt_engine(fmt_stack, paged_attention, kv_dtype="native", **kw):
    prefill_raw, lm_step = fmt_stack
    cfg = serving.ServingConfig(
        num_layers=FL, num_heads=FH, head_dim=FE // FH, max_len=FM,
        max_batch=4, buckets=(1, 4), page_size=16, kv_dtype=kv_dtype,
        paged_attention=paged_attention, **kw)
    return serving.Engine(prefill_raw, lm_step, cfg)


def _drain(eng, prompts, n_new=5):
    futs = [eng.submit(serving.GenerationRequest(p, max_new_tokens=n_new))
            for p in prompts]
    eng.run()
    return [f.result(timeout=10).tokens for f in futs]


class TestEngineParity:
    @pytest.mark.parametrize("kv_dtype", ["native", "bf16", "int8"])
    def test_kernel_matches_dense_engine(self, kv_dtype, metrics,
                                         fmt_stack):
        """The acceptance gate: kernel-tier greedy transcripts are
        IDENTICAL (match_frac 1.0) to the dense tier's on every kv leg,
        page-boundary lengths included (prompts 8/5/11, 5 new tokens
        across the page_size=16 boundary)."""
        dense = _drain(_fmt_engine(fmt_stack, "off", kv_dtype),
                       FMT_PROMPTS)
        snap = obs.snapshot()
        assert snap["serving.paged_attention_steps_total"][
            "path=dense"] > 0
        paged_eng = _fmt_engine(fmt_stack, "on", kv_dtype)
        paged = _drain(paged_eng, FMT_PROMPTS)
        assert paged == dense
        assert paged_eng.kv.free_pages == \
            paged_eng.kv.config.num_pages - 1
        snap = obs.snapshot()
        assert snap["serving.paged_attention_steps_total"][
            "path=kernel"] > 0

    def test_boundary_straddling_decode(self, fmt_stack):
        """One request decoded ACROSS a page boundary: prompt page_size-2
        + 5 tokens writes positions ps-2 .. ps+2 — the t = ps-1/ps/ps+1
        straddle exercised through the full engine."""
        prompts = [np.asarray(FMT_PROMPTS[0][:2], np.int32),
                   _RNG.integers(0, FV, (14,), dtype=np.int32)]
        dense = _drain(_fmt_engine(fmt_stack, "off"), prompts, n_new=6)
        paged = _drain(_fmt_engine(fmt_stack, "on"), prompts, n_new=6)
        assert paged == dense

    def test_warmup_and_eviction_admission_cycle(self, fmt_stack):
        eng = _fmt_engine(fmt_stack, "on").warmup(prompt_lens=[8])
        assert eng.kv.free_pages == eng.kv.config.num_pages - 1
        toks = _drain(eng, FMT_PROMPTS)
        assert toks == _drain(_fmt_engine(fmt_stack, "off"), FMT_PROMPTS)


class TestStructuralNoMaterialize:
    """The compiled_text() pin: the kernel-tier bucketed decode program
    provably contains no dense stacked-cache buffer."""

    DENSE_6D = re.compile(
        rf"\[{FL},2,2,{FH},{FM},{FE // FH}\]")       # (L,2,B,H,M,D), B=2
    GATHER_7D = re.compile(
        rf"\[2,4,{FL},2,{FH},16,{FE // FH}\]")       # (B,S,L,2,H,ps,D)

    def _decode_hlo(self, fmt_stack, paged_attention: str) -> str:
        prefill_raw, lm_step = fmt_stack
        cfg = serving.ServingConfig(
            num_layers=FL, num_heads=FH, head_dim=FE // FH, max_len=FM,
            max_batch=2, buckets=(2,), page_size=16,
            paged_attention=paged_attention)
        eng = serving.Engine(prefill_raw, lm_step, cfg)
        paddle.set_flags({"FLAGS_to_static_capture_lowered": True})
        try:
            eng.warmup()
            return eng.programs.decode_program.compiled_text()
        finally:
            paddle.set_flags({"FLAGS_to_static_capture_lowered": False})

    def test_dense_program_materializes_the_cache(self, fmt_stack):
        # positive control: the pin CAN fail — the legacy tier's HLO
        # carries both the gathered 7-D buffer and the stacked 6-D cache
        txt = self._decode_hlo(fmt_stack, "off")
        assert self.DENSE_6D.search(txt) or self.GATHER_7D.search(txt), \
            "dense-tier decode program no longer gathers the stacked " \
            "cache — update this structural test's shape pins"

    def test_kernel_program_never_materializes_the_cache(self, fmt_stack):
        txt = self._decode_hlo(fmt_stack, "on")
        assert not self.DENSE_6D.search(txt), \
            "kernel-tier decode program materializes the dense " \
            "(L, 2, B, H, max_len, D) stacked cache"
        assert not self.GATHER_7D.search(txt), \
            "kernel-tier decode program gathers the full per-slot page " \
            "set into a dense buffer"
        # the program really is the paged one: the pool shape is in play
        assert re.search(rf"\[\d+,{FL},2,{FH},16,{FE // FH}\]", txt), \
            "paged pool shape absent from the kernel-tier program"


# ---------------------------------------------------------------------------
# llama through the engine (GQA + per-row rope), kernel vs dense vs generate
# ---------------------------------------------------------------------------

class TestLlamaServing:
    @pytest.fixture(scope="class")
    def llama(self):
        from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
        paddle.seed(11)
        cfg = LlamaConfig.tiny(vocab=64, hidden=32, layers=2, heads=4,
                               kv_heads=2, inter=48, max_pos=64)
        model = LlamaForCausalLM(cfg)
        model.eval()
        yield model
        # same hygiene as fmt_stack: registered params must not outlive
        # the class (see the fixture docstring there)
        import gc
        del model
        gc.collect()

    def test_engine_matches_generate_on_both_tiers(self, llama):
        cfg = llama.config
        prefill_fn, step_fn = llama.serving_callables(64)
        rng = np.random.default_rng(5)
        prompts = [rng.integers(0, 64, (n,), dtype=np.int32)
                   for n in (6, 9)]
        refs = []
        for p in prompts:
            out = llama.generate(paddle.to_tensor(p[None, :]),
                                 max_new_tokens=5, do_sample=False)
            refs.append([int(x) for x in np.asarray(out._data)[0, p.size:]])
        for mode in ("off", "on"):
            scfg = serving.ServingConfig(
                num_layers=cfg.num_hidden_layers,
                num_heads=cfg.num_key_value_heads,
                head_dim=cfg.hidden_size // cfg.num_attention_heads,
                max_len=64, max_batch=2, buckets=(1, 2), page_size=16,
                paged_attention=mode)
            eng = serving.Engine(prefill_fn, step_fn, scfg)
            toks = _drain(eng, prompts)
            assert toks == refs, mode
            assert eng.kv.free_pages == eng.kv.config.num_pages - 1

    def test_scan_layers_checkpoint_is_rejected(self, llama):
        from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
        cfg = LlamaConfig.tiny(vocab=16, hidden=16, layers=1, heads=2,
                               kv_heads=2, inter=16)
        cfg.scan_layers = True
        m = LlamaForCausalLM(cfg)
        with pytest.raises(NotImplementedError, match="scan_layers"):
            m.serving_callables(32)
        with pytest.raises(ValueError, match="max_position_embeddings"):
            llama.serving_callables(4096)


# ---------------------------------------------------------------------------
# ISSUE 26: the pool is one buffer that every program consumes and gives back
# ---------------------------------------------------------------------------

def _same_buffer_kind(new, old):
    return new.shape == old.shape and new.dtype == old.dtype \
        and not new.is_deleted()


class TestDonatedPool:
    @pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
    @pytest.mark.parametrize("bucket", [1, 4])
    @pytest.mark.parametrize("path", ["on", "off"])
    def test_prefill_and_step_consume_the_pool(self, fmt_stack, path,
                                               bucket, kv_dtype):
        """Both decode tiers, both buckets, both storage legs: the array
        that was ``kv.pool`` before a prefill or a decode step is deleted
        by it (donated, written in place), the one adopted has its shape
        and dtype — and sixteen steps of that still decode the dense
        tier's tokens."""
        prompts = (FMT_PROMPTS + [FMT_PROMPTS[0][:4]])[:bucket]
        n_new = 17                                # 1 prefill + 16 steps
        eng = _fmt_engine(fmt_stack, path, kv_dtype)
        futs = [eng.submit(serving.GenerationRequest(p, max_new_tokens=n_new))
                for p in prompts]
        quantized = kv_dtype == "int8"
        pool0, scales0 = eng.kv.pool, eng.kv.scales
        assert eng._admit()                       # the prefills
        assert pool0.is_deleted() and _same_buffer_kind(eng.kv.pool, pool0)
        if quantized:
            assert scales0.is_deleted() and \
                _same_buffer_kind(eng.kv.scales, scales0)
        pool1, scales1 = eng.kv.pool, eng.kv.scales
        assert eng.step()                         # one decode step
        assert eng._bucket_for(len(prompts)) == bucket
        assert pool1.is_deleted() and _same_buffer_kind(eng.kv.pool, pool1)
        if quantized:
            assert scales1.is_deleted() and \
                _same_buffer_kind(eng.kv.scales, scales1)
        eng.run()
        tokens = [f.result(timeout=10).tokens for f in futs]
        assert all(len(t) == n_new for t in tokens)
        other = "off" if path == "on" else "on"
        assert tokens == _drain(_fmt_engine(fmt_stack, other, kv_dtype),
                                prompts, n_new=n_new)
        assert eng.kv.free_pages == eng.kv.config.num_pages - 1

    def test_warmup_rebinds_what_its_calls_return(self, fmt_stack):
        eng = _fmt_engine(fmt_stack, "on", "int8")
        pool0, scales0 = eng.kv.pool, eng.kv.scales
        eng.warmup(prompt_lens=[8])
        assert pool0.is_deleted() and scales0.is_deleted()
        assert _same_buffer_kind(eng.kv.pool, pool0)
        assert _same_buffer_kind(eng.kv.scales, scales0)
        # every page but the scratch one is as it was: zeros, unit scales
        np.testing.assert_array_equal(np.asarray(eng.kv.pool)[1:], 0)
        np.testing.assert_array_equal(np.asarray(eng.kv.scales)[1:], 1.0)

    @pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
    def test_tail_program_adopts_the_pool_when_its_result_is_dropped(
            self, kv_dtype):
        """What ``perfbench/runners/serve_open_loop.py::_warm_tails``
        does: call the tail program with ``Tensor(engine.kv.pool)`` and
        drop the result. The callable adopts the pool itself."""
        from paddle_tpu.core.tensor import Tensor
        from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
        paddle.seed(12)
        model = LlamaForCausalLM(LlamaConfig.tiny(
            vocab=64, hidden=32, layers=2, heads=4, kv_heads=2, inter=48,
            max_pos=64))
        model.eval()
        cfg = model.config
        prefill_fn, step_fn = model.serving_callables(64)
        eng = serving.Engine(prefill_fn, step_fn, serving.ServingConfig(
            num_layers=cfg.num_hidden_layers,
            num_heads=cfg.num_key_value_heads,
            head_dim=cfg.hidden_size // cfg.num_attention_heads,
            max_len=64, max_batch=2, buckets=(1, 2), page_size=16,
            kv_dtype=kv_dtype, paged_attention="on"))
        slots = eng.kv.config.pages_per_slot
        for _ in range(2):                        # built, then cached
            pool0 = eng.kv.pool
            eng._tail_program(16)(
                Tensor(jnp.zeros((1, 5), jnp.int32)),
                Tensor(jnp.zeros((slots,), jnp.int32)),
                Tensor(jnp.asarray(21, jnp.int32)),
                Tensor(eng.kv.pool), *eng._scales_args())
            assert pool0.is_deleted()
            assert _same_buffer_kind(eng.kv.pool, pool0)
            if kv_dtype == "int8":
                assert not eng.kv.scales.is_deleted()
        # and the engine still serves, through the adopted pool, what the
        # dense tier serves
        prompt = np.random.default_rng(3).integers(0, 64, (6,),
                                                   dtype=np.int32)
        off = serving.Engine(prefill_fn, step_fn, serving.ServingConfig(
            num_layers=cfg.num_hidden_layers,
            num_heads=cfg.num_key_value_heads,
            head_dim=cfg.hidden_size // cfg.num_attention_heads,
            max_len=64, max_batch=2, buckets=(1, 2), page_size=16,
            kv_dtype=kv_dtype, paged_attention="off"))
        assert _drain(eng, [prompt], n_new=4) == \
            _drain(off, [prompt], n_new=4)
        del model, prefill_fn, step_fn, eng, off
        import gc
        gc.collect()


# ---------------------------------------------------------------------------
# serving under fire with the kernel path enabled
# ---------------------------------------------------------------------------

class TestFaultsWithKernel:
    def test_replay_recovery_stays_bit_identical(self, metrics,
                                                 fmt_stack):
        """A double-faulted batched step with the kernel tier enabled
        recovers through bounded prefill replay and completes the exact
        dense-tier transcripts — the injected fault raises before the
        call, so the donated pool is as it was for the retry and the
        replay."""
        ref = _drain(_fmt_engine(fmt_stack, "off"), FMT_PROMPTS[:2],
                     n_new=4)
        sched = faults.FaultSchedule().error("serving.watchdog", on=(2, 3))
        eng = _fmt_engine(fmt_stack, "on", max_replays=1)
        with faults.installed(sched):
            futs = [eng.submit(serving.GenerationRequest(
                p, max_new_tokens=4)) for p in FMT_PROMPTS[:2]]
            eng.run()
        assert [f.result(timeout=10).tokens for f in futs] == ref
        snap = obs.snapshot()
        assert snap["serving.replays_total"] == 2
        assert eng.kv.free_pages == eng.kv.config.num_pages - 1

    def test_faulted_slot_fails_alone_on_kernel_tier(self, metrics,
                                                     fmt_stack):
        ref = _drain(_fmt_engine(fmt_stack, "off"), FMT_PROMPTS, n_new=4)
        sched = faults.FaultSchedule().error("serving.step", on=(2, 5))
        eng = _fmt_engine(fmt_stack, "on")
        with faults.installed(sched):
            futs = [eng.submit(serving.GenerationRequest(
                p, max_new_tokens=4)) for p in FMT_PROMPTS]
            eng.run()
        with pytest.raises(faults.FaultInjected):
            futs[1].result(timeout=10)
        assert futs[0].result(timeout=10).tokens == ref[0]
        assert futs[2].result(timeout=10).tokens == ref[2]
        assert eng.kv.free_pages == eng.kv.config.num_pages - 1


# ---------------------------------------------------------------------------
# a table per (row, KV head) — ISSUE 31: the pages a selection chose
# ---------------------------------------------------------------------------

def _sparse_case(rng, rep=4):
    from paddle_tpu.ops import sparse_attention as sa
    pool = jnp.asarray(rng.standard_normal((P, L, 2, H, PS, D)), jnp.float32)
    q = jnp.asarray(rng.standard_normal((B, H * rep, D)), jnp.float32)
    kn = jnp.asarray(rng.standard_normal((B, H, D)), jnp.float32)
    vn = jnp.asarray(rng.standard_normal((B, H, D)), jnp.float32)
    return sa, pool, q, kn, vn


@pytest.mark.parametrize("t_last", [PS * 3 + 5, PS * 2, PS * 2 + 1])
def test_one_table_for_every_kv_head_is_the_per_row_kernel(t_last):
    """Every page of the row in each head's table, the last one partly:
    what ``paged_attention`` computes from the row's one table."""
    rng = np.random.default_rng(t_last)
    sa, pool, q, kn, vn = _sparse_case(rng)
    tables = jnp.asarray(rng.integers(1, P, (B, S)), jnp.int32)
    t = jnp.asarray([t_last, PS + 3, t_last - 1], jnp.int32)
    col = jnp.arange(S)[None, :]
    lens = jnp.clip(t[:, None] - col * PS, 0, PS)
    both = lambda a: jnp.broadcast_to(a[:, None, :], (B, H, S))  # noqa: E731
    want = pa.paged_attention_dense(q, kn, vn, pool, None, tables, t, 1, PS)
    for impl in ("dense", "kernel"):
        got = sa.sparse_paged_attention(
            q, kn, vn, pool, both(tables), both(lens), 1, page_size=PS,
            impl=impl, interpret=True)
        assert np.abs(np.asarray(got) - np.asarray(want)).max() < 2e-5


@pytest.mark.parametrize("columns", [3, 8, 11])
def test_a_table_per_kv_head_reads_each_heads_own_pages(columns):
    """Each head its own pages, in its own order, some columns naming no
    page: the kernel against the gather, and against plain attention over
    the tokens the tables name."""
    rng = np.random.default_rng(columns)
    sa, pool, q, kn, vn = _sparse_case(rng)
    tables = jnp.asarray(rng.integers(1, P, (B, H, columns)), jnp.int32)
    lens = jnp.asarray(rng.choice([0, 5, PS], (B, H, columns)), jnp.int32)
    lens = lens.at[0, 0].set(0)              # a head that reads t alone
    got = {impl: np.asarray(sa.sparse_paged_attention(
        q, kn, vn, pool, tables, lens, 0, page_size=PS, impl=impl,
        interpret=True)) for impl in ("dense", "kernel")}
    assert np.abs(got["kernel"] - got["dense"]).max() < 2e-5
    rep = q.shape[1] // H
    for b, h in np.ndindex(B, H):
        ks = [np.asarray(pool[tables[b, h, c], 0, 0, h, :lens[b, h, c]])
              for c in range(columns)] + [np.asarray(kn[b, h])[None]]
        vs = [np.asarray(pool[tables[b, h, c], 0, 1, h, :lens[b, h, c]])
              for c in range(columns)] + [np.asarray(vn[b, h])[None]]
        kk, vv = np.concatenate(ks), np.concatenate(vs)
        qh = np.asarray(q[b, h * rep:(h + 1) * rep])
        s = qh @ kk.T / np.sqrt(D)
        p = np.exp(s - s.max(-1, keepdims=True))
        want = (p / p.sum(-1, keepdims=True)) @ vv
        assert np.abs(got["kernel"][b, h * rep:(h + 1) * rep] - want).max() \
            < 2e-5
    assert np.abs(got["kernel"][0, :rep] - np.asarray(vn[0, 0])).max() < 1e-6
