"""Program cost accounting (ISSUE 16): the process-global cost registry.

Covers the contract end to end: capture at all three hook sites
(dispatch-cache entry, captured/to_static program, serving bucket
warmup), per-signature records under one cache entry, retirement on
eviction / cache clear / retrace / program death, the HBM ledger
arithmetic against hand-computed param+pool bytes, the MFU/bandwidth
join on fake timings, no-cost-model degradation (counted, never
raised), the Prometheus series names, the ``/debug/cost`` route, the
flight-dump cost snapshot, and the 503-independent ``/healthz`` hbm
component.

The suite runs with ``PADDLE_TPU_COST=off`` globally (conftest) —
every test here opts in through the ``cost_on`` fixture.
"""

import gc
import json
import urllib.request

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn
from paddle_tpu.core import dispatch_cache as dcache
from paddle_tpu.observability import cost as cost_mod


@pytest.fixture()
def cost_on(metrics, monkeypatch):
    """Metrics enabled (via ``metrics``) + the cost hooks installed for
    one test; the suite-wide PADDLE_TPU_COST=off is overridden here."""
    monkeypatch.setenv("PADDLE_TPU_COST", "on")
    # a CPU process has no device to price against: name the chip these
    # tests plan for (the v5e row of cost.DEVICE_PEAKS)
    v5e = cost_mod.device_peaks("TPU v5 lite")
    monkeypatch.setenv("PADDLE_TPU_HBM_BYTES", str(v5e["hbm_bytes"]))
    monkeypatch.setenv("PADDLE_TPU_PEAK_FLOPS", str(v5e["peak_flops"]))
    monkeypatch.setenv("PADDLE_TPU_HBM_BW_BYTES", str(v5e["hbm_bw_bytes"]))
    cost_mod.install()
    cost_mod.clear()
    cost_mod._HBM_WARN_ONCE[0] = False
    yield cost_mod
    cost_mod.uninstall()
    cost_mod.clear()
    cost_mod._HBM_WARN_ONCE[0] = False


def test_mode_resolution(monkeypatch):
    monkeypatch.delenv("PADDLE_TPU_COST", raising=False)
    assert cost_mod.mode() == "on"
    for off in ("off", "0", "false", "no"):
        monkeypatch.setenv("PADDLE_TPU_COST", off)
        assert cost_mod.mode() == "off"


def test_install_noop_when_off(metrics, monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_COST", "off")
    cost_mod.uninstall()
    cost_mod.install()
    assert not cost_mod.installed()
    from paddle_tpu.jit import to_static as _dec  # the decorator
    import importlib
    ts_mod = importlib.import_module("paddle_tpu.jit.to_static")
    assert ts_mod is not _dec
    assert ts_mod._cost_hook is None
    assert dcache._cost_hook is None


# ---------------------------------------------------------------------------
# capture sites
# ---------------------------------------------------------------------------

def test_jit_site_capture_and_program_death(cost_on, metrics):
    @paddle.jit.to_static
    def f(x):
        return x * 2.0 + 1.0

    f(paddle.to_tensor(np.ones((4, 4), np.float32)))
    recs = cost_on.records(site="jit")
    assert len(recs) == 1
    r = recs[0]
    assert r["model_source"] == "xla"
    assert r["flops"] and r["flops"] > 0
    assert r["bytes_accessed"] and r["bytes_accessed"] > 0
    # peak = argument+output+temp+generated_code, all present on CPU XLA
    assert r["peak_bytes"] == (r["argument_bytes"] + r["output_bytes"]
                               + r["temp_bytes"]
                               + r["generated_code_bytes"])

    # ONE cache entry respecializes per aval: a second shape through the
    # same entry is a distinct program and lands its own record
    f(paddle.to_tensor(np.ones((8, 4), np.float32)))
    assert len(cost_on.records(site="jit")) == 2
    # same signature again: no re-capture
    f(paddle.to_tensor(np.ones((8, 4), np.float32)))
    assert len(cost_on.records(site="jit")) == 2

    captured = metrics.snapshot()["cost.programs_captured_total"]
    assert captured.get("site=jit,model_source=xla") == 2

    del f
    gc.collect()
    assert cost_on.records(site="jit") == []
    retired = metrics.snapshot()["cost.records_retired_total"]
    assert retired.get("site=jit") == 2


def test_dispatch_site_capture_evict_and_clear(cost_on, metrics):
    prev = (dcache._ENABLED, dcache._MAXSIZE, dcache._WARMUP)
    dcache.configure(enabled=True, maxsize=256, warmup=1)
    dcache.cache_clear()
    try:
        x = paddle.to_tensor(np.ones((4, 4), np.float32))
        for _ in range(3):           # past warmup: entry stores + serves
            y = x * 2.0
            z = x + y
        recs = cost_on.records(site="dispatch")
        assert len(recs) == 2
        assert all(r["model_source"] == "xla" and r["flops"] is not None
                   for r in recs)
        ops = {r["program"] for r in recs}
        assert any("mul" in o for o in ops) or any("scale" in o
                                                   for o in ops) or ops

        # shrinking maxsize evicts entries -> their records retire
        dcache.configure(maxsize=1)
        assert len(cost_on.records(site="dispatch")) == 1
        retired = metrics.snapshot()["cost.records_retired_total"]
        assert retired.get("site=dispatch") == 1

        # cache_clear drops every dispatch record
        dcache.cache_clear()
        assert cost_on.records(site="dispatch") == []
    finally:
        dcache.configure(enabled=prev[0], maxsize=prev[1], warmup=prev[2])
        dcache.cache_clear()


def test_serving_bucket_warmup_capture(cost_on):
    from test_serving import make_engine

    eng = make_engine(max_batch=4)
    eng.warmup(prompt_lens=[5])
    buckets = cost_on.decode_bucket_records()
    # /debug/cost lists one record per warmed bucket program
    assert set(buckets) == set(eng.config.buckets) == {1, 4}
    for b, rec in buckets.items():
        assert rec["site"] == "serving.decode" and rec["bucket"] == b
        assert rec["flops"] and rec["bytes_accessed"]
        assert f"[b={b}]" in rec["program"]
    prefill = cost_on.records(site="serving.prefill")
    assert len(prefill) == 1 and "[len=5]" in prefill[0]["program"]

    # engine death retires every bucket's record
    del eng
    gc.collect()
    assert cost_on.records(site="serving.decode") == []
    assert cost_on.records(site="serving.prefill") == []


def test_retire_event_drops_entry_records(cost_on):
    # the dead-state retrace path fires ("retire", sf, key=...) before
    # purging the entry: every per-signature record under it must go
    class SF:
        cost_site = cost_label = _fn = None

    sf = SF()
    key = ("treedef", "static")
    prefix = cost_on._sf_prefix(sf, key)
    for sig in ("aa", "bb"):
        cost_on._store(cost_mod.ProgramCostRecord(
            key=prefix + sig, site="jit", program="p",
            model_source="xla", flops=1.0))
    cost_on._store(cost_mod.ProgramCostRecord(
        key="sf:999:other", site="jit", program="q", model_source="xla"))
    cost_on._on_static_build("retire", sf, key=key)
    left = cost_on.records(site="jit")
    assert [r["key"] for r in left] == ["sf:999:other"]


# ---------------------------------------------------------------------------
# degradation
# ---------------------------------------------------------------------------

def test_lower_failure_degrades_counted(cost_on, metrics):
    def boom():
        raise RuntimeError("no lowering")

    rec = cost_on._capture("k1", "dispatch", "p", boom)
    assert rec.model_source == "none" and rec.flops is None
    rec2 = cost_on._capture("k2", "dispatch", "p2", boom,
                            analytic_flops=123.0)
    assert rec2.model_source == "analytic" and rec2.flops == 123.0
    fails = metrics.snapshot()["cost.analysis_failures_total"]
    assert fails.get("reason=lower_error") == 2
    # both records survive and are listed
    assert {r["key"] for r in cost_on.records()} == {"k1", "k2"}


def test_no_cost_model_degrades_counted(cost_on, metrics):
    class FakeCompiled:
        def cost_analysis(self):
            return None

        def memory_analysis(self):
            raise RuntimeError("backend has no memory stats")

        def as_text(self):
            return "HloModule m\n all-reduce(x)\n all-reduce-start(y)\n"

    class FakeLowered:
        def compile(self):
            return FakeCompiled()

    rec = cost_on._capture("k", "train.step", "step",
                           lambda: FakeLowered())
    assert rec.model_source == "none"
    assert rec.peak_bytes is None
    assert rec.collectives == {"all-reduce": 2}
    fails = metrics.snapshot()["cost.analysis_failures_total"]
    assert fails.get("reason=no_cost_model") == 1
    assert fails.get("reason=memory_analysis") == 1


def test_flops_counter_feeds_analytic_records(cost_on):
    net = nn.Sequential(nn.Linear(8, 8), nn.ReLU())
    total = paddle.flops(net, [2, 8])
    recs = cost_on.records(site="analytic")
    assert len(recs) == 1
    assert recs[0]["model_source"] == "analytic"
    assert recs[0]["flops"] == float(total) and total > 0


# ---------------------------------------------------------------------------
# HBM ledger
# ---------------------------------------------------------------------------

def test_hbm_ledger_arithmetic(cost_on):
    gc.collect()
    led0 = cost_on.hbm_ledger()
    net = nn.Linear(8, 8)            # 8x8 weight + 8 bias, float32
    led1 = cost_on.hbm_ledger()
    assert led1["param_bytes"] - led0["param_bytes"] == (64 + 8) * 4

    class FakeArr:
        nbytes = 4096

    class FakeKV:
        pool = FakeArr()
        scales = None

    kv = FakeKV()
    cost_on.register_kv_cache(kv)
    led2 = cost_on.hbm_ledger()
    assert led2["kv_pool_bytes"] - led1["kv_pool_bytes"] == 4096

    # a live program's modeled temp rides into the peak
    cost_on._store(cost_mod.ProgramCostRecord(
        key="k", site="train.step", program="step", model_source="xla",
        temp_bytes=1 << 20))
    led3 = cost_on.hbm_ledger()
    assert led3["program_temp_peak_bytes"] == 1 << 20
    assert led3["peak_hbm_bytes"] == (led3["state_bytes_total"]
                                      + led3["kv_pool_bytes"]
                                      + (1 << 20))
    assert led3["headroom_bytes"] == led3["hbm_bytes"] - \
        led3["peak_hbm_bytes"]

    # dropping the cache drops its pool from the ledger (weakref)
    del kv
    gc.collect()
    assert cost_on.hbm_ledger()["kv_pool_bytes"] == \
        led1["kv_pool_bytes"]
    del net


def test_hbm_low_headroom_warns_once(cost_on, monkeypatch, caplog):
    monkeypatch.setenv("PADDLE_TPU_HBM_BYTES", "1")
    nn.Linear(4, 4)                  # any resident state overflows 1 byte
    with caplog.at_level("WARNING", "paddle_tpu.observability.cost"):
        cost_on.hbm_ledger()
        assert any("HBM headroom" in r.message for r in caplog.records)
        caplog.clear()
        cost_on.hbm_ledger()         # latched: once per process
        assert not caplog.records


def test_device_model_env_overrides(cost_on, monkeypatch):
    for k in ("HBM_BYTES", "PEAK_FLOPS", "HBM_BW_BYTES"):
        monkeypatch.delenv(f"PADDLE_TPU_{k}")
    dev = cost_on.device_model()
    # CPU: nothing to price against, and no borrowed row
    assert dev["platform"] == "cpu" and dev["source"] == "none"
    assert dev["hbm_bytes"] is None and dev["peak_flops"] is None
    assert cost_on.hbm_ledger()["headroom_bytes"] is None
    with pytest.raises(LookupError, match="no published peaks"):
        cost_on.device_peaks("TPU v99")
    monkeypatch.setenv("PADDLE_TPU_HBM_BYTES", "1000")
    monkeypatch.setenv("PADDLE_TPU_PEAK_FLOPS", "2e12")
    dev = cost_on.device_model()
    assert dev["hbm_bytes"] == 1000 and dev["peak_flops"] == 2e12
    assert dev["source"] == "env"


# ---------------------------------------------------------------------------
# utilization join
# ---------------------------------------------------------------------------

def test_utilization_join_math(cost_on, metrics, monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_PEAK_FLOPS", "1e12")
    monkeypatch.setenv("PADDLE_TPU_HBM_BW_BYTES", "1e9")
    cost_on._store(cost_mod.ProgramCostRecord(
        key="step", site="train.step", program="step", model_source="xla",
        flops=2e9, bytes_accessed=1e8))
    cost_on._store(cost_mod.ProgramCostRecord(
        key="dec", site="serving.decode", program="decode[b=4]",
        model_source="xla", flops=5e8, bucket=4))
    # fake measured timings: 10ms steps, 5ms TPOT
    metrics.observe("train.step_seconds", 0.01)
    metrics.observe("serving.tpot_seconds", 0.005)
    rows = {r["key"]: r for r in cost_on.utilization()}
    assert rows["step"]["mfu"] == pytest.approx(2e9 / (0.01 * 1e12))
    assert rows["step"]["bandwidth_util"] == pytest.approx(
        1e8 / (0.01 * 1e9))
    assert rows["dec"]["mfu"] == pytest.approx(5e8 / (0.005 * 1e12))
    assert rows["dec"]["bandwidth_util"] is None
    snap = metrics.snapshot()
    assert snap["cost.mfu"]["site=train.step,program=step"] == \
        pytest.approx(0.2)


def test_utilization_empty_without_timings(cost_on):
    cost_on._store(cost_mod.ProgramCostRecord(
        key="step", site="train.step", program="step", model_source="xla",
        flops=2e9))
    assert cost_on.utilization() == []


# ---------------------------------------------------------------------------
# operator surfaces
# ---------------------------------------------------------------------------

def test_prometheus_series_names(cost_on, metrics):
    @paddle.jit.to_static
    def f(x):
        return x + 1.0

    f(paddle.to_tensor(np.ones((2, 2), np.float32)))
    cost_on.hbm_ledger()
    text = metrics.prometheus_text()
    for fam in ("cost_programs", "cost_programs_captured_total",
                "cost_program_flops", "cost_program_bytes",
                "cost_program_peak_bytes", "cost_hbm_bytes"):
        assert fam in text, fam


def test_debug_cost_route(cost_on):
    @paddle.jit.to_static
    def f(x):
        return x + 1.0

    f(paddle.to_tensor(np.ones((2, 2), np.float32)))
    from paddle_tpu.observability.http import start_http_server
    srv = start_http_server(0)
    try:
        doc = json.load(urllib.request.urlopen(
            srv.url + "/debug/cost", timeout=10))
    finally:
        srv.close()
    assert doc["mode"] == "on" and doc["installed"] is True
    assert len(doc["records"]) == 1
    assert doc["records"][0]["site"] == "jit"
    assert doc["hbm"]["hbm_bytes"] > 0
    assert "utilization" in doc and "device" in doc


def test_flight_dump_carries_cost_snapshot(cost_on, tracing, tmp_path):
    cost_on._store(cost_mod.ProgramCostRecord(
        key="k", site="train.step", program="step", model_source="xla",
        flops=1.0))
    p = tracing.flight_recorder().dump("test_cost_abort")
    with open(p) as f:
        doc = json.load(f)
    assert doc["cost"]["records"][0]["key"] == "k"
    assert "hbm" in doc["cost"]


def test_healthz_hbm_component_is_503_independent(cost_on, tracing):
    # beacons are process-global and trace.clear() does not touch them:
    # retire ours or every later /healthz in the suite reads unhealthy
    try:
        tracing.heartbeat("test.engine", ttl_s=60.0)
        doc = tracing.health()
        assert doc["status"] == "ok"
        hbm = doc["components"]["hbm"]
        assert hbm["ok"] is True and hbm["stale"] is False
        assert hbm["headroom_bytes"] == hbm["hbm_bytes"] - \
            hbm["peak_hbm_bytes"]
        # a stale beacon flips the process status; the hbm component
        # never does (low headroom warns, it does not take us out of
        # rotation)
        tracing.heartbeat("stale.engine", ttl_s=0.0)
        doc = tracing.health()
        assert doc["status"] == "unhealthy"
        assert doc["components"]["hbm"]["ok"] is True
    finally:
        tracing.heartbeat_clear("test.engine")
        tracing.heartbeat_clear("stale.engine")
