"""Program cost accounting — ISSUE 16.

Every compiled-program surface in the repo (the PR 2 eager dispatch
cache, the PR 11 captured whole-step program, the PR 13 bucketed serving
decode/prefill programs) holds a ``jax`` executable whose
``cost_analysis()`` / ``memory_analysis()`` were thrown away until now.
This module is the process-global **program cost registry** that keeps
them: at compile time each new executable is lowered once more against
its argument specs and XLA's modeled flops / bytes-accessed / memory
footprint are recorded under a per-program key. On top of the records it
derives the three numbers ROADMAP item 6(b) says the repo cannot
currently produce:

* a live **HBM ledger** — param/master/moment bytes from the state
  registry, KV pool page bytes from every live
  :class:`~paddle_tpu.serving.kv_cache.PagedKVCache`, the captured
  step's donated-buffer bytes, and headroom against a
  ``PADDLE_TPU_HBM_BYTES`` device model;
* per-program / per-decode-bucket **MFU** and **bandwidth utilization**,
  joined from the cost records and the existing ``train.step_seconds`` /
  ``serving.tpot_seconds`` timing histograms;
* the schema-pinned ``cost`` block in ``bench.py``'s row of record, so
  the next on-chip round pins MFU >= 0.70 against a number the code
  computes rather than a notebook.

Contracts (same shape as the rest of the observability package):

* **Zero per-step host work.** Analysis runs ONCE per compile, under the
  registry lock, from is-None hooks (``jit.to_static._cost_hook``,
  ``core.dispatch_cache._cost_hook``) that stay ``None`` unless
  :func:`install` ran — the ``_op_metrics_hook`` discipline. Disabled
  mode pays one is-None probe per compile, nothing per step.
* **Degrades gracefully.** A backend with no cost model (or an analysis
  call that raises) is COUNTED (``cost.analysis_failures_total``), never
  raised; the record survives with ``model_source="analytic"`` (when an
  analytic estimate exists — the unified ``flops_counter`` fallback) or
  ``"none"``.
* **Records retire** when cache entries evict, programs retrace dead
  state, or their owning ``StaticFunction`` is dropped (weakref
  finalizer) — ``/debug/cost`` lists one record per LIVE program.

Env knobs: ``PADDLE_TPU_COST=on|off`` (default on; the test suite turns
it off suite-wide because capture pays one extra AOT compile per
program), ``PADDLE_TPU_HBM_BYTES`` / ``PADDLE_TPU_PEAK_FLOPS`` /
``PADDLE_TPU_HBM_BW_BYTES`` (device model), and
``PADDLE_TPU_HBM_WARN_FRACTION`` (default 0.10 — the once-per-process
low-headroom warning threshold).
"""

from __future__ import annotations

import logging
import os
import threading
import time
import weakref
from collections import OrderedDict
from dataclasses import asdict, dataclass, field
from typing import Any, Callable, Dict, List, Optional, Tuple

_log = logging.getLogger("paddle_tpu.observability.cost")

__all__ = [
    "ProgramCostRecord", "mode", "installed", "install", "uninstall",
    "clear", "records", "record_analytic", "DEVICE_PEAKS", "device_peaks",
    "device_model", "hbm_ledger",
    "utilization", "debug_doc", "flight_snapshot", "healthz_component",
    "register_kv_cache", "decode_bucket_records", "prefix_sharing_stats",
]

# ---------------------------------------------------------------------------
# metric families (pre-created so capture never races family creation)
# ---------------------------------------------------------------------------
from . import _REGISTRY as _R            # noqa: E402  (same package)

_PROGRAMS = _R.gauge(
    "cost.programs", "live compiled programs with a cost record")
_CAPTURED = _R.counter(
    "cost.programs_captured_total",
    "cost records captured at compile time, by hook site and which cost "
    "model produced the figures", labelnames=("site", "model_source"))
_RETIRED = _R.counter(
    "cost.records_retired_total",
    "cost records dropped (cache eviction / retrace / program death)",
    labelnames=("site",))
_FAILURES = _R.counter(
    "cost.analysis_failures_total",
    "cost/memory analysis calls that returned nothing or raised "
    "(counted, never raised)", labelnames=("reason",))
_FLOPS_G = _R.gauge(
    "cost.program_flops", "XLA-modeled flops of one executable",
    labelnames=("site", "program"))
_BYTES_G = _R.gauge(
    "cost.program_bytes", "XLA-modeled bytes accessed by one executable",
    labelnames=("site", "program"))
_PEAK_G = _R.gauge(
    "cost.program_peak_bytes",
    "modeled memory footprint (argument+output+temp+code) of one "
    "executable", labelnames=("site", "program"))
_MFU_G = _R.gauge(
    "cost.mfu", "achieved MFU: modeled flops / measured seconds / device "
    "peak flops", labelnames=("site", "program"))
_BW_G = _R.gauge(
    "cost.bandwidth_util", "achieved HBM bandwidth fraction: modeled "
    "bytes / measured seconds / device bandwidth",
    labelnames=("site", "program"))
_HBM_G = _R.gauge(
    "cost.hbm_bytes", "live HBM ledger, by component",
    labelnames=("component",))

# ---------------------------------------------------------------------------
# record + registry state
# ---------------------------------------------------------------------------

#: substrings counted in the compiled HLO text — per-program collective
#: counts (optional: big programs may not render; counted best-effort)
_COLLECTIVE_OPS = ("all-reduce", "all-gather", "reduce-scatter",
                   "all-to-all", "collective-permute")


@dataclass
class ProgramCostRecord:
    """One live executable's modeled cost, captured at compile time."""

    key: str                             # registry key (unique per program)
    site: str                            # dispatch | train.step | serving.*
    program: str                         # human label (op name, bucket, ...)
    model_source: str                    # xla | analytic | none
    flops: Optional[float] = None
    bytes_accessed: Optional[float] = None
    argument_bytes: Optional[int] = None
    output_bytes: Optional[int] = None
    temp_bytes: Optional[int] = None
    generated_code_bytes: Optional[int] = None
    peak_bytes: Optional[int] = None     # argument+output+temp+code
    bucket: Optional[int] = None         # serving decode batch bucket
    collectives: Dict[str, int] = field(default_factory=dict)
    captured_at: float = 0.0
    analysis_seconds: float = 0.0


_LOCK = threading.RLock()
_RECORDS: "OrderedDict[str, ProgramCostRecord]" = OrderedDict()
_INSTALLED = False
#: StaticFunction ids with a live weakref finalizer (retire-on-death)
_FINALIZED: set = set()
#: weakrefs to every live PagedKVCache (ledger input)
_KV_CACHES: List[Any] = []
#: the low-headroom warning fires once per process (list so tests can
#: reset the latch without reaching for a global statement)
_HBM_WARN_ONCE = [False]


def mode() -> str:
    """``PADDLE_TPU_COST`` resolved: ``on`` (default) or ``off``."""
    v = os.environ.get("PADDLE_TPU_COST", "on").strip().lower()
    return "off" if v in ("off", "0", "false", "no") else "on"


def installed() -> bool:
    return _INSTALLED


# ---------------------------------------------------------------------------
# device model
# ---------------------------------------------------------------------------

_GIB = 1024 ** 3
#: Published peaks of one chip, keyed by ``jax.Device.device_kind`` — the
#: one peaks table in the repo (bench.py, the benchmarks and chip_smoke.py
#: read it through :func:`device_peaks`). Source of the v5e row: Google
#: Cloud documentation, "TPU v5e" — 197 TFLOP/s bf16, 393 TOP/s int8,
#: 16 GB of HBM at 819 GB/s, 1,600 Gbit/s of chip-to-chip interconnect.
#: A device that is not in the table is an error, not a default: add its
#: row with its source.
DEVICE_PEAKS = {
    "TPU v5 lite": {"peak_flops": 197e12, "peak_int8_ops": 393e12,
                    "hbm_bytes": 16 * _GIB, "hbm_bw_bytes": 819e9,
                    "ici_bits_per_s": 1600e9},
}


def device_peaks(device_kind: str) -> Dict[str, float]:
    """The :data:`DEVICE_PEAKS` row for ``device_kind``; unknown raises."""
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise LookupError(
            f"no published peaks for device kind {device_kind!r} in "
            f"observability.cost.DEVICE_PEAKS (known: "
            f"{sorted(DEVICE_PEAKS)}); add the row with its source") from None


def _env_float(name: str) -> Optional[float]:
    raw = os.environ.get(name, "").strip()
    if not raw:
        return None
    try:
        return float(raw)
    except ValueError:
        _log.warning("ignoring unparseable %s=%r", name, raw)
        return None


def device_model() -> Dict[str, Any]:
    """The device the ledger and the utilization join price against: HBM
    bytes, peak flop/s, HBM bandwidth. On a TPU, the chip's
    :data:`DEVICE_PEAKS` row (an unknown chip raises). A CPU process has
    no such device: every figure is ``None`` — no headroom, no MFU —
    unless the ``PADDLE_TPU_HBM_BYTES`` / ``_PEAK_FLOPS`` /
    ``_HBM_BW_BYTES`` overrides name one to plan against."""
    from .. import device as _device
    dev = _device.describe()
    base = device_peaks(dev["kind"]) if dev["platform"] == "tpu" else {}
    hbm = _env_float("PADDLE_TPU_HBM_BYTES")
    peak = _env_float("PADDLE_TPU_PEAK_FLOPS")
    bw = _env_float("PADDLE_TPU_HBM_BW_BYTES")
    return {
        "platform": dev["platform"], "device_kind": dev["kind"],
        "hbm_bytes": int(hbm) if hbm else base.get("hbm_bytes"),
        "peak_flops": peak if peak else base.get("peak_flops"),
        "hbm_bw_bytes": bw if bw else base.get("hbm_bw_bytes"),
        "source": "env" if (hbm or peak or bw) else
        ("published" if base else "none"),
    }


# ---------------------------------------------------------------------------
# capture core
# ---------------------------------------------------------------------------

def _store(rec: ProgramCostRecord) -> None:
    with _LOCK:
        _RECORDS.pop(rec.key, None)
        _RECORDS[rec.key] = rec
        _PROGRAMS.set(len(_RECORDS))
    _CAPTURED.inc(site=rec.site, model_source=rec.model_source)
    if rec.flops is not None:
        _FLOPS_G.set(rec.flops, site=rec.site, program=rec.program)
    if rec.bytes_accessed is not None:
        _BYTES_G.set(rec.bytes_accessed, site=rec.site, program=rec.program)
    if rec.peak_bytes is not None:
        _PEAK_G.set(rec.peak_bytes, site=rec.site, program=rec.program)


def _retire(key: str) -> None:
    with _LOCK:
        rec = _RECORDS.pop(key, None)
        _PROGRAMS.set(len(_RECORDS))
    if rec is not None:
        _RETIRED.inc(site=rec.site)


def _retire_prefix(prefix: str, sf_id: Optional[int] = None) -> None:
    """Retire every record whose key starts with ``prefix`` (an owning
    StaticFunction died, taking all its per-signature programs)."""
    with _LOCK:
        if sf_id is not None:
            _FINALIZED.discard(sf_id)
        dead = [k for k in _RECORDS if k.startswith(prefix)]
    for k in dead:
        _retire(k)


def _capture(key: str, site: str, program: str, lower_fn: Callable[[], Any],
             *, bucket: Optional[int] = None,
             analytic_flops: Optional[float] = None) -> ProgramCostRecord:
    """Lower+compile once, harvest XLA's cost/memory model, store the
    record. Never raises: every analysis failure is counted and the
    record degrades to the analytic fallback (or ``model_source="none"``).
    """
    t0 = time.perf_counter()
    flops = bytes_accessed = None
    mem: Dict[str, Optional[int]] = {}
    collectives: Dict[str, int] = {}
    compiled = None
    try:
        compiled = lower_fn().compile()
    except Exception as e:
        _FAILURES.inc(reason="lower_error")
        _log.debug("cost: lowering %s failed: %s", program, e)
    if compiled is not None:
        try:
            ca = compiled.cost_analysis()
            # jax 0.4.x returns a one-dict list; newer builds a plain dict;
            # a backend without a cost model returns None/empty
            if isinstance(ca, (list, tuple)):
                ca = ca[0] if ca else None
            if ca:
                if ca.get("flops") is not None:
                    flops = float(ca["flops"])
                if ca.get("bytes accessed") is not None:
                    bytes_accessed = float(ca["bytes accessed"])
            if flops is None:
                _FAILURES.inc(reason="no_cost_model")
        except Exception as e:
            _FAILURES.inc(reason="cost_analysis")
            _log.debug("cost: cost_analysis(%s) failed: %s", program, e)
        try:
            ms = compiled.memory_analysis()
            mem = {
                "argument_bytes": int(ms.argument_size_in_bytes),
                "output_bytes": int(ms.output_size_in_bytes),
                "temp_bytes": int(ms.temp_size_in_bytes),
                "generated_code_bytes": int(ms.generated_code_size_in_bytes),
            }
        except Exception as e:
            _FAILURES.inc(reason="memory_analysis")
            _log.debug("cost: memory_analysis(%s) failed: %s", program, e)
        try:
            txt = compiled.as_text()
            for opname in _COLLECTIVE_OPS:
                n = txt.count(opname + "(") + txt.count(opname + "-start(")
                if n:
                    collectives[opname] = n
        except Exception:
            pass                          # collective counts are optional
    source = "xla"
    if flops is None:
        if analytic_flops is not None:
            flops, source = float(analytic_flops), "analytic"
        else:
            source = "none"
    peak = None
    if mem:
        peak = sum(v for v in mem.values() if v is not None)
    rec = ProgramCostRecord(
        key=key, site=site, program=program, model_source=source,
        flops=flops, bytes_accessed=bytes_accessed,
        peak_bytes=peak, bucket=bucket, collectives=collectives,
        captured_at=time.time(),
        analysis_seconds=time.perf_counter() - t0, **mem)
    _store(rec)
    return rec


def record_analytic(program: str, flops: float, *, site: str = "analytic",
                    bytes_accessed: Optional[float] = None) -> None:
    """Register an analytic (non-XLA) estimate — the unified
    ``flops_counter`` path feeds per-network totals through here so the
    ``cost.model_source{analytic}`` series reflects them."""
    rec = ProgramCostRecord(
        key=f"analytic:{site}:{program}", site=site, program=program,
        model_source="analytic", flops=float(flops),
        bytes_accessed=bytes_accessed, captured_at=time.time())
    _store(rec)


# ---------------------------------------------------------------------------
# hooks (installed into the hot modules' is-None globals)
# ---------------------------------------------------------------------------

def _spec_of(a) -> Any:
    """ShapeDtypeStruct for one array, preserving a NamedSharding when the
    executable was built against one (same guard as to_static's donation
    spec builder — other sharding kinds re-derive on compile)."""
    import jax
    sh = getattr(a, "sharding", None)
    if sh is not None and not isinstance(
            sh, getattr(jax.sharding, "NamedSharding", ())):
        sh = None
    return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh)


def _sf_prefix(sf, cache_key) -> str:
    return f"sf:{id(sf)}:{abs(hash(cache_key)):x}:"


def _on_static_build(event: str, sf, **kw) -> None:
    """``jit.to_static._cost_hook``: event "build" fires once per NEW
    (cache entry, arg aval signature) pair — one entry's jax.jit
    respecializes per input shape, so each serving bucket lands its own
    record — with the jitted callable + specs captured before donation
    consumed the buffers. Event "retire" fires on a dead-state retrace
    and drops every signature's record for that entry."""
    if event == "retire":
        _retire_prefix(_sf_prefix(sf, kw["key"]))
        return
    if event != "build":
        return
    jitted, state_specs, arg_specs = (kw["jitted"], kw["state_specs"],
                                      kw["arg_specs"])
    site = getattr(sf, "cost_site", None) or "jit"
    label = getattr(sf, "cost_label", None) or getattr(
        getattr(sf, "_fn", None), "__name__", "program")
    bucket = None
    shape0 = getattr(arg_specs[0], "shape", None) if arg_specs else None
    if site == "serving.decode" and shape0:
        bucket = int(shape0[0])
        label = f"{label}[b={bucket}]"
    elif site == "serving.prefill" and shape0 is not None and len(shape0) > 1:
        label = f"{label}[len={int(shape0[1])}]"
    sid = id(sf)
    with _LOCK:
        register_finalizer = sid not in _FINALIZED
        if register_finalizer:
            _FINALIZED.add(sid)
    if register_finalizer:
        weakref.finalize(sf, _retire_prefix, f"sf:{sid}:", sid)
    key = _sf_prefix(sf, kw["key"]) + f"{abs(hash(kw.get('sig'))):x}"
    _capture(key, site, label,
             lambda: jitted.lower(state_specs, arg_specs), bucket=bucket,
             analytic_flops=getattr(sf, "cost_analytic_flops", None))


def _dispatch_key(key) -> str:
    return f"op:{abs(hash(key)):x}"


def _on_dispatch_event(event: str, key, **kw) -> None:
    """``core.dispatch_cache._cost_hook``: "store" fires from
    ``core.tensor._apply_cached`` right after a fresh entry lands (the
    run arrays are still in scope for spec building); "evict" fires per
    LRU/configure eviction; "clear" on ``cache_clear``."""
    if event == "store":
        entry, arrays = kw["entry"], kw["arrays"]
        specs = [_spec_of(a) for a in arrays]
        _capture(_dispatch_key(key), "dispatch", str(kw.get("op", "op")),
                 lambda: entry.fwd.lower(*specs))
    elif event == "evict":
        _retire(_dispatch_key(key))
    elif event == "clear":
        with _LOCK:
            dead = [k for k, r in _RECORDS.items() if r.site == "dispatch"]
        for k in dead:
            _retire(k)


def install() -> None:
    """Install the compile-time capture hooks (no-op when
    ``PADDLE_TPU_COST=off``). Called from ``observability.enable()``."""
    global _INSTALLED
    if mode() == "off":
        return
    with _LOCK:
        import importlib
        from ..core import dispatch_cache as _dcache_mod
        # NOT ``from ..jit import to_static``: the jit package re-exports
        # the decorator under the submodule's name, shadowing the module
        _ts_mod = importlib.import_module("paddle_tpu.jit.to_static")
        _dcache_mod._cost_hook = _on_dispatch_event
        _ts_mod._cost_hook = _on_static_build
        _INSTALLED = True


def uninstall() -> None:
    """Remove the hooks; records remain readable until :func:`clear`."""
    global _INSTALLED
    with _LOCK:
        import sys
        dc = sys.modules.get("paddle_tpu.core.dispatch_cache")
        ts = sys.modules.get("paddle_tpu.jit.to_static")
        if dc is not None:
            dc._cost_hook = None
        if ts is not None:
            ts._cost_hook = None
        _INSTALLED = False


def clear() -> None:
    """Drop every record (test isolation seam; wired into
    ``observability.reset()``)."""
    with _LOCK:
        _RECORDS.clear()
        _PROGRAMS.set(0)


def records(site: Optional[str] = None) -> List[Dict[str, Any]]:
    """Plain-data view of the live records, insertion-ordered."""
    with _LOCK:
        recs = list(_RECORDS.values())
    return [asdict(r) for r in recs if site is None or r.site == site]


def decode_bucket_records() -> Dict[int, Dict[str, Any]]:
    """{batch bucket: record} for the live serving decode programs — the
    bench's measured-bytes source for the paged_attention block."""
    out: Dict[int, Dict[str, Any]] = {}
    for r in records(site="serving.decode"):
        if r.get("bucket") is not None:
            out[int(r["bucket"])] = r
    return out


def register_kv_cache(kv) -> None:
    """Track a live PagedKVCache's pool/scales bytes in the HBM ledger
    (weakly: a dropped engine drops its pool from the ledger)."""
    with _LOCK:
        _KV_CACHES[:] = [r for r in _KV_CACHES if r() is not None]
        _KV_CACHES.append(weakref.ref(kv))


def prefix_sharing_stats() -> List[Dict[str, Any]]:
    """Per-live-pool prefix-sharing counters (ISSUE 17): pages in use /
    idle / high-water, the shared-page ratio, and the prefix-index hit
    rate — one row per registered :class:`PagedKVCache`. A page mapped by
    N slots appears here as sharing, never as N× bytes: the HBM ledger
    prices ``pool.nbytes`` (physical pages), so refcounts cannot inflate
    it."""
    with _LOCK:
        kvs = [r() for r in _KV_CACHES]
    rows: List[Dict[str, Any]] = []
    for kv in kvs:
        if kv is None:
            continue
        stats = getattr(kv, "prefix_stats", None)
        if stats is None:
            continue
        try:
            rows.append(stats())
        except Exception as e:             # pragma: no cover - defensive
            rows.append({"error": str(e)})
    return rows


# ---------------------------------------------------------------------------
# HBM ledger
# ---------------------------------------------------------------------------

def warn_fraction() -> float:
    v = _env_float("PADDLE_TPU_HBM_WARN_FRACTION")
    return 0.10 if v is None else v


def hbm_ledger() -> Dict[str, Any]:
    """The live HBM ledger: what is resident (state registry + KV pools),
    what the programs need on top (max modeled temp bytes), and the
    headroom against the device model. Pure read — walks live objects,
    no device work."""
    from ..core import tensor as _tensor_mod
    param = master = moment = other = 0
    for t in _tensor_mod._state_registry.alive():
        data = getattr(t, "_data", None)
        nb = int(getattr(data, "nbytes", 0) or 0)
        name = getattr(t, "name", "") or ""
        if isinstance(t, _tensor_mod.Parameter):
            param += nb
        elif name.endswith("_master"):
            master += nb
        elif "moment" in name or name.startswith("fused_"):
            moment += nb
        else:
            other += nb
    kv_pool = 0
    with _LOCK:
        kvs = [r() for r in _KV_CACHES]
    for kv in kvs:
        if kv is None:
            continue
        kv_pool += int(getattr(getattr(kv, "pool", None), "nbytes", 0) or 0)
        kv_pool += int(getattr(getattr(kv, "scales", None), "nbytes", 0) or 0)
    donated = 0
    g = _R.get("train.capture_donated_bytes")
    if g is not None:
        try:
            donated = int(g.value())
        except Exception:
            donated = 0
    with _LOCK:
        temps = [r.temp_bytes for r in _RECORDS.values()
                 if r.temp_bytes is not None]
    program_temp_peak = max(temps) if temps else 0
    dev = device_model()
    state_total = param + master + moment + other
    peak_hbm = state_total + kv_pool + program_temp_peak
    # no device to price against (a CPU process): no headroom, no warning
    headroom = dev["hbm_bytes"] - peak_hbm if dev["hbm_bytes"] else None
    frac = headroom / dev["hbm_bytes"] if dev["hbm_bytes"] else None
    ledger = {
        "param_bytes": param, "master_bytes": master,
        "moment_bytes": moment, "other_state_bytes": other,
        "state_bytes_total": state_total, "kv_pool_bytes": kv_pool,
        "donated_bytes": donated, "program_temp_peak_bytes":
        program_temp_peak, "hbm_bytes": dev["hbm_bytes"],
        "peak_hbm_bytes": peak_hbm, "headroom_bytes": headroom,
        "headroom_fraction": frac,
    }
    for comp in ("param_bytes", "master_bytes", "moment_bytes",
                 "other_state_bytes", "kv_pool_bytes",
                 "program_temp_peak_bytes", "peak_hbm_bytes",
                 "headroom_bytes"):
        if ledger[comp] is not None:
            _HBM_G.set(ledger[comp], component=comp[:-len("_bytes")])
    fire_warn = False
    if frac is not None and frac < warn_fraction():
        with _LOCK:
            if not _HBM_WARN_ONCE[0]:
                _HBM_WARN_ONCE[0] = True
                fire_warn = True
    if fire_warn:
        _log.warning(
            "HBM headroom %.1f%% below the %.0f%% warn threshold: modeled "
            "peak %d bytes vs %d device bytes (state %d + kv %d + program "
            "temps %d) — set PADDLE_TPU_HBM_BYTES if the device model is "
            "wrong", 100 * frac, 100 * warn_fraction(), peak_hbm,
            dev["hbm_bytes"], state_total, kv_pool, program_temp_peak)
    return ledger


# ---------------------------------------------------------------------------
# utilization join (cost records x timing histograms)
# ---------------------------------------------------------------------------

def _hist_mean(name: str) -> Optional[float]:
    """Mean of every sample across ALL label series of one histogram
    family, or None when the family has no samples."""
    h = _R.get(name)
    if h is None:
        return None
    total = count = 0.0
    for st in h.series().values():
        total += st["sum"]
        count += st["count"]
    return (total / count) if count else None


def utilization() -> List[Dict[str, Any]]:
    """Join the live cost records against the measured timing histograms:
    ``train.step_seconds`` prices the captured step, ``serving.tpot_seconds``
    prices each decode bucket (TPOT ~ one decode step). Sets the
    ``cost.mfu`` / ``cost.bandwidth_util`` gauges and returns the rows."""
    step_s = _hist_mean("train.step_seconds")
    tpot_s = _hist_mean("serving.tpot_seconds")
    dev = device_model()
    rows: List[Dict[str, Any]] = []
    for r in records():
        secs = None
        if r["site"] == "train.step":
            secs = step_s
        elif r["site"] == "serving.decode":
            secs = tpot_s
        if not secs:
            continue
        mfu = bw = None
        if r["flops"] and dev["peak_flops"]:
            mfu = r["flops"] / (secs * dev["peak_flops"])
            _MFU_G.set(mfu, site=r["site"], program=r["program"])
        if r["bytes_accessed"] and dev["hbm_bw_bytes"]:
            bw = r["bytes_accessed"] / (secs * dev["hbm_bw_bytes"])
            _BW_G.set(bw, site=r["site"], program=r["program"])
        if mfu is None and bw is None:
            continue
        rows.append({"key": r["key"], "site": r["site"],
                     "program": r["program"], "bucket": r["bucket"],
                     "seconds": secs, "mfu": mfu, "bandwidth_util": bw})
    return rows


# ---------------------------------------------------------------------------
# operator surfaces: /debug/cost, flight dumps, /healthz
# ---------------------------------------------------------------------------

def debug_doc() -> Dict[str, Any]:
    """The ``/debug/cost`` document: one record per live compiled
    program, the HBM ledger, the measured-utilization join, and the
    device model they are priced against."""
    try:
        hbm: Any = hbm_ledger()
    except Exception as e:                             # pragma: no cover
        hbm = {"error": str(e)}
    return {
        "pid": os.getpid(), "mode": mode(), "installed": installed(),
        "device": device_model(), "records": records(),
        "hbm": hbm, "utilization": utilization(),
        "prefix_sharing": prefix_sharing_stats(),
    }


def flight_snapshot() -> Dict[str, Any]:
    """Cost snapshot embedded in flight-recorder dumps. NEVER raises —
    a post-mortem must not die collecting its own context."""
    if not installed():
        # chaos paths dump a lot; don't walk the live-tensor registry
        # per dump unless the operator opted into cost accounting —
        # but the prefix-index counters are cheap dict reads and a
        # post-mortem of an eviction storm needs them, so they ride
        # along in the dump tail unconditionally
        return {"mode": "off", "prefix_sharing": prefix_sharing_stats()}
    try:
        return {"records": records(), "hbm": hbm_ledger(),
                "prefix_sharing": prefix_sharing_stats()}
    except Exception as e:
        return {"error": str(e)}


def healthz_component() -> Optional[Dict[str, Any]]:
    """The 503-independent ``hbm`` component for ``/healthz``: ledger
    bytes + headroom detail. ``ok`` is always True — low headroom warns
    (once) but never takes the process out of rotation.

    Returns None when cost accounting is not installed: /healthz is the
    router's rotation signal and may be polled hot, so it must not pay
    a live-tensor registry walk unless the operator opted in."""
    if not installed():
        return None
    try:
        led = hbm_ledger()
    except Exception:
        return None
    return {
        "ok": True, "stale": False,
        "hbm_bytes": led["hbm_bytes"],
        "peak_hbm_bytes": led["peak_hbm_bytes"],
        "state_bytes_total": led["state_bytes_total"],
        "kv_pool_bytes": led["kv_pool_bytes"],
        "headroom_bytes": led["headroom_bytes"],
        "headroom_fraction": led["headroom_fraction"],
        "warn": (led["headroom_fraction"] is not None
                 and led["headroom_fraction"] < warn_fraction()),
    }
