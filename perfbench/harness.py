"""What both runners share: the device, memory, compile events, the profiler
slice, and the bridge from the model under test to the reference's layout.

``open_device``, ``hbm_peak`` and ``CompileCounter`` are copies of
``chip_smoke.py``'s (the yardstick imports nothing a later PR may change
beyond the entry points it measures).
"""

from __future__ import annotations

import glob
import json
import os
import time
from typing import Dict, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(HERE, "out")


_T0 = time.monotonic()


def log(*a) -> None:
    """A line of the run's own story, stamped with the seconds since this
    module was imported (the result line is printed last, without one)."""
    print(f"[{time.monotonic() - _T0:6.1f}s]", *a, flush=True)


def load_json(*parts: str) -> Dict:
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def open_device(chips: int, on_chip: bool = True) -> Dict:
    """Take the device; refuse anything but a TPU with the chips the cell
    asks for. Unknown device kinds have no peaks: an error, not a default."""
    import jax

    devs = jax.devices()
    d = devs[0]
    if on_chip and d.platform != "tpu":
        raise SystemExit(
            f"perfbench: needs a TPU, jax found platform {d.platform!r} "
            f"(device_kind {d.device_kind!r}, {len(devs)} device(s))")
    if len(devs) < chips:
        raise SystemExit(
            f"perfbench: the cell needs {chips} chip(s), jax found "
            f"{len(devs)}")
    device = {"platform": d.platform, "kind": d.device_kind,
              "count": len(devs)}
    peaks = load_json("peaks.json")["devices"].get(d.device_kind)
    if on_chip and peaks is None:
        raise SystemExit(f"perfbench: no peaks for device kind "
                         f"{d.device_kind!r} in perfbench/peaks.json")
    log("device:", json.dumps(device))
    return {"device": device, "peaks": peaks}


def hbm_peak(chips: int) -> int:
    """Peak bytes in use on the fullest of the first ``chips`` devices."""
    import jax
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.devices()[:chips])


def hbm_line() -> str:
    """Bytes in use / peak / limit of the first device, for the log."""
    import jax
    st = jax.devices()[0].memory_stats() or {}
    return "HBM in use {:.2f} GB, peak {:.2f}, limit {:.2f}".format(
        *(st.get(k, 0) / 1e9 for k in
          ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")))


def device_barrier() -> None:
    """Wait until everything enqueued on the device has run (programs run in
    order, so a trivial one enqueued last ends last)."""
    import jax.numpy as jnp
    (jnp.zeros((), jnp.int32) + 1).block_until_ready()


class CompileCounter:
    """Counts XLA backend compilations through jax's own monitoring events
    — every one, whichever layer of the program asked for it."""

    def __init__(self):
        import jax.monitoring
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_kw) -> None:
        if event.endswith("backend_compile_duration"):
            self.count += 1


class ProfilerSlice:
    """A few seconds of ``jax.profiler`` in mid-window; ``load`` returns the
    reduced trace with the slice's length on the host clock."""

    def __init__(self, tag: str):
        self.dir = os.path.join(OUT_DIR, f"{tag}.trace")
        self.t0: Optional[float] = None         # set by start
        self.window_s: Optional[float] = None   # set by stop

    @property
    def running(self) -> bool:
        return self.t0 is not None and self.window_s is None

    def start(self) -> None:
        import shutil

        import jax.profiler
        shutil.rmtree(self.dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0          # device planes are what we read
        opts.host_tracer_level = 1
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.t0 = time.monotonic()

    def stop(self) -> None:
        import jax.profiler
        self.window_s = time.monotonic() - self.t0
        jax.profiler.stop_trace()

    def load(self) -> Dict:
        """Parse what ``stop`` wrote — after the window, it is host work."""
        from . import trace_reduce
        files = sorted(glob.glob(os.path.join(
            self.dir, "plugins", "profile", "*", "*.xplane.pb")))
        trace = trace_reduce.load(files[-1]) if files else {"planes": []}
        trace["window_s"] = self.window_s
        trace["t0"] = self.t0
        return trace


def llama_config(published: Dict, **overrides):
    """The program's config object from a configuration file's published
    keys (those ``LlamaConfig`` has a field for)."""
    import dataclasses

    from paddle_tpu.models.llama import LlamaConfig
    fields = {f.name for f in dataclasses.fields(LlamaConfig)}
    cfg = LlamaConfig(**{k: v for k, v in published.items() if k in fields})
    for k, v in overrides.items():
        setattr(cfg, k, v)
    return cfg


_LAYER_KEYS = {
    "ln1": "input_layernorm.weight", "q": "self_attn.q_proj.weight",
    "k": "self_attn.k_proj.weight", "v": "self_attn.v_proj.weight",
    "o": "self_attn.o_proj.weight", "ln2": "post_attention_layernorm.weight",
    "gate": "mlp.gate_proj.weight", "up": "mlp.up_proj.weight",
    "down": "mlp.down_proj.weight"}


def reference_params(model) -> Dict:
    """The model under test's weights (as they are, bf16 on the device) in
    ``reference.py``'s layout — arrays are shared, not copied."""
    sd = {k: v._data for k, v in model.state_dict().items()}
    out = {"embed": sd["model.embed_tokens.weight"],
           "norm": sd["model.norm.weight"], "head": sd["lm_head.weight"]}
    if model.config.scan_layers:
        out["layers"] = {
            short: sd["model.scan_" + name.replace(".", "_")]
            for short, name in _LAYER_KEYS.items()}
    else:
        out["layers"] = [
            {short: sd[f"model.layers.{i}.{name}"]
             for short, name in _LAYER_KEYS.items()}
            for i in range(model.config.num_hidden_layers)]
    return out


def fold_seed(seed: int) -> int:
    """``--seed`` may exceed 32 signed bits; fold it for APIs that do not."""
    return int(seed) % (2 ** 31 - 1)


def snapshot_counters(engine=None) -> Dict:
    """The program's counters a reader may take a delta of."""
    from paddle_tpu import observability as obs
    snap = dict(obs.snapshot())
    if engine is not None:
        req, comp = engine.prefill_token_stats()
        snap["prefill_tokens_requested"] = req
        snap["prefill_tokens_computed"] = comp
    return snap
