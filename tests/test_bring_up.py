"""Bring-up pins (ISSUE 21): what must hold on a CPU for the program to start
on the chip, and for a failure there to be raised rather than hidden.

* every Pallas kernel cross-lowers for ``tpu`` at ``chip_smoke.py``'s shapes
  (the Pallas TPU lowering runs on any host; only Mosaic needs the chip) —
  the check that would have caught the PR 13 decode kernel being refused;
* importing the package initialises no backend (a chip belongs to one
  process: launcher and fleet-supervisor parents must stay off it);
* the compile cache is placed by one resolver, from outside when asked;
* ``chip_smoke.py`` refuses to run without a TPU, and its legs still run
  end to end at a tiny size;
* a lowering failure inside a captured step, or in a kernel op, raises.
"""

import json
import os
import re
import shutil
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
import chip_smoke  # noqa: E402

SDS = jax.ShapeDtypeStruct


def _lower_for_tpu(fn, *specs) -> str:
    return jax.jit(fn).trace(*specs).lower(
        lowering_platforms=("tpu",)).as_text()


def _kernel_names(text: str):
    assert "tpu_custom_call" in text
    return re.findall(r'kernel_name = "([^"]+)"', text)


# ---------------------------------------------------------------------------
# (a) every Pallas kernel reaches Mosaic MLIR at chip_smoke's shapes
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kv", ["bf16", "int8"])
@pytest.mark.parametrize("page_size", [16, 32, 64])
def test_paged_decode_kernel_lowers_for_tpu(kv, page_size):
    from paddle_tpu.ops import paged_attention as pa
    b, h, d = chip_smoke.SERVE_MAX_BATCH, chip_smoke.HEADS, chip_smoke.HEAD_DIM
    s = chip_smoke.SERVE_MAX_LEN // page_size
    pages = b * s + 1
    dt = jnp.int8 if kv == "int8" else jnp.bfloat16
    assert pa.kernel_eligible(page_size, d, dt, h)
    row = SDS((b, h, d), jnp.bfloat16)
    pool = SDS((pages, 2, 2, h, page_size, d), dt)
    scales = SDS((pages, 2, 2, h), jnp.float32) if kv == "int8" else None
    text = _lower_for_tpu(
        lambda q, kn, vn, pool, sc, tabs, t, layer: pa._kernel_call(
            q, kn, vn, pool, sc, tabs, t, layer, page_size, False),
        row, row, row, pool, scales, SDS((b, s), jnp.int32),
        SDS((b,), jnp.int32), SDS((), jnp.int32))
    assert _kernel_names(text) == ["paged_attention_decode"]


def test_paged_decode_kernel_lowers_with_gqa():
    from paddle_tpu.ops import paged_attention as pa
    b, s, ps, d = 4, 8, 64, 128
    text = _lower_for_tpu(
        lambda q, kn, vn, pool, tabs, t, layer: pa._kernel_call(
            q, kn, vn, pool, None, tabs, t, layer, ps, False),
        SDS((b, 32, d), jnp.bfloat16), SDS((b, 8, d), jnp.bfloat16),
        SDS((b, 8, d), jnp.bfloat16),
        SDS((33, 2, 2, 8, ps, d), jnp.bfloat16), SDS((b, s), jnp.int32),
        SDS((b,), jnp.int32), SDS((), jnp.int32))
    assert _kernel_names(text) == ["paged_attention_decode"]


_FLASH = (chip_smoke.TRAIN_BATCH, chip_smoke.HEADS, chip_smoke.TRAIN_SEQ,
          chip_smoke.HEAD_DIM)


@pytest.mark.parametrize("variant", ["plain", "segments", "dropout"])
def test_flash_kernels_lower_for_tpu(variant):
    """Forward, forward+lse and both backward kernels, with segment ids and
    in-kernel dropout, at the train leg's attention shape."""
    from paddle_tpu.ops import flash_attention as fa
    b, h, l, d = _FLASH
    qkv = SDS((b, h, l, d), jnp.bfloat16)
    segs = SDS((b, l), jnp.int32)
    seed = SDS((1,), jnp.int32)
    bq = fa._fit_block(l, 512)
    sm = 1.0 / float(d) ** 0.5
    kw = {}
    extra = ()
    if variant != "plain":
        extra = (segs, segs)
    if variant == "dropout":
        extra = (segs, segs, seed)
        kw["dropout_p"] = 0.1

    def unpack(rest):
        out = dict(kw)
        if variant != "plain":
            out["q_segs"], out["kv_segs"] = rest[0], rest[1]
        if variant == "dropout":
            out["seed"] = rest[2]
        return out

    def fwd(q, k, v, *rest):
        return fa._pallas_flash(q, k, v, True, sm, bq, bq, False,
                                **unpack(rest))

    def fwd_lse(q, k, v, *rest):
        return fa._pallas_flash(q, k, v, True, sm, bq, bq, False,
                                with_lse=True, **unpack(rest))

    def bwd(q, k, v, out, lse, g, *rest):
        return fa._pallas_flash_bwd(q, k, v, out, lse, g, True, sm, bq, bq,
                                    False, **unpack(rest))

    assert _kernel_names(_lower_for_tpu(fwd, qkv, qkv, qkv, *extra)) == \
        ["flash_fwd"]
    assert _kernel_names(_lower_for_tpu(fwd_lse, qkv, qkv, qkv, *extra)) == \
        ["flash_fwd_lse"]
    lse = SDS((b, h, l), jnp.float32)
    assert _kernel_names(_lower_for_tpu(
        bwd, qkv, qkv, qkv, qkv, lse, qkv, *extra)) == \
        ["flash_bwd_dq", "flash_bwd_dkv"]


@pytest.mark.parametrize("use_sr", [False, True])
def test_q8_adam_kernel_lowers_for_tpu(use_sr):
    """The stacked MLP weight of the train leg at depth 8: (8, 4096, 11008)
    elements in 2048-element quantization blocks."""
    from paddle_tpu.ops.q8_adam_pallas import q8_adam_update
    nb = 8 * chip_smoke.HIDDEN * chip_smoke.FFN // 2048
    q8 = SDS((nb, 2048), jnp.int8)
    sc = SDS((nb, 1), jnp.float32)
    w = SDS((nb, 2048), jnp.bfloat16)
    text = q8_adam_update.trace(
        q8, sc, q8, sc, w, w, SDS((7,), jnp.float32), SDS((1,), jnp.int32),
        use_sr=use_sr, has_wd=True).lower(
            lowering_platforms=("tpu",)).as_text()
    assert _kernel_names(text) == ["q8_adam_update"]


@pytest.mark.parametrize("use_sr", [False, True])
def test_q8_adam_kernel_lowers_in_the_parameters_layout(use_sr):
    """A stacked MLP weight walked where it lies (PR 32), at the widths
    whose rows hold whole 2048-element blocks: `train-4k`'s (7, 4096,
    14336) as (28672, 14336), seven column blocks a row, scales
    (28672, 7). (`chip_smoke`'s 11008 is no multiple of 2048: flat rows,
    the test above.)"""
    from paddle_tpu.ops.q8_adam_pallas import q8_adam_update
    rows, cols = 7 * 4096, 14336
    q8 = SDS((rows, cols), jnp.int8)
    sc = SDS((rows, cols // 2048), jnp.float32)
    w = SDS((rows, cols), jnp.bfloat16)
    text = q8_adam_update.trace(
        q8, sc, q8, sc, w, w, SDS((7,), jnp.float32), SDS((1,), jnp.int32),
        use_sr=use_sr, has_wd=True).lower(
            lowering_platforms=("tpu",)).as_text()
    assert _kernel_names(text) == ["q8_adam_update"]


def test_q8_update_relays_no_parameter(q8_update_fn):
    """The traced int8 update of a [2, 64, 4096] bf16 parameter, routed as
    a lone chip routes it: parameter and gradient reach the kernel through
    one `reshape` to [R, C] each — leading dimensions merged, the last kept
    — the new parameter leaves it through the inverse, and no other
    equation of the trace touches anything of the parameter's size."""
    shape, view = (2, 64, 4096), (128, 4096)
    update, arrays, opt = q8_update_fn(shape)
    assert [a.shape for a in arrays[2:]] == [view, (128, 2), view, (128, 2)]
    jaxpr = jax.make_jaxpr(update)(*arrays).jaxpr
    assert opt._q8_routed == {"in_layout_params": 1, "relaid_elements": 0}

    n = int(np.prod(shape))
    big = [e for e in jaxpr.eqns
           if any(getattr(v.aval, "size", 0) >= n
                  for v in list(e.invars) + list(e.outvars))]
    kernel, = [e for e in big if e.primitive.name in ("pjit", "jit")]
    assert kernel.params["name"] == "q8_adam_update"
    inner = [e.primitive.name for e in kernel.params["jaxpr"].jaxpr.eqns]
    assert inner == ["pallas_call"], inner
    w_in, g_in = jaxpr.invars[:2]
    made_by = {v: e for e in jaxpr.eqns for v in e.outvars}
    for operand, source in zip(kernel.invars[4:6], (w_in, g_in)):
        eqn = made_by[operand]
        assert eqn.primitive.name == "reshape" and eqn.invars[0] is source
        assert operand.aval.shape == view
    reshapes = [e for e in big if e is not kernel]
    assert [e.primitive.name for e in reshapes] == ["reshape"] * 3, big
    for e in reshapes:                   # merges or splits of leading dims
        assert e.invars[0].aval.shape[-1] == e.outvars[0].aval.shape[-1]
    assert reshapes[-1].invars[0] is kernel.outvars[4]
    assert reshapes[-1].outvars[0].aval.shape == shape


# ---------------------------------------------------------------------------
# (b) importing the package takes no device
# ---------------------------------------------------------------------------

def test_import_initialises_no_backend():
    """With ``JAX_PLATFORMS`` naming a backend that does not exist, any
    backend initialisation raises — so a clean import proves none
    happened."""
    code = ("import paddle_tpu, paddle_tpu.serving, "
            "paddle_tpu.distributed.launch\n"
            "from jax._src import xla_bridge\n"
            "assert not xla_bridge._backends, xla_bridge._backends\n"
            "print('IMPORT_OK')\n")
    env = dict(os.environ, JAX_PLATFORMS="no_such_backend", PYTHONPATH=REPO)
    r = subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0 and "IMPORT_OK" in r.stdout, r.stderr[-2000:]


def test_default_generator_key_matches_jax():
    from paddle_tpu.core.random import _host_key
    for seed in (0, 1, 2024, 2 ** 31 - 1, 2 ** 32 - 1, 2 ** 32 + 5, -7):
        np.testing.assert_array_equal(
            _host_key(seed), np.asarray(jax.random.PRNGKey(seed)))


# ---------------------------------------------------------------------------
# (c) one compile-cache resolver
# ---------------------------------------------------------------------------

def test_compile_cache_resolver():
    resolve = paddle._compile_cache_dir
    # placed from outside: the code sets nothing, on any platform
    env = {"JAX_COMPILATION_CACHE_DIR": "/somewhere/else"}
    assert resolve(env, None) is None and resolve(env, "cpu") is None
    # unset: a fixed path inside the checkout — twice the same, and no
    # tempdir, pid or timestamp in it
    a, b = resolve({}, None), resolve({}, "tpu")
    assert a == b == os.path.join(REPO, ".jax_cache")
    # the CPU tier stays cold (tests/conftest.py says why)
    assert resolve({}, "cpu") is None


def test_compile_cache_has_one_setter_in_the_tree():
    needle = "jax_compilation_" + "cache_dir"
    hits = []
    for root, dirs, files in os.walk(REPO):
        dirs[:] = [d for d in dirs if not d.startswith(".")
                   and d not in ("chiprun_out", "chip_work", "__pycache__")]
        for f in files:
            if f.endswith(".py"):
                path = os.path.join(root, f)
                with open(path, encoding="utf-8") as fh:
                    for n, line in enumerate(fh, 1):
                        if needle in line:
                            hits.append(f"{os.path.relpath(path, REPO)}:{n}")
    assert len(hits) == 1 and hits[0].startswith("paddle_tpu/__init__.py"), \
        hits


def test_this_process_runs_without_a_compile_cache():
    # the suite is pinned to the CPU backend, so the resolver set nothing
    assert jax.config.values["jax_compilation_" + "cache_dir"] is None \
        or os.environ.get("JAX_COMPILATION_CACHE_DIR")


# ---------------------------------------------------------------------------
# (d) chip_smoke.py: refuses without a TPU; its legs run at a tiny size
# ---------------------------------------------------------------------------

def test_chip_smoke_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                       env=env, cwd=REPO, capture_output=True, text=True,
                       timeout=300)
    assert r.returncode != 0
    assert "jax found platform 'cpu'" in r.stdout
    assert '"ok"' not in r.stdout          # no result line


def test_chip_smoke_alone_in_a_directory_fails(tmp_path):
    """A directory that holds chip_smoke.py and nothing else of the repo:
    non-zero, no result line (on the chip it dies importing paddle_tpu)."""
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    r = subprocess.run([sys.executable, "chip_smoke.py"], env=env,
                       cwd=tmp_path, capture_output=True, text=True,
                       timeout=300)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout


def test_chip_smoke_last_line_is_the_result_object(monkeypatch, capsys):
    """The driver reads the last line of stdout: exactly ``ok`` and
    ``device``, and in ``device`` exactly ``platform``/``kind``/``count``.
    What the legs observed goes on the SUMMARY line above it."""
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    monkeypatch.setattr(
        chip_smoke, "run_leg",
        lambda name, budget: {"leg": name, "device": dict(device)})
    assert chip_smoke.main() == 0
    lines = capsys.readouterr().out.strip().splitlines()
    last = json.loads(lines[-1])
    assert last == {"ok": True, "device": device}
    assert set(last) == {"ok", "device"}
    assert set(last["device"]) == {"platform", "kind", "count"}
    assert type(last["device"]["count"]) is int
    assert lines[-2].startswith("SUMMARY ")
    summary = json.loads(lines[-2][len("SUMMARY "):])
    assert list(summary)[-1] == "claim" and summary["claim"] is None
    assert set(summary["legs"]) == {"train", "serve"}     # one chip: no hybrid
    # a failed leg prints no result object at all
    monkeypatch.setattr(chip_smoke, "run_leg", lambda name, budget: None)
    assert chip_smoke.main() != 0
    assert '"ok"' not in capsys.readouterr().out


def _tiny():
    from paddle_tpu.models.llama import LlamaConfig
    return LlamaConfig.tiny(vocab=512, hidden=256, layers=2, heads=2,
                            kv_heads=2, inter=512, max_pos=2048)


# the legs switch process-wide observability on (and the train leg a debug
# flag), as a script may; the ``metrics`` fixture and the finally put the
# test process back

def test_chip_smoke_train_leg_runs_tiny(monkeypatch, metrics):
    from paddle_tpu.core import step_capture
    monkeypatch.setenv("PADDLE_TPU_STEP_CAPTURE", "auto")
    step_capture.stats_clear()
    try:
        res = chip_smoke.leg_train(depth=2, seq=128, steps=2,
                                   config=_tiny(), on_chip=False)
    finally:
        paddle.set_flags({"FLAGS_to_static_capture_lowered": False})
    assert res["capture"]["bypasses"] == {} and res["capture"]["hits"] > 0
    assert res["losses"][-1] < res["losses"][0]
    json.dumps(res)


def test_chip_smoke_serve_leg_runs_tiny(metrics):
    from paddle_tpu.observability import trace
    res = chip_smoke.leg_serve(depth=2, config=_tiny(), on_chip=False)
    assert not [b for b in trace.health()["components"]
                if b.startswith("serving.")], "a beacon outlived the leg"
    assert res["compiles_after_warmup"] == 0
    assert set(res["pool_copies_by_bucket"]) == {"1", "4", "16"}
    assert res["decode_tier"] == "dense"   # auto on a CPU
    shared, asked = res["prefix_shared_prefill"]
    assert shared < asked
    json.dumps(res)


@pytest.mark.parametrize("line,want", [
    # the program before ISSUE 26, compiled for a v5e: the argument copied
    # into the scatter's layout, and a layer's scatter copied back
    ("  %copy.1350 = bf16[1025,7,2,8,64,128]{5,3,4,2,1,0:T(8,128)(2,1)} "
     "copy(%arg_arrays_3_.1), sharding={replicated}", 1),
    ("  %copy.1352 = bf16[1025,7,2,8,64,128]{5,4,3,2,1,0:T(8,128)(2,1)} "
     "copy(%fusion.4), metadata={op_name=\"jit(pure_fn)/scatter\"}", 1),
    ("  %copy.9 = s8[1025,7,2,8,64,128]{5,4,3,2,1,0} copy(%p.1)", 1),
    # another shape, another op, the pool as an operand: not counted
    ("  %copy.3 = bf16[16,7,2,8,64,128]{5,4,3,2,1,0} copy(%gather.2)", 0),
    ("  %fusion.4 = bf16[1025,7,2,8,64,128]{5,4,3,2,1,0} fusion(%p.1)", 0),
    ("  %copy.5 = bf16[8,128]{1,0} copy(bf16[1025,7,2,8,64,128] %p.1)", 0),
])
def test_chip_smoke_counts_pool_shaped_copies(line, want):
    assert chip_smoke.pool_copies(
        "HloModule jit_pure_fn\n" + line + "\n",
        (1025, 7, 2, 8, 64, 128)) == want


def _reset_fleet():
    """Fleet state is process-global: put it back for the next test."""
    from paddle_tpu.distributed import fleet, topology
    topology.set_hybrid_communicate_group(None)
    fleet._fleet_initialized = False


def test_chip_smoke_hybrid_leg_runs_tiny(monkeypatch, metrics):
    monkeypatch.setenv("PADDLE_TPU_STEP_CAPTURE", "auto")
    try:
        res = chip_smoke.leg_hybrid(depth=2, seq=128, steps=2,
                                    config=_tiny(), on_chip=False)
    finally:
        paddle.set_flags({"FLAGS_to_static_capture_lowered": False})
        _reset_fleet()
    assert len(res["shard_shapes"]) == 4
    assert res["losses"][-1] < res["losses"][0]
    json.dumps(res)


def test_chip_smoke_depth_model():
    limit = 16_909_336_064                 # bytes_limit of one TPU v5e
    for bytes_of in (chip_smoke.train_bytes, chip_smoke.serve_bytes):
        d = chip_smoke.pick_depth(bytes_of, limit)
        assert 1 <= d < 32
        assert bytes_of(d) <= 0.9 * limit < bytes_of(d + 1)


# ---------------------------------------------------------------------------
# (e) a lowering failure raises: no demotion, no CPU re-execution
# ---------------------------------------------------------------------------

@pytest.fixture()
def refused_kernel(monkeypatch):
    """Stand-in for a kernel Mosaic refuses: traces fine, fails to lower
    (jitted) or to run (eager) with the error type Pallas uses."""
    from jax.extend import core as jex_core
    from jax.interpreters import mlir

    from paddle_tpu.ops import flash_attention as fa

    prim = jex_core.Primitive("refused_by_mosaic")
    prim.def_abstract_eval(lambda x: x)

    def refuse(*_a, **_k):
        raise NotImplementedError("Mosaic refused the kernel (injected)")

    prim.def_impl(refuse)
    mlir.register_lowering(prim, refuse)
    monkeypatch.setattr(
        fa, "_pallas_flash",
        lambda q, *a, **k: prim.bind(q))
    return prim


def _qkv():
    rng = np.random.default_rng(0)
    return [paddle.to_tensor(rng.normal(size=(1, 128, 2, 8))
                             .astype(np.float32)) for _ in range(3)]


def test_lowering_error_in_captured_step_raises(refused_kernel, monkeypatch):
    from paddle_tpu.core import fallback, step_capture
    from paddle_tpu.jit.to_static import LoweringError
    from paddle_tpu.nn import functional as F

    monkeypatch.setenv("PADDLE_TPU_STEP_CAPTURE", "auto")
    step_capture.stats_clear()
    runs = []

    def body(q, k, v):
        runs.append(1)
        return F.flash_attention(q, k, v, causal=True).sum()

    step = paddle.jit.capture_step(body)
    with pytest.raises(LoweringError, match="Mosaic refused"):
        step(*_qkv())
    # traced once, never re-run on the eager tier, nothing left the device
    assert len(runs) == 1
    assert step_capture.capture_info()["bypasses"] == {}
    assert not fallback.fallback_ops()


def test_refused_kernel_op_never_degrades_to_cpu(refused_kernel):
    from paddle_tpu.core import fallback
    from paddle_tpu.nn import functional as F

    assert fallback.enabled()
    with pytest.raises(NotImplementedError, match="Mosaic refused"):
        F.flash_attention(*_qkv(), causal=True)
    assert not fallback.fallback_ops()


def test_kernel_ops_cover_every_pallas_op():
    """Every op name the Pallas modules dispatch under is exempt from the
    CPU fallback."""
    from paddle_tpu.core.fallback import KERNEL_OPS
    names = set()
    for mod in ("flash_attention.py", "paged_attention.py"):
        with open(os.path.join(REPO, "paddle_tpu", "ops", mod)) as fh:
            names |= set(re.findall(r'apply\("([a-z_0-9]+)"', fh.read()))
    assert names and names <= KERNEL_OPS, names - KERNEL_OPS


# ---------------------------------------------------------------------------
# Mosaic kernels cannot be partitioned automatically: per shard under a mesh
# ---------------------------------------------------------------------------

@pytest.fixture()
def hybrid_mesh():
    """dp2 x sharding2 x mp2 over the 8 virtual devices."""
    from paddle_tpu.distributed import fleet
    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 2, "sharding_degree": 2,
                               "mp_degree": 2}
    fleet.init(is_collective=True, strategy=strategy)
    yield fleet.get_hybrid_communicate_group().mesh
    _reset_fleet()


def _flash_fwd_bwd(segments: bool):
    """Output and q/k/v grads of one compiled flash-attention step."""
    from paddle_tpu.nn import functional as F
    rng = np.random.default_rng(0)
    b, l, h, d = 4, 128, 4, 16
    q, k, v = (paddle.to_tensor(rng.normal(size=(b, l, h, d))
                                .astype(np.float32), stop_gradient=False)
               for _ in range(3))
    kw = {}
    if segments:
        ids = paddle.to_tensor(np.tile(np.repeat(np.arange(2), l // 2),
                                       (b, 1)).astype(np.int32))
        kw = {"q_segment_ids": ids, "kv_segment_ids": ids}

    @paddle.jit.to_static
    def step(q, k, v):
        out = F.flash_attention(q, k, v, causal=True, **kw)
        (out * out).sum().backward()
        return out, q.grad, k.grad, v.grad

    return [np.asarray(t._data) for t in step(q, k, v)]


@pytest.mark.parametrize("segments", [False, True])
def test_flash_kernels_run_per_shard_under_a_hybrid_mesh(segments, request):
    """What the four-chip run found: the TPU lowering refuses a Mosaic
    kernel inside an auto-partitioned program. Under a fleet mesh the
    kernels are shard_mapped — same numbers as on one device."""
    from paddle_tpu.ops import flash_attention as fa
    assert fa._shard_axes(4, 4) is None
    want = _flash_fwd_bwd(segments)
    mesh = request.getfixturevalue("hybrid_mesh")
    assert fa._shard_axes(4, 4) == (mesh, ("dp", "sharding"), ("mp",))
    assert fa._shard_axes(3, 4) is False       # 4 data shards, 3 rows
    got = _flash_fwd_bwd(segments)
    for w, g in zip(want, got):
        np.testing.assert_array_equal(g, w)


def test_flash_kernel_inside_a_manual_region_shards_the_rest(hybrid_mesh):
    """Inside an enclosing shard_map (the pipeline engine maps pp/dp
    manually) only the still-automatic axes are left to shard over."""
    from jax.sharding import PartitionSpec as P

    from paddle_tpu.ops import flash_attention as fa
    rng = np.random.default_rng(1)
    q, k, v = (jnp.asarray(rng.normal(size=(4, 4, 128, 16))
                           .astype(np.float32)) for _ in range(3))
    seen = []

    def inner(q, k, v):
        seen.append(fa._shard_axes(q.shape[0], q.shape[1])[1:])
        return fa._pallas_flash(q, k, v, True, 0.25, 128, 128, True)

    got = jax.jit(jax.shard_map(
        inner, mesh=hybrid_mesh, in_specs=P("dp"), out_specs=P("dp"),
        axis_names=frozenset({"dp"}), check_vma=False))(q, k, v)
    assert seen == [(("sharding",), ("mp",))]
    topology_free = fa._pallas_flash_local(
        q, k, v, True, 0.25, 128, 128, True, False, None, None, 0.0, None)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(topology_free))


def test_flash_kernels_lower_for_tpu_under_a_mesh(hybrid_mesh):
    """The TPU lowering takes a Mosaic kernel only where the whole mesh is
    manual ("Mosaic kernels cannot be automatically partitioned") — and
    that check runs in the lowering, so it can be made here: the
    shard_mapped kernels cross-lower for ``tpu`` with sharded operands,
    at the top level and inside a partially manual region."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from paddle_tpu.ops import flash_attention as fa
    q = SDS((4, 8, 1024, 128), jnp.bfloat16,
            sharding=NamedSharding(hybrid_mesh, P("dp", "mp")))
    lse = SDS((4, 8, 1024), jnp.float32,
              sharding=NamedSharding(hybrid_mesh, P("dp", "mp")))

    def fwd(q, k, v):
        return fa._pallas_flash(q, k, v, True, 0.088, 512, 512, False,
                                with_lse=True)

    def bwd(q, k, v, out, lse, g):
        return fa._pallas_flash_bwd(q, k, v, out, lse, g, True, 0.088, 512,
                                    512, False)

    assert _kernel_names(_lower_for_tpu(fwd, q, q, q)) == ["flash_fwd_lse"]
    assert _kernel_names(_lower_for_tpu(bwd, q, q, q, q, lse, q)) == \
        ["flash_bwd_dq", "flash_bwd_dkv"]
    nested = jax.shard_map(
        lambda q, k, v: fa._pallas_flash(q, k, v, True, 0.088, 512, 512,
                                         False),
        mesh=hybrid_mesh, in_specs=P("dp"), out_specs=P("dp"),
        axis_names=frozenset({"dp"}), check_vma=False)
    assert _kernel_names(_lower_for_tpu(nested, q, q, q)) == ["flash_fwd"]


def test_q8_kernel_is_for_one_device(hybrid_mesh):
    from paddle_tpu.optimizer import _on_one_device
    assert not _on_one_device()


# ---------------------------------------------------------------------------
# the tpu place is the TPU
# ---------------------------------------------------------------------------

def test_tpu_place_is_platform_tpu_only():
    from paddle_tpu import device
    assert device._accelerator_type() == "cpu"
    assert device._devices_of_type("tpu") == ()
    with pytest.raises(RuntimeError, match="no 'tpu' devices"):
        device.TPUPlace().jax_device()
    before = device.current_place()
    with pytest.raises(RuntimeError, match="no 'tpu' devices"):
        device.set_device("tpu")
    assert device.current_place() == before    # a refused place sets nothing
    assert device.describe() == {"platform": "cpu", "kind": "cpu",
                                 "count": len(jax.devices())}
    assert not hasattr(device, "force_platform")
