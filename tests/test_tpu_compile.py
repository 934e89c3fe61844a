"""What only the chip's compiler can say, asked without the chip: the
serving engine's decode programs, compiled for a DESCRIBED TPU v5e (the
TPU compiler is installed here; nothing runs), hold no copy of the page
pool (ISSUE 26) — with the tokens carried on the device between steps
(ISSUE 28) as without. And the scanned, checkpointed train layers hold
the flash forward kernel once, not twice (ISSUE 30).

The program before ISSUE 26 held 1 / 8 / 8 pool-shaped copies in buckets
1 / 4 / 16 at these widths: the pool argument was not donated, and each
layer's batched scatter ran in a layout of its own (page rows above the
heads) that the Pallas kernel cannot read, so the compiler copied the pool
into it and back around every layer. Interpret mode and the CPU backend
see none of that.

The topology is described inside a fixture, never at import (one process
at a time may load libtpu; every xdist worker imports this file), and all
such compiles live in this one file.
"""

import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

import paddle_tpu as paddle
from paddle_tpu import observability as obs
from paddle_tpu import serving
from paddle_tpu.core.tensor import Tensor as T
from paddle_tpu.ops import paged_attention as pa

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from chip_smoke import pool_copies  # noqa: E402  (the count the chip run makes)

# Mistral-7B's KV geometry (8 KV heads x 128, pages of 64) on a small body
KV_HEADS, HEAD_DIM, PAGE, MAX_LEN, SLOTS, LAYERS = 8, 128, 64, 1024, 8, 2
BUCKETS = (1, 4, 8)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def llama():
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    paddle.seed(3)
    model = LlamaForCausalLM(LlamaConfig.tiny(
        vocab=256, hidden=KV_HEADS * HEAD_DIM, layers=LAYERS, heads=KV_HEADS,
        kv_heads=KV_HEADS, inter=256, max_pos=MAX_LEN))
    model.to(dtype="bfloat16")
    model.eval()
    yield model
    import gc
    del model
    gc.collect()


def _compiled_for_chip(prog, one_chip):
    """The program's last call, lowered again for the described chip."""
    jitted, state_specs, arg_specs = prog._last_lowered

    def on_chip(specs):
        return [jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip)
                for s in specs]

    return jitted.lower(on_chip(state_specs), on_chip(arg_specs)).compile()


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
def test_decode_programs_hold_no_copy_of_the_pool(
        llama, one_chip, kv_dtype, monkeypatch):
    cfg = llama.config
    # the compiled kernel, not the interpreter: the trace is what the chip
    # would get; its CPU call fails at lowering, after the trace is kept
    monkeypatch.setattr(pa, "kernel_interpret", lambda: False)
    prefill_fn, step_fn = llama.serving_callables(MAX_LEN)
    eng = serving.Engine(prefill_fn, step_fn, serving.ServingConfig(
        num_layers=cfg.num_hidden_layers, num_heads=KV_HEADS,
        head_dim=HEAD_DIM, max_len=MAX_LEN, max_batch=SLOTS,
        buckets=BUCKETS, page_size=PAGE, compute_dtype="bfloat16",
        kv_dtype=kv_dtype, paged_attention="on"))
    assert eng._paged_path == "kernel"
    shape = eng.kv.pool.shape
    paddle.set_flags({"FLAGS_to_static_capture_lowered": True})
    try:
        for bucket in BUCKETS:
            with pytest.raises(Exception, match="interpret mode"):
                eng.programs.warm(buckets=[bucket])
            assert not eng.kv.pool.is_deleted()    # it never ran
            # ISSUE 28: the program takes the step before's tokens from the
            # device — one shape for every bucket — and a selection per
            # row, after the pool and its scales, and gives its own tokens
            # back in that shape; none of that is donated
            arg_specs = eng.programs.decode_program._last_lowered[2]
            assert [(a.shape, a.dtype) for a in arg_specs[-2:]] == [
                ((BUCKETS[-1],), jnp.int32), ((bucket,), jnp.int32)]
            assert arg_specs[3].shape == shape
            assert not eng.programs.no_carry._data.is_deleted()
            compiled = _compiled_for_chip(eng.programs.decode_program,
                                          one_chip)
            outs = jax.tree_util.tree_leaves(compiled.out_info[0])
            assert (outs[-1].shape, outs[-1].dtype) == \
                ((BUCKETS[-1],), jnp.int32)
            text = compiled.as_text()
            assert "paged_attention_decode" in text, \
                "the decode kernel is not in the program"
            assert pool_copies(text, shape) == 0, \
                f"bucket {bucket}: the decode program copies the pool"
            # the pool goes in and comes out as one buffer: no second one
            # among the temporaries
            pool_bytes = int(np.prod(shape)) * eng.kv.pool.dtype.itemsize
            mem = compiled.memory_analysis()
            assert mem.temp_size_in_bytes < pool_bytes // 2, \
                (bucket, mem.temp_size_in_bytes, pool_bytes)
    finally:
        paddle.set_flags({"FLAGS_to_static_capture_lowered": False})


def test_prefill_program_holds_no_copy_of_the_pool(llama, one_chip):
    cfg = llama.config
    prefill_fn, step_fn = llama.serving_callables(MAX_LEN)
    eng = serving.Engine(prefill_fn, step_fn, serving.ServingConfig(
        num_layers=cfg.num_hidden_layers, num_heads=KV_HEADS,
        head_dim=HEAD_DIM, max_len=MAX_LEN, max_batch=SLOTS,
        buckets=BUCKETS, page_size=PAGE, compute_dtype="bfloat16",
        kv_dtype="bf16", paged_attention="off"))
    paddle.set_flags({"FLAGS_to_static_capture_lowered": True})
    try:
        eng.warmup(prompt_lens=[96])               # runs on the CPU, dense
        compiled = _compiled_for_chip(eng.programs.prefill_program,
                                      one_chip)
    finally:
        paddle.set_flags({"FLAGS_to_static_capture_lowered": False})
    assert pool_copies(compiled.as_text(), eng.kv.pool.shape) == 0


def test_a_batched_scatter_would_copy_the_pool(one_chip):
    """The positive control, and the cause on record: the write the decode
    program used to make, one scatter over the batch rows, is compiled
    with the pool copied into the scatter's layout and back."""
    pool = jax.ShapeDtypeStruct((129, LAYERS, 2, KV_HEADS, PAGE, HEAD_DIM),
                                jnp.bfloat16, sharding=one_chip)
    rows = jax.ShapeDtypeStruct((4, LAYERS, 2, KV_HEADS, HEAD_DIM),
                                jnp.bfloat16, sharding=one_chip)
    ids = jax.ShapeDtypeStruct((4,), jnp.int32, sharding=one_chip)
    q = jax.ShapeDtypeStruct((4, KV_HEADS, HEAD_DIM), jnp.bfloat16,
                             sharding=one_chip)
    tables = jax.ShapeDtypeStruct((4, MAX_LEN // PAGE), jnp.int32,
                                  sharding=one_chip)

    def step(pool, rows, pids, off, q, tables):
        out = pa.paged_attention(q, q, q, pool, None, tables, off,
                                 jnp.asarray(0), page_size=PAGE)
        return out, pool.at[pids, :, :, :, off, :].set(rows)

    text = jax.jit(step, donate_argnums=(0,)).lower(
        pool, rows, ids, ids, q, tables).compile().as_text()
    assert pool_copies(text, pool.shape) >= 1


@pytest.mark.parametrize("recompute", [True, False])
def test_scanned_layers_run_the_flash_forward_once(
        one_chip, recompute, monkeypatch, scanned_layers_fn,
        flash_kernels_not_interpreted):
    """ISSUE 30: forward and backward of the scanned decoder layers at
    Mistral's head geometry (4:1 heads of 128), compiled for the chip. The
    checkpointed layer keeps flash's output and log-sum-exp by name, so the
    compiled backward loop calls the two backward kernels and no second
    ``flash_fwd_lse`` — the same kernel calls as with no checkpoint at all,
    in less memory (before ISSUE 30: two forward calls)."""
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.ops import nn_ops

    monkeypatch.setattr(nn_ops, "_sdpa_flash_backend_ok", lambda: True)
    cfg = LlamaConfig.tiny(vocab=256, hidden=4 * HEAD_DIM, layers=LAYERS,
                           heads=4, kv_heads=1, inter=1024, max_pos=512)
    cfg.scan_layers, cfg.recompute = True, recompute
    paddle.seed(3)
    net = LlamaForCausalLM(cfg)
    net.to(dtype="bfloat16")
    f, stacked = scanned_layers_fn(net.model)

    def on_chip(shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)

    grad = jax.jit(jax.grad(
        lambda *a: f(*a).astype(jnp.float32).sum(),
        argnums=tuple(range(1, 1 + len(stacked)))))
    compiled = grad.lower(on_chip((1, 512, 4 * HEAD_DIM)),
                          *[on_chip(a.shape) for a in stacked]).compile()
    calls = [line for line in compiled.as_text().splitlines()
             if "custom-call(" in line and "tpu_custom_call" in line]

    def count(kernel):
        return sum(kernel in c for c in calls)

    assert (count("flash_fwd_lse"), count("flash_bwd_dq"),
            count("flash_bwd_dkv")) == (1, 1, 1), calls


# ---------------------------------------------------------------------------
# ISSUE 31: the two decode kernels of a model with sparse pages and a state
# per slot, at MiniCPM-SALA's widths (32 query heads on 2 KV heads of 128,
# pages of 64, 32 rows, 64 chosen pages a head; 32 state heads of 128 x 128)
# ---------------------------------------------------------------------------

def _spec(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def test_sparse_decode_kernel_compiles_for_the_chip(one_chip):
    from paddle_tpu.ops import sparse_attention as sa
    b, h, hkv, d, ps, pages = 32, 32, 2, 128, 64, 4097
    bf, i32 = jnp.bfloat16, jnp.int32
    text = jax.jit(lambda q, kn, vn, pool, tab, lens: sa._sparse_kernel_call(
        q, kn, vn, pool, tab, lens, 1, ps, False)).lower(
        _spec(one_chip, (b, h, d), bf), _spec(one_chip, (b, hkv, d), bf),
        _spec(one_chip, (b, hkv, d), bf),
        _spec(one_chip, (pages, 2, 2, hkv, ps, d), bf),
        _spec(one_chip, (b, hkv, 64), i32),
        _spec(one_chip, (b, hkv, 64), i32)).compile().as_text()
    assert "sparse_attention_decode" in text and "tpu_custom_call" in text
    assert not pool_copies(text, (pages, 2, 2, hkv, ps, d))
    # ISSUE 37: the pool is handed over once, where it lies, and the kernel
    # copies a live row's chosen pages out of it (before: 64 operands, a
    # block of one page each)
    (call,) = [line for line in text.splitlines()
               if "custom-call(" in line and "tpu_custom_call" in line]
    operands = call.split("operand_layout_constraints={")[1].split("}}")[0]
    assert operands.count(f"bf16[{pages},2,2,{hkv},{ps},{d}]") == 1
    assert operands.count("[") == 8, operands


def test_linear_state_kernel_compiles_for_the_chip_and_writes_in_place(
        one_chip):
    from paddle_tpu.ops import linear_attention as la
    b, h, d, rows, layers = 32, 32, 128, 9, 6
    bf = jnp.bfloat16
    slopes = la.lightning_slopes(h, 10, 32)
    compiled = jax.jit(
        lambda q, k, v, pool, r: la._decode_kernel_call(
            q, k, v, slopes, pool, r, 3, d ** -0.5, False),
        donate_argnums=(3,)).lower(
        *[_spec(one_chip, (b, h, d), bf)] * 3,
        _spec(one_chip, (rows, layers, h, d, d), jnp.float32),
        _spec(one_chip, (b,), jnp.int32)).compile()
    text = compiled.as_text()
    assert "linear_state_decode" in text and "tpu_custom_call" in text
    # the donated pool is updated where it lies: no copy of its shape
    assert not pool_copies(text, (rows, layers, h, d, d))


def test_gated_delta_kernels_compile_for_the_chip_and_write_in_place(
        one_chip):
    """ISSUE 33: the delta-rule update and the convolution's tail at
    ``longform-decode``'s shapes — 64 rows, 32 value heads of 128 x 128, 8192
    channels laid out 64 x 128, six state layers."""
    from paddle_tpu.ops import linear_attention as la
    b, h, d, rows, layers, taps = 64, 32, 128, 9, 6, 4
    f32 = jnp.float32
    text = jax.jit(
        lambda q, k, v, g, beta, pool, r: la.gated_delta_decode(
            q, k, v, g, beta, pool, r, 3),
        donate_argnums=(5,)).lower(
        *[_spec(one_chip, (b, h, d), f32)] * 3,
        *[_spec(one_chip, (b, h), f32)] * 2,
        _spec(one_chip, (rows, layers, h, d, d), f32),
        _spec(one_chip, (b,), jnp.int32)).compile().as_text()
    assert "gated_delta_decode" in text and "tpu_custom_call" in text
    assert not pool_copies(text, (rows, layers, h, d, d))
    tail = (rows, layers, taps - 1, 64, 128)
    text = jax.jit(
        lambda x, w, pool, r: la.conv_tail_decode(x, w, pool, r, 3),
        donate_argnums=(2,)).lower(
        _spec(one_chip, (b, 64, 128), f32),
        _spec(one_chip, (taps, 64, 128), f32), _spec(one_chip, tail, f32),
        _spec(one_chip, (b,), jnp.int32)).compile().as_text()
    assert "gated_delta_decode_conv" in text and "tpu_custom_call" in text
    assert not pool_copies(text, tail)


def test_qwen3_next_decode_program_compiles_with_no_copy_of_any_pool(
        one_chip, monkeypatch):
    """ISSUE 33: the decode program of a Gated DeltaNet and a gated
    attention layer at the published widths (head_dim 256 on 2 KV heads,
    32 value heads of 128 x 128, a router of 512) with 8 experts held:
    both delta kernels, the paged kernel and the grouped matmuls are in it,
    and neither the pages nor either part of the state is copied."""
    from paddle_tpu.models.qwen3_next import (Qwen3NextConfig,
                                              Qwen3NextForCausalLM)
    monkeypatch.setattr(pa, "kernel_interpret", lambda: False)
    paddle.seed(4)
    cfg = Qwen3NextConfig(layers_run=(2, 3), experts_held=(0, 8),
                          vocab_held=(0, 512), dtype="bfloat16")
    model = Qwen3NextForCausalLM(cfg)
    model.eval()
    eng = serving.Engine(*model.serving_callables(512, block=128),
                         serving.ServingConfig(
        num_layers=2, num_heads=cfg.num_key_value_heads,
        head_dim=cfg.head_dim, max_len=512, max_batch=8, buckets=(8,),
        page_size=64, compute_dtype="bfloat16", kv_dtype="bf16",
        layer_kinds=cfg.layer_kinds, state_shape=cfg.state_shapes,
        state_snapshot_tokens=128, paged_attention="on"))
    assert eng._paged_path == "kernel" and eng.index is None
    obs.enable()
    paddle.set_flags({"FLAGS_to_static_capture_lowered": True})
    try:
        with pytest.raises(Exception, match="interpret mode"):
            eng.programs.warm(buckets=[8])
        # ISSUE 35: 8 query heads to a KV head take the row-walking kernel
        assert obs.snapshot()["serving.paged_attention_row_walk_layers"] == 1
        text = _compiled_for_chip(eng.programs.decode_program,
                                  one_chip).as_text()
    finally:
        paddle.set_flags({"FLAGS_to_static_capture_lowered": False})
        obs.disable()
    for name in ("gated_delta_decode", "gated_delta_decode_conv",
                 "paged_attention_decode", "ragged-dot"):
        assert name in text, name
    assert "sparse_attention_decode" not in text
    for shape in [eng.kv.pool.shape] + [p.shape for p in eng.state.parts]:
        assert pool_copies(text, shape) == 0, shape


# ---------------------------------------------------------------------------
# ISSUE 35: the grouped paged-decode kernel walks a row's own page groups with
# copies of its own out of the pool where it lies. Its calls in the serving
# cells, at their real shapes: Command A+'s full layer and window layer (32
# rows, 128 query heads on 8 KV heads of 128), Qwen3-Next's (64 rows, 16 on 2
# of 256), Mellum 2's full layer at 1576 table columns (a 100,864-token
# context; the table is scalar-prefetched whole) and its window layer over a
# pool with kept boundary pages (24 rows, 32 on 4 of 128); a bf16 pool as the
# cells hold, int8 and float32 as the tests do. The page buffer (2 x 8 pages
# of K and V) is 4 / 2 / 8 / 2 / 2 MiB.
# ---------------------------------------------------------------------------

ROW_WALK_CALLS = {
    # rows, KV heads, query heads to one, head_dim, layers a page, pages,
    # table columns, window
    "command-a-full": (32, 8, 16, 128, 1, 6401, 200, None),
    "command-a-window": (32, 8, 16, 128, 3, 2113, 66, 4096),
    "qwen3-next": (64, 2, 8, 256, 2, 10753, 168, None),
    "mellum-full": (24, 4, 8, 128, 1, 24 * 1576 + 1, 1576, None),
    "mellum-window": (24, 4, 8, 128, 3, 24 * 18 + 1 + 1024, 18, 1024),
}


def _row_walk_call(spec, call, kv):
    """The kernel call of ``ROW_WALK_CALLS[call]`` over a ``kv`` pool and
    its arguments' shapes, each made by ``spec(shape, dtype)``."""
    b, h_kv, rep, d, layers, pages, cols, window = ROW_WALK_CALLS[call]
    pool = (pages, layers, 2, h_kv, PAGE, d)
    bf = jnp.bfloat16
    args = [spec((b, h_kv * rep, d), bf), spec((b, h_kv, d), bf),
            spec((b, h_kv, d), bf), spec(pool, jnp.dtype(kv)),
            spec((b, cols), jnp.int32), spec((b,), jnp.int32),
            spec((), jnp.int32)]
    if kv == "int8":
        args.append(spec(pool[:4], jnp.float32))

    def call_(q, kn, vn, pool, tables, t, layer, scales=None):
        return pa._kernel_call(q, kn, vn, pool, scales, tables, t, layer,
                               PAGE, False, window)

    return call_, args, pool


@pytest.mark.parametrize("kv", ["bfloat16", "int8", "float32"])
@pytest.mark.parametrize("call", sorted(ROW_WALK_CALLS))
def test_row_walk_kernel_compiles_at_the_cells_shapes(one_chip, call, kv):
    fn, args, pool = _row_walk_call(
        lambda shape, dt: _spec(one_chip, shape, dt), call, kv)
    rep = ROW_WALK_CALLS[call][2]
    assert pa.kernel_eligible(PAGE, pool[-1], jnp.dtype(kv), pool[3], rep)
    compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    assert "paged_attention_decode" in text and "tpu_custom_call" in text
    # the kernel takes the pool where it lies: no copy of it, nothing of its
    # size beside it
    assert not pool_copies(text, pool)
    pool_bytes = int(np.prod(pool)) * jnp.dtype(kv).itemsize
    assert compiled.memory_analysis().temp_size_in_bytes < pool_bytes // 8


def test_row_walk_kernel_lowers_to_the_same_text_in_every_process():
    """What the compile cache's key is made from does not change from run
    to run: the Command A+ full-layer call, lowered for the TPU in two
    fresh processes under different hash seeds, is the same text (the
    Mosaic kernel's serialized body is inside it). Lowering for a platform
    needs no device and no TPU library, so the children load none."""
    import subprocess
    child = (
        "import hashlib, sys; import jax, jax.numpy as jnp\n"
        "sys.path[:0] = [%r, %r]\n"
        "from test_tpu_compile import _row_walk_call\n"
        "fn, args, _ = _row_walk_call(jax.ShapeDtypeStruct, "
        "'command-a-full', 'bfloat16')\n"
        "text = jax.jit(fn).trace(*args).lower("
        "lowering_platforms=('tpu',)).as_text()\n"
        "assert 'tpu_custom_call' in text\n"
        "print(hashlib.sha256(text.encode()).hexdigest(), len(text))\n"
    ) % (os.path.dirname(os.path.abspath(__file__)),
         os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    said = []
    for hash_seed in ("1", "2"):
        env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONHASHSEED=hash_seed)
        run = subprocess.run([sys.executable, "-c", child], env=env,
                             capture_output=True, text=True, timeout=300)
        assert run.returncode == 0, run.stderr[-2000:]
        said.append(run.stdout.strip().splitlines()[-1])
    assert said[0] == said[1], said


def test_command_a_plus_decode_program_walks_rows_with_no_copy_of_a_pool(
        one_chip, monkeypatch):
    """ISSUE 35: the decode program of one period of Command A+ at the
    published attention geometry (128 query heads on 8 KV heads of 128,
    pages of 64, window 4096) with pages by layer kind: all four layers take
    the row-walking kernel — three on the window kind's compact table, one
    on the full kind's — and neither kind's pool is copied."""
    from paddle_tpu.models.cohere2_moe import (Cohere2MoeConfig,
                                               Cohere2MoeForCausalLM)
    monkeypatch.setattr(pa, "kernel_interpret", lambda: False)
    paddle.seed(12)
    cfg = Cohere2MoeConfig(
        vocab_size=256, hidden_size=128, intermediate_size=128,
        num_hidden_layers=4, num_experts=8, num_experts_per_tok=2,
        num_shared_experts=1, experts_held=(0, 2), dtype="bfloat16",
        max_position_embeddings=8192)
    model = Cohere2MoeForCausalLM(cfg)
    model.eval()
    eng = serving.Engine(*model.serving_callables(8192),
                         serving.ServingConfig(
        num_layers=4, num_heads=8, head_dim=128, max_len=8192, max_batch=8,
        buckets=(8,), page_size=PAGE, compute_dtype="bfloat16",
        kv_dtype="bf16", layer_kinds=cfg.layer_kinds, window=4096,
        paged_attention="on"))
    assert eng._paged_path == "kernel" and len(eng.kvs) == 2
    obs.enable()
    paddle.set_flags({"FLAGS_to_static_capture_lowered": True})
    try:
        with pytest.raises(Exception, match="interpret mode"):
            eng.programs.warm(buckets=[8])
        walked = obs.snapshot()["serving.paged_attention_row_walk_layers"]
        compiled = _compiled_for_chip(eng.programs.decode_program, one_chip)
    finally:
        paddle.set_flags({"FLAGS_to_static_capture_lowered": False})
        obs.disable()
    assert walked == 4
    text = compiled.as_text()
    assert text.count("paged_attention_decode") >= 4
    for kv in eng.kvs:
        assert pool_copies(text, kv.pool.shape) == 0, kv.config.kind
    pools = sum(int(np.prod(kv.pool.shape)) * 2 for kv in eng.kvs)
    assert compiled.memory_analysis().temp_size_in_bytes < pools // 4
    # the window pool's table is the compact one: 66 columns, not 128
    assert eng.programs.table_width(eng.kvs[1], True) == 66


def test_mellum_decode_program_walks_rows_with_no_copy_of_a_pool(
        one_chip, monkeypatch):
    """Mellum 2's decode program at the published attention geometry (32
    query heads on 4 KV heads of 128, pages of 64, window 1024, YaRN on the
    full layer) over pages by layer kind with window pages kept at prefix
    boundaries: all four layers take the row-walking kernel, the experts
    the grouped matmuls, and neither kind's pool is copied."""
    from paddle_tpu.models.mellum import MellumConfig, MellumForCausalLM
    monkeypatch.setattr(pa, "kernel_interpret", lambda: False)
    paddle.seed(13)
    cfg = MellumConfig(vocab_size=256, hidden_size=128,
                       moe_intermediate_size=128, num_hidden_layers=4,
                       num_experts=8, num_experts_per_tok=2,
                       dtype="bfloat16", max_position_embeddings=8192)
    model = MellumForCausalLM(cfg)
    model.eval()
    eng = serving.Engine(*model.serving_callables(8192),
                         serving.ServingConfig(
        num_layers=4, num_heads=4, head_dim=128, max_len=8192, max_batch=8,
        buckets=(8,), page_size=PAGE, compute_dtype="bfloat16",
        kv_dtype="bf16", layer_kinds=cfg.layer_kinds, window=1024,
        window_boundary_tokens=2048, window_boundary_pages=64,
        paged_attention="on"))
    assert eng._paged_path == "kernel" and len(eng.kvs) == 2
    obs.enable()
    paddle.set_flags({"FLAGS_to_static_capture_lowered": True})
    try:
        with pytest.raises(Exception, match="interpret mode"):
            eng.programs.warm(buckets=[8])
        walked = obs.snapshot()["serving.paged_attention_row_walk_layers"]
        compiled = _compiled_for_chip(eng.programs.decode_program, one_chip)
    finally:
        paddle.set_flags({"FLAGS_to_static_capture_lowered": False})
        obs.disable()
    assert walked == 4
    text = compiled.as_text()
    assert text.count("paged_attention_decode") >= 4 and "ragged-dot" in text
    for kv in eng.kvs:
        assert pool_copies(text, kv.pool.shape) == 0, kv.config.kind
    # the window pool's decode table is the compact one: 18 columns; the
    # pool itself has every slot's 18 pages, the budget and the scratch page
    assert eng.programs.table_width(eng.kvs[1], True) == 18
    assert eng.kvs[1].pool.shape[0] == 8 * 18 + 64 + 1


# ---------------------------------------------------------------------------
# ISSUE 32: the int8 Adam update of a stacked matrix, at `train-4k`'s widths
# with few rows (the tiles cover the last two dimensions; 256 rows a layer
# are whole tiles as 4096 are). The chip keeps `[L, rows, C]` in tiles of the
# last two dimensions, so the view `[L * rows, C]` is a bitcast and the flat
# `[nb, 2048]` form the kernel took before was three copies of the parameter.
# ---------------------------------------------------------------------------

def _ops_with_results_of(text, n):
    """The operations of a compiled program whose result holds ``n``
    elements, by name."""
    import re
    ops = []
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?\S+ = \w+\[([\d,]+)\]\S* ([\w\-]+)\(",
                     line)
        if m and int(np.prod([int(d) for d in m.group(1).split(",")])) == n:
            ops.append(m.group(2))
    return ops


@pytest.mark.parametrize("shape", [(2, 256, 14336), (2, 256, 4096),
                                   (2, 256, 1024)],
                         ids=["mlp", "attn_qo", "attn_kv_flat_rows"])
def test_q8_update_compiles_with_no_copy_of_the_parameter(
        shape, q8_update_fn, one_chip):
    update, arrays, opt = q8_update_fn(shape)
    compiled = jax.jit(update, donate_argnums=(0, 2, 3, 4, 5)).lower(
        *[_spec(one_chip, a.shape, a.dtype) for a in arrays]).compile()
    text = compiled.as_text()
    assert "q8_adam_update" in text and "tpu_custom_call" in text
    n = int(np.prod(shape))
    ops = _ops_with_results_of(text, n)
    moved = [op for op in ops if op not in (
        "parameter", "bitcast", "custom-call", "get-tuple-element")]
    if shape[-1] % 2048:
        # a row is half a block: flat (nb, 2048) rows as before PR 32, and
        # the three relayouts that form costs (what the stacked k / v
        # projections still pay, 29 M elements each in `train-4k`)
        assert opt._q8_routed == {"in_layout_params": 0,
                                  "relaid_elements": n}
        assert moved.count("reshape") == 3, ops
        return
    assert opt._q8_routed == {"in_layout_params": 1, "relaid_elements": 0}
    assert "bitcast" in ops and not moved, ops
    # nothing of the parameter's size lives beside arguments and results
    assert compiled.memory_analysis().temp_size_in_bytes < n
