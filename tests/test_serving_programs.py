"""``serving/programs.py`` (ISSUE 29): the one owner of the serving engine's
compiled programs and of how a call to one is laid out.

CPU-deterministic, the toy model of ``test_prefix_sharing`` on the dense
tier (the kernel tier's programs are held by ``test_paged_attention`` and
``test_tpu_compile``). What is pinned here: the layout round-trips for one
pool, two pools and the int8 leg, and donates exactly the pools; a call's
pools are adopted whatever becomes of its tokens; ``prefill`` picks the
full or the tail program by ``start``; ``pools_lost`` tells a consuming
call that raised from one that never ran; ``warm`` compiles what it is
asked for and nothing compiles afterwards; the benchmark's own reach-in
still runs; the four environment names that mirrored ``ServingConfig``
fields decide nothing.
"""

import os
import sys
from types import SimpleNamespace

import numpy as np
import pytest

import jax.numpy as jnp

import paddle_tpu as paddle  # noqa: F401  (backend pin via conftest)
from paddle_tpu import serving
from paddle_tpu.core.tensor import Tensor as T
from paddle_tpu.serving.programs import Programs, Step

from test_prefix_sharing import BASE, PS, SHARED_PROMPTS, make_engine3
from test_serving import V
from test_serving_chaos import _consume_and_raise_on


def _zeros(*shape):
    return T(jnp.zeros(shape, jnp.int32))


def _decode_args(eng, bucket):
    """A decode step of ``bucket`` padded rows: the scratch page only."""
    p = eng.programs
    return (_zeros(bucket, 1),
            [_zeros(bucket, p.table_width(kv, True)) for kv in eng.kvs],
            _zeros(bucket), p.no_carry, T(jnp.full((bucket,), -1, jnp.int32)))


def _executables(*progs):
    """How many executables the given programs have compiled."""
    return sum(entry[0]._jitted._cache_size() for prog in progs
               for entry in prog.program_cache.values())


def _all_programs(eng):
    p = eng.programs
    return [p.decode_program, p.prefill_program, *p._tail_programs.values()]


# ---------------------------------------------------------------------------
# the layout
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("pools,kv_dtype", [
    (1, "native"), (2, "native"), (1, "int8"), (2, "int8")])
def test_layout_round_trips_and_donates_exactly_the_pools(pools, kv_dtype):
    kinds = {1: {}, 2: {"num_layers": 4, "window": 8,
                        "layer_kinds": ("window",) * 3 + ("full",)}}[pools]
    cfg = serving.ServingConfig(**{**dict(
        num_layers=1, num_heads=1, head_dim=8, max_len=32, max_batch=2,
        buckets=(1, 2), page_size=4, kv_dtype=kv_dtype), **kinds})
    eng = serving.Engine(lambda *a: None, lambda *a: None, cfg)
    p, q = eng.programs, kv_dtype == "int8"
    assert len(eng.kvs) == pools
    parts = [(f"tables{k}", f"pool{k}", f"scales{k}" if q else None)
             for k in range(pools)]
    flat = p._flatten("head", parts, "mid", ("carry", "sel"))
    # the order XLA has always seen: the first pool's tables before ``mid``
    assert flat[:4] == ("head", "tables0", "mid", "pool0")
    assert flat[-2:] == ("carry", "sel")
    assert len(flat) == 4 + pools * (2 + q)
    head, back, mid, tail = p._unflatten(flat, 2)
    assert (head, back, mid, list(tail)) == ("head", parts, "mid",
                                             ["carry", "sel"])
    assert p._unflatten(p._flatten("ids", parts, "len"))[1:] == \
        (parts, "len", [])
    # donated: every pool and its scales, nothing else — never the tables,
    # never the carried tokens
    assert [flat[i] for i in p._donate] == [
        x for _, pool, scales in parts for x in (pool, scales) if x]
    # and back out: the pools are adopted, the rest is handed on by name
    new = [(SimpleNamespace(_data=f"new pool{k}"),
            SimpleNamespace(_data=f"new scales{k}") if q else None)
           for k in range(pools)]
    first, rest = p._adopt(p._returns("tokens", new, ("carried",)))
    assert (first, rest) == ("tokens", ("carried",))
    assert [kv.pool for kv in eng.kvs] == [
        f"new pool{k}" for k in range(pools)]
    if q:
        assert [kv.scales for kv in eng.kvs] == [
            f"new scales{k}" for k in range(pools)]
    assert p._adopt(p._returns("first", new)) == ("first", ())


# ---------------------------------------------------------------------------
# adoption and loss
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kv_dtype", ["native", "int8"])
def test_decode_adopts_the_pools_when_its_tokens_are_abandoned(kv_dtype):
    eng = make_engine3(kv_dtype=kv_dtype)
    pool0, scales0 = eng.kv.pool, eng.kv.scales
    step = eng.programs.decode(*_decode_args(eng, 2))
    assert isinstance(step, Step) and step.rows == 2
    del step                                      # nobody reads its tokens
    assert pool0.is_deleted()                     # consumed by the call
    assert not eng.kv.pool.is_deleted()           # what it returned: adopted
    assert eng.kv.pool.shape == pool0.shape
    if kv_dtype == "int8":
        assert scales0.is_deleted() and not eng.kv.scales.is_deleted()
    assert not eng.programs.no_carry._data.is_deleted()   # never donated
    assert not eng.programs.pools_lost()
    # and the engine serves on through the adopted pool
    fut = eng.submit(serving.GenerationRequest(SHARED_PROMPTS[0],
                                               max_new_tokens=3))
    eng.run()
    assert len(fut.result(timeout=30).tokens) == 3


def test_a_step_reads_its_tokens_counts_and_carry_by_name():
    eng = make_engine3()
    step = eng.programs.decode(*_decode_args(eng, 2))
    tokens, counts = step.read()
    assert tokens.shape == (2,) and counts.size == 0   # no expert layer
    # the carried tokens are the step's own, in the largest bucket's shape
    assert np.asarray(step.carry._data).tolist() == tokens.tolist()
    nxt = eng.programs.decode(*_decode_args(eng, 2)[:3], step.carry,
                              T(jnp.asarray([1, 0], jnp.int32)))
    assert nxt.read()[0].shape == (2,)


@pytest.mark.parametrize("kv_dtype", ["native", "int8"])
def test_pools_lost_after_a_consuming_call_that_raised(kv_dtype):
    eng = make_engine3(kv_dtype=kv_dtype)
    real = eng.programs.decode_program

    def never_ran(*args):
        raise RuntimeError("raised before the call")

    eng.programs.decode_program = never_ran
    with pytest.raises(RuntimeError, match="before the call"):
        eng.programs.decode(*_decode_args(eng, 1))
    assert not eng.programs.pools_lost()          # the pools are as they were
    eng.programs.decode_program = real
    eng.programs.decode(*_decode_args(eng, 1))
    assert not eng.programs.pools_lost()
    _consume_and_raise_on(eng, "decode_program", nth=1)
    with pytest.raises(RuntimeError, match="consumed"):
        eng.programs.decode(*_decode_args(eng, 1))
    assert eng.programs.pools_lost()
    # what to do about it is the engine's: fresh pools
    assert eng._restore_lost_pool(RuntimeError("x"))
    assert not eng.programs.pools_lost()


# ---------------------------------------------------------------------------
# the program family
# ---------------------------------------------------------------------------

def test_prefill_takes_the_tail_program_iff_start_and_builds_one_per_start():
    eng = make_engine3()
    p = eng.programs
    assert p.tail_capable and eng.prefix_sharing_enabled
    full_calls = []
    real = p.prefill_program
    p.prefill_program = lambda *a: (full_calls.append(1), real(*a))[1]
    row = [_zeros(eng.kv.config.pages_per_slot)]

    def prefill(start, n):
        step = p.prefill(_zeros(1, n), row,
                         T(jnp.asarray(start + n, jnp.int32)), start)
        (tok,), counts = step.read()
        assert step.carry is None and 0 <= int(tok) < V and not counts.size

    prefill(0, 6)
    assert len(full_calls) == 1 and p._tail_programs == {}
    prefill(2 * PS, 3)
    assert len(full_calls) == 1 and set(p._tail_programs) == {2 * PS}
    first = p._tail_programs[2 * PS]
    prefill(2 * PS, 5)                            # another length, same start
    assert p._tail_programs[2 * PS] is first
    prefill(PS, 3)
    assert set(p._tail_programs) == {PS, 2 * PS} and len(full_calls) == 1
    assert first.cost_label == f"engine.prefill_tail{2 * PS}"
    assert (real.cost_site, p.decode_program.cost_site) == (
        "serving.prefill", "serving.decode")


def test_warm_compiles_what_it_is_asked_for_and_nothing_afterwards():
    eng = make_engine3(max_batch=4)               # buckets (1, 4)
    n_doc, tail = len(BASE), 3
    lens = sorted({len(p) for p in SHARED_PROMPTS})
    assert _executables(*_all_programs(eng)) == 0
    assert eng.warmup(prompt_lens=lens, tails=[(n_doc, tail)]) is eng
    p = eng.programs
    assert _executables(p.decode_program) == 2            # one per bucket
    assert _executables(p.prefill_program) == len(lens)
    assert set(p._tail_programs) == {n_doc}
    assert _executables(p._tail_programs[n_doc]) == 1
    warmed = _executables(*_all_programs(eng))
    eng.warmup(prompt_lens=lens, tails=[(n_doc, tail)])   # idempotent
    # a full prefill, then a sharer of its document with a 3-token tail
    futs = [eng.submit(serving.GenerationRequest(SHARED_PROMPTS[k],
                                                 max_new_tokens=4))
            for k in (1, 0)]
    eng.run()
    assert all(len(f.result(timeout=30).tokens) == 4 for f in futs)
    requested, computed = eng.prefill_token_stats()
    assert computed == requested - n_doc                  # the tail program
    assert _executables(*_all_programs(eng)) == warmed
    assert eng.kv.outstanding_pages == 0


def test_the_benchmarks_reach_in_still_runs():
    """``perfbench/runners/serve_open_loop.py::_warm_tails`` — the file may
    not be edited — builds tail programs through ``Engine._tail_program``
    and ``Engine._scales_args`` with the one-pool positional call."""
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from perfbench.runners.serve_open_loop import _warm_tails
    for kv_dtype in ("native", "int8"):
        eng = make_engine3(kv_dtype=kv_dtype)
        pool0 = eng.kv.pool
        _warm_tails(eng, [(2 * PS, 3), (PS, 2)])
        assert set(eng.programs._tail_programs) == {PS, 2 * PS}
        assert pool0.is_deleted() and not eng.programs.pools_lost()
        assert len(eng._scales_args()) == (kv_dtype == "int8")


def test_the_environment_names_that_mirrored_the_fields_decide_nothing(
        monkeypatch, tier_engine):
    plain = tier_engine("kernel")
    for name, value in [("PADDLE_TPU_PAGED_ATTENTION", "off"),
                        ("PADDLE_TPU_KV_DTYPE", "int8"),
                        ("PADDLE_TPU_PREFIX_SHARING", "off"),
                        ("PADDLE_TPU_PREFIX_MIN_PAGES", "9")]:
        monkeypatch.setenv(name, value)
    eng = tier_engine("kernel")
    assert eng.config == plain.config
    assert eng._paged_path == eng.programs.path == "kernel"
    assert not eng.kv.config.quantized and eng.kv.pool.dtype == \
        plain.kv.pool.dtype
    assert eng.prefix_sharing_enabled and eng.kv.config.min_shared_pages == 1
    assert isinstance(eng.programs, Programs)


# ---------------------------------------------------------------------------
# a state per slot (ISSUE 31): the state pool and the compressed keys ride
# behind the pools, donated and adopted like them
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def hybrid():
    from test_minicpm_sala import _engine
    from paddle_tpu.models.minicpm_sala import (MiniCPMSalaConfig,
                                                MiniCPMSalaForCausalLM)
    paddle.seed(31)
    model = MiniCPMSalaForCausalLM(MiniCPMSalaConfig.tiny())
    model.eval()
    return lambda **over: _engine(model, **over)


def _hybrid_decode_args(eng, bucket):
    return _decode_args(eng, bucket) + (_zeros(bucket),)


def test_layout_donates_the_state_pool_and_the_compressed_keys(hybrid):
    eng = hybrid()
    p = eng.programs
    assert [type(x).__name__ for x in p.extras] == ["IndexPool", "StatePart"]
    parts = [("tables", "pool", None)]
    flat = p._flatten("head", parts, "mid",
                      ("index", "state", "carry", "sel", "rows"))
    assert flat == ("head", "tables", "mid", "pool", "index", "state",
                    "carry", "sel", "rows")
    assert [flat[i] for i in p._donate] == ["pool", "index", "state"]
    head, back, mid, tail = p._unflatten(flat, 5)
    assert (head, back, mid) == ("head", parts, "mid")
    assert list(tail) == ["index", "state", "carry", "sel", "rows"]


def test_a_decode_step_adopts_the_state_pool_it_was_given_donated(hybrid):
    eng = hybrid()
    part = eng.state.parts[0]
    pool0, index0, state0 = eng.kv.pool, eng.index.array, part.array
    step = eng.programs.decode(*_hybrid_decode_args(eng, 3))
    assert step.read()[0].shape == (3,)
    assert pool0.is_deleted() and index0.is_deleted() \
        and state0.is_deleted()                       # consumed by the call
    assert not part.array.is_deleted() \
        and part.array.shape == state0.shape          # what it returned
    assert not eng.index.array.is_deleted()
    assert not eng.programs.zero_states[0]._data.is_deleted()   # never donated
    assert not eng.programs.pools_lost()
    # a prefill writes the slot's row of the state pool and keeps snapshots
    row = eng.state.alloc()
    before = np.asarray(part.array)
    step = eng.programs.prefill(
        T(jnp.ones((1, 20), jnp.int32)),
        [T(jnp.asarray(eng.kv.table_row(eng.kv.alloc(6))))],
        T(jnp.asarray(20, jnp.int32)), 0, T(jnp.asarray(row, jnp.int32)))
    assert step.extra[0].shape[0] == 20 // eng.config.state_snapshot_tokens
    after = np.asarray(part.array)
    assert np.abs(after[row]).max() > 0
    assert (np.delete(after, row, 0) == np.delete(before, row, 0)).all()


def test_a_consuming_call_that_raised_loses_the_state_too(hybrid):
    eng = hybrid()
    doc = np.arange(40, dtype=np.int32) % 90
    fut = eng.submit(serving.GenerationRequest(doc, max_new_tokens=2))
    eng.run()
    assert len(fut.result(timeout=30).tokens) == 2 and len(eng.snapshots)
    _consume_and_raise_on(eng, "decode_program", nth=1)
    with pytest.raises(RuntimeError, match="consumed"):
        eng.programs.decode(*_hybrid_decode_args(eng, 1))
    part = eng.state.parts[0]
    assert part.array.is_deleted() and eng.index.array.is_deleted()
    assert eng.programs.pools_lost()
    assert eng._restore_lost_pool(RuntimeError("x"))
    assert not eng.programs.pools_lost()
    assert not np.asarray(part.array).any()           # fresh states
    assert len(eng.snapshots) == 0                    # nothing to start from
    assert eng.state.free_rows == eng.config.max_batch
    # and the engine serves on: the document is prefilled in full again
    before = eng.prefill_token_stats()[1]
    fut = eng.submit(serving.GenerationRequest(doc, max_new_tokens=2))
    eng.run()
    assert len(fut.result(timeout=30).tokens) == 2
    assert eng.prefill_token_stats()[1] - before == 40
