"""Reshard coverage matrix (VERDICT r2 item 8; upstream
paddle/phi/core/distributed/auto_parallel/reshard/ transition functions).

Two layers of guarantees:

* the full placement-transition matrix (Replicate / Shard(0) / Shard(1) /
  Partial -> each other) on 1D and 2D meshes preserves the logical value
  and the placement metadata — ``reshard`` is ``device_put`` to the target
  layout; Partial at the eager boundary is metadata (the reduction is
  materialized — partial values exist INSIDE compiled programs where XLA
  tracks them);
* the compiled-program layer really emits the minimal collective per
  transition: r->s lowers to a local slice (no collective), s->r to an
  all-gather, s0->s1 to an all-to-all (never gather+scatter through a
  replicated intermediate), and partial-consumption to
  reduce-scatter/all-reduce — asserted on HLO text.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import paddle_tpu as paddle
import paddle_tpu.distributed as dist
from paddle_tpu.distributed import Partial, Replicate, Shard

pytestmark = pytest.mark.skipif(len(jax.devices()) < 8,
                                reason="needs the 8-device CPU mesh")


def _mesh_1d():
    return dist.ProcessMesh(np.arange(8), dim_names=["x"])


def _mesh_2d():
    return dist.ProcessMesh(np.arange(8).reshape(2, 4), dim_names=["x", "y"])


_PLACEMENTS_1D = [
    [Replicate()], [Shard(0)], [Shard(1)], [Partial()],
]
_PLACEMENTS_2D = [
    [Replicate(), Replicate()], [Shard(0), Replicate()],
    [Replicate(), Shard(1)], [Shard(0), Shard(1)], [Shard(1), Shard(0)],
    [Partial(), Replicate()], [Partial(), Shard(0)],
]


@pytest.mark.parametrize("src", range(len(_PLACEMENTS_1D)))
@pytest.mark.parametrize("dst", range(len(_PLACEMENTS_1D)))
def test_reshard_matrix_1d(src, dst):
    mesh = _mesh_1d()
    x = np.arange(8 * 16, dtype=np.float32).reshape(8, 16)
    t = dist.shard_tensor(x, mesh, _PLACEMENTS_1D[src])
    out = dist.reshard(t, mesh, _PLACEMENTS_1D[dst])
    assert out.placements == _PLACEMENTS_1D[dst] or \
        all(type(a) == type(b) for a, b in
            zip(out.placements, _PLACEMENTS_1D[dst]))
    got = np.asarray(dist.unshard_dtensor(out)._data)
    np.testing.assert_allclose(got, x)
    # physical layout sanity: a Shard(k) destination leaves 1/8 of the
    # rows/cols per device
    pl = _PLACEMENTS_1D[dst][0]
    if isinstance(pl, Shard):
        shard_shapes = {s.data.shape for s in out._data.addressable_shards}
        want = list(x.shape)
        want[pl.dim] //= 8
        assert shard_shapes == {tuple(want)}


@pytest.mark.parametrize("src", range(len(_PLACEMENTS_2D)))
@pytest.mark.parametrize("dst", range(len(_PLACEMENTS_2D)))
def test_reshard_matrix_2d(src, dst):
    mesh = _mesh_2d()
    x = np.arange(8 * 16, dtype=np.float32).reshape(8, 16)
    t = dist.shard_tensor(x, mesh, _PLACEMENTS_2D[src])
    out = dist.reshard(t, mesh, _PLACEMENTS_2D[dst])
    got = np.asarray(dist.unshard_dtensor(out)._data)
    np.testing.assert_allclose(got, x)
    for mesh_dim, pl in enumerate(_PLACEMENTS_2D[dst]):
        if isinstance(pl, Shard):
            sizes = {s.data.shape[pl.dim] for s in out._data.addressable_shards}
            assert sizes == {x.shape[pl.dim] // mesh.shape[mesh_dim]}


# ---------------------------------------------------------------------------
# compiled-layer: the minimal collective per transition (HLO text)
# ---------------------------------------------------------------------------

def _jmesh():
    return Mesh(np.array(jax.devices()[:8]), ("x",))


def _relayout_hlo(src_spec, dst_spec):
    mesh = _jmesh()
    src = NamedSharding(mesh, src_spec)
    dst = NamedSharding(mesh, dst_spec)
    fn = jax.jit(lambda a: jax.lax.with_sharding_constraint(a, dst),
                 in_shardings=src, out_shardings=dst)
    return fn.lower(
        jax.ShapeDtypeStruct((8, 16), jnp.float32)).compile().as_text()


def test_hlo_replicate_to_shard_is_local_slice():
    txt = _relayout_hlo(P(), P("x"))
    assert "all-gather" not in txt and "all-to-all" not in txt
    assert "dynamic-slice" in txt or "slice" in txt


def test_hlo_shard_to_replicate_is_all_gather():
    txt = _relayout_hlo(P("x"), P())
    assert "all-gather" in txt


def test_hlo_shard0_to_shard1_is_all_to_all():
    txt = _relayout_hlo(P("x", None), P(None, "x"))
    assert "all-to-all" in txt
    assert "all-gather" not in txt, \
        "relayout must not gather through a replicated intermediate"


def test_hlo_partial_consumption_reduce_scatter():
    """Partial inside a program: psum_scatter consumes partial values with
    ONE reduce-scatter (not all-reduce + slice)."""
    mesh = _jmesh()

    def body(a):
        part = a * 2.0  # stand-in partial term per device
        return jax.lax.psum_scatter(part, "x", scatter_dimension=0,
                                    tiled=True)

    fn = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=P(),
                               out_specs=P("x")))
    txt = fn.lower(
        jax.ShapeDtypeStruct((8, 16), jnp.float32)).compile().as_text()
    assert "reduce-scatter" in txt and "all-reduce" not in txt


def test_hlo_partial_to_replicate_all_reduce():
    mesh = _jmesh()

    def body(a):
        return jax.lax.psum(a, "x")

    fn = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=P("x"),
                               out_specs=P()))
    txt = fn.lower(
        jax.ShapeDtypeStruct((8, 16), jnp.float32)).compile().as_text()
    assert "all-reduce" in txt
