"""Semantics tests for the communication API on the 8-device CPU mesh
(upstream: python/paddle/distributed/communication/* — gather/scatter/
alltoall/batch_isend_irecv). Each collective runs Tensor-level inside a
manual (shard_map) region and is checked against its mathematical
definition per rank."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
import paddle_tpu.distributed as dist
from paddle_tpu.distributed.mesh import (
    build_global_mesh,
    manual_axes,
    reset_mesh,
)
from paddle_tpu.framework.core import Tensor

N = 4


@pytest.fixture()
def mesh4():
    reset_mesh()
    mesh = build_global_mesh(("x",), (N,))
    yield mesh
    reset_mesh()


def _run_manual(fn, *arrs):
    """shard_map `fn` over axis x; fn sees local shards as Tensors."""
    mesh = paddle.distributed.mesh.global_mesh()
    spec = jax.sharding.PartitionSpec("x")

    def body(*local):
        with manual_axes(("x",)):
            out = fn(*[Tensor(a) for a in local])
        return out._data if isinstance(out, Tensor) else out

    from paddle_tpu.distributed.mesh import shard_map

    return shard_map(
        body, mesh=mesh,
        in_specs=(spec,) * len(arrs),
        out_specs=spec,
    )(*arrs)


class TestScatterGather:
    def test_scatter_routes_src_chunks(self, mesh4):
        g = dist.new_group(axis_names=("x",))
        # per-rank input rows: rank r holds row r of each chunk table
        table = np.arange(N * N * 2, dtype=np.float32).reshape(N, N, 2)

        def fn(local):
            # local: (1, N, 2) — this rank's chunk table row
            chunks = [Tensor(local._data[0, i]) for i in range(N)]
            out = Tensor(jnp.zeros((2,), jnp.float32))
            dist.scatter(out, chunks, src=1, group=g)
            return Tensor(out._data[None, None, :])

        got = _run_manual(fn, table)
        # every rank r must end with src rank 1's chunk r
        got = np.asarray(got).reshape(N, 2)
        np.testing.assert_allclose(got, table[1])

    def test_scatter_outside_manual_raises(self, mesh4):
        g = dist.new_group(axis_names=("x",))
        t = paddle.to_tensor(np.zeros(2, np.float32))
        with pytest.raises(RuntimeError):
            dist.scatter(t, [t, t, t, t], src=0, group=g)

    def test_gather_collects_all_ranks(self, mesh4):
        g = dist.new_group(axis_names=("x",))
        data = np.arange(N * 3, dtype=np.float32).reshape(N, 3)

        def fn(local):
            lst = []
            dist.gather(Tensor(local._data[0]), lst, dst=0, group=g)
            stacked = jnp.stack([t._data for t in lst])  # (N, 3)
            return Tensor(stacked[None])

        got = np.asarray(_run_manual(fn, data))  # (N, N, 3)
        for r in range(N):
            np.testing.assert_allclose(got[r], data)

    def test_gather_outside_manual_raises(self, mesh4):
        g = dist.new_group(axis_names=("x",))
        with pytest.raises(RuntimeError):
            dist.gather(paddle.to_tensor(np.zeros(2, np.float32)),
                        [], dst=0, group=g)


class TestAllToAllErrors:
    def test_alltoall_outside_manual_raises(self, mesh4):
        g = dist.new_group(axis_names=("x",))
        ts = [paddle.to_tensor(np.zeros(2, np.float32)) for _ in range(N)]
        with pytest.raises(RuntimeError):
            dist.alltoall([], ts, group=g)

    def test_alltoall_single_outside_manual_raises(self, mesh4):
        g = dist.new_group(axis_names=("x",))
        t = paddle.to_tensor(np.zeros((4, 2), np.float32))
        o = paddle.to_tensor(np.zeros((4, 2), np.float32))
        with pytest.raises(RuntimeError):
            dist.alltoall_single(o, t, group=g)


class TestBatchIsendIrecv:
    def test_neighbor_ring_exchange(self, mesh4):
        g = dist.new_group(axis_names=("x",))
        data = np.arange(N * 2, dtype=np.float32).reshape(N, 2)

        def fn(local):
            send_buf = Tensor(local._data[0])
            recv_buf = Tensor(jnp.zeros_like(local._data[0]))
            ops = [
                dist.P2POp(dist.isend, send_buf, 1, group=g),
                dist.P2POp(dist.irecv, recv_buf, 1, group=g),
            ]
            tasks = dist.batch_isend_irecv(ops)
            for t in tasks:
                t.wait()
            return Tensor(recv_buf._data[None])

        got = np.asarray(_run_manual(fn, data))
        # rank r receives from rank r-1 (shift +1 ring)
        np.testing.assert_allclose(got, np.roll(data, 1, axis=0))

    def test_outside_manual_raises(self, mesh4):
        g = dist.new_group(axis_names=("x",))
        t = paddle.to_tensor(np.zeros(2, np.float32))
        ops = [dist.P2POp(dist.isend, t, 1, group=g),
               dist.P2POp(dist.irecv, t, 1, group=g)]
        with pytest.raises(RuntimeError):
            dist.batch_isend_irecv(ops)

    def test_mismatched_pairs_raise(self, mesh4):
        g = dist.new_group(axis_names=("x",))
        t = paddle.to_tensor(np.zeros(2, np.float32))
        with manual_axes(("x",)):
            with pytest.raises(ValueError):
                dist.batch_isend_irecv(
                    [dist.P2POp(dist.isend, t, 1, group=g)]
                )


def test_stream_namespace_delegates():
    """paddle.distributed.stream.* variants mirror the base collectives
    (upstream: python/paddle/distributed/communication/stream/)."""
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu import distributed as dist

    t = paddle.to_tensor(np.ones(4, "float32"))
    dist.stream.all_reduce(t, use_calc_stream=True)  # world=1: no-op
    np.testing.assert_array_equal(t.numpy(), np.ones(4, "float32"))
    out = []
    dist.stream.all_gather(out, t)
    assert len(out) >= 1


def test_fused_linear_matches_linear():
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.incubate.nn import FusedLinear, fused_linear

    paddle.seed(3)
    fl = FusedLinear(6, 4)
    x = paddle.to_tensor(np.random.RandomState(0).randn(3, 6)
                         .astype("float32"))
    ref = x.numpy() @ fl.weight.numpy() + fl.bias.numpy()
    np.testing.assert_allclose(fl(x).numpy(), ref, rtol=1e-5)
    np.testing.assert_allclose(
        fused_linear(x, fl.weight, fl.bias).numpy(), ref, rtol=1e-5
    )
