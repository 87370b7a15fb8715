"""The routed experts' grouped matmul as a Pallas kernel
(``ops/kernels/grouped_matmul.py``) against ``jax.lax.ragged_dot``, in
the TPU interpreter (which raises on a read out of bounds), and the
dispatch of ``dropless.grouped_matmul`` that chooses between them."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

import paddle_tpu as paddle
from paddle_tpu.incubate.distributed.models.moe import dropless
from paddle_tpu.ops import kernels
from paddle_tpu.ops.kernels import grouped_matmul as G


@pytest.fixture
def interpret():
    prev = paddle.get_flags("FLAGS_pallas_interpret")
    paddle.set_flags({"FLAGS_pallas_interpret": True})
    yield
    paddle.set_flags(prev)


def _operands(sizes, m, k, n, dtype=jnp.bfloat16, seed=0):
    rng = np.random.default_rng(seed)
    xs = jnp.asarray(rng.normal(size=(m, k)), dtype)
    w = jnp.asarray(rng.normal(size=(len(sizes), k, n)), dtype)
    return xs, w, jnp.asarray(sizes, jnp.int32)


def _ulps(got, want):
    """|got - want| in units of the bfloat16 spacing at the larger, and
    at least at 1/256 of the largest result: a sum that cancels to near 0
    keeps the float32 rounding of its terms, which another order of the
    same sums moves by more than its own spacing."""
    g, w = (np.asarray(a, np.float32) for a in (got, want))
    big = np.maximum(np.maximum(np.abs(g), np.abs(w)),
                     np.abs(w).max() / 256)
    ulp = np.exp2(np.floor(np.log2(np.maximum(big, 1e-30))) - 7)
    return np.abs(g - w) / ulp


SKEWED = [4, 6, 35, 5, 3, 0, 2, 1]                  # one group 5x the mean


@pytest.mark.parametrize("sizes,m,k,n,tiling", [
    (SKEWED, 56, 256, 384, None),
    (SKEWED, 72, 256, 384, None),                    # 16 rows past them
    ([0, 0, 9, 20, 0, 11, 0, 0], 40, 128, 256, (16, 128, 128)),
    ([50, 0, 0, 0], 64, 128, 128, (16, 128, 128)),   # all in the first
    ([0, 0, 0, 50], 64, 128, 128, (16, 128, 128)),   # all in the last
    ([37], 37, 200, 130, None),                      # one group
    ([0, 0, 0, 0, 0], 24, 96, 80, None),             # no group has a row
    ([5, 3, 9], 20, 96, 80, None),                   # m not a multiple of E
    ([21, 0, 44, 3, 19, 0, 7], 150, 384, 256, (64, 128, 128)),
])
def test_kernel_is_ragged_dot(sizes, m, k, n, tiling):
    """Within one bfloat16 spacing of ``ragged_dot`` (float32 sums in
    another order, both cast), and the rows past the last group exactly
    0: K tiles, a row tile across groups, empty groups at the start, in
    the middle and at the end, an edge row tile, K and N not multiples of
    128."""
    xs, w, gs = _operands(sizes, m, k, n)
    got = G.gmm_pallas(xs, w, gs, tiling=tiling,
                       interpret=pltpu.InterpretParams())
    want = G.grouped_matmul_reference(xs, w, gs)
    assert got.dtype == jnp.bfloat16 and got.shape == (m, n)
    assert _ulps(got, want).max() <= 1.0
    tail = np.asarray(got, np.float32)[sum(sizes):]
    assert (tail == 0).all()


def test_float32_operands():
    xs, w, gs = _operands(SKEWED, 60, 128, 128, jnp.float32)
    got = G.gmm_pallas(xs, w, gs, interpret=pltpu.InterpretParams())
    want = G.grouped_matmul_reference(xs, w, gs)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-4)
    assert (np.asarray(got)[56:] == 0).all()


@pytest.mark.parametrize("m,k,n,want", [
    (2048, 2048, 768, (128, 2048, 768)),            # SDAR gate / up
    (8192, 768, 2048, (128, 768, 2048)),            # SDAR down
    (256, 3584, 1024, (128, 3584, 512)),            # Xing4 gate / up
    (256, 1024, 3584, (128, 1024, 1792)),           # Xing4 down
    (40, 200, 130, (40, 200, 130)),
])
def test_tiling_follows_the_shapes(m, k, n, want):
    """The whole K in a tile, then the widest N whose tiles (weights, rows
    and result double-buffered, the float32 accumulator) fit the budget:
    Xing4's [3584, 1024] stack splits N in two."""
    tm, tk, tn = G.gmm_tiling(m, k, n)
    assert (tm, tk, tn) == want
    vmem = 2 * tk * tn * 2 + 2 * tm * tk * 2 + 2 * tm * tn * 2 + tm * tn * 4
    assert vmem <= G.VMEM_BUDGET


def test_dispatch_is_counted(interpret):
    xs, w, gs = _operands(SKEWED, 60, 128, 128)
    kernels.kernel_dispatch_stats(reset=True)
    on = dropless.grouped_matmul(xs, w, gs)
    paddle.set_flags({"FLAGS_pallas_interpret": False})
    off = dropless.grouped_matmul(xs, w, gs)
    stats = kernels.kernel_dispatch_stats(reset=True)
    assert stats["grouped_matmul:pallas"] == 1
    assert stats["grouped_matmul:xla_fallback"] == 1
    assert _ulps(on, off).max() <= 1.0


def _moe_grads(interpret_on, x):
    from paddle_tpu.incubate.distributed.models.moe import DroplessMoE

    paddle.set_flags({"FLAGS_pallas_interpret": interpret_on})
    paddle.seed(5)
    layer = DroplessMoE(32, 16, 8, 2, num_shared=1, init_std=0.3)
    xt = paddle.to_tensor(x, stop_gradient=False)
    y = layer(xt)
    (y * y).sum().backward()
    return ([np.asarray(y._data)], [np.asarray(xt.grad._data)]
            + [np.asarray(p.grad._data) for p in layer.parameters()
               if p.grad is not None])


def test_grad_through_the_layer_is_ragged_dots(interpret):
    """The tape's backward through ``DroplessMoE`` with the kernel in the
    forward pass (its backward is ``ragged_dot``'s) matches the layer on
    ``ragged_dot`` alone."""
    x = np.random.default_rng(1).normal(size=(3, 13, 32)).astype(np.float32)
    kernels.kernel_dispatch_stats(reset=True)
    (y_on,), g_on = _moe_grads(True, x)
    assert kernels.kernel_dispatch_stats()["grouped_matmul:pallas"] >= 3
    (y_off,), g_off = _moe_grads(False, x)
    np.testing.assert_allclose(y_on, y_off, rtol=1e-5, atol=1e-5)
    assert len(g_on) == len(g_off) >= 6
    for a, b in zip(g_on, g_off):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)


def test_jax_grad_of_the_kernel_is_ragged_dots():
    xs, w, gs = _operands([3, 0, 9, 4], 20, 128, 128, jnp.float32)

    def loss(f):
        return lambda a, b: jnp.sum(f(a, b, gs) ** 2)

    kern = loss(lambda a, b, s: G.grouped_matmul_pallas(a, b, s, True))
    got = jax.grad(kern, argnums=(0, 1))(xs, w)
    want = jax.grad(loss(G.grouped_matmul_reference), argnums=(0, 1))(xs, w)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-3)


def test_padding_rows_stay_finite(interpret):
    """``dropless_moe`` over a packed axis whose padding rows are routed
    nowhere (``valid`` false): their assignments sort past every group,
    the kernel gives those rows zeros, and no row of the result is NaN."""
    rng = np.random.default_rng(2)
    n, c, f, e, k = 24, 128, 128, 8, 2
    x = jnp.asarray(rng.normal(size=(n, c)), jnp.bfloat16)
    wr = jnp.asarray(rng.normal(size=(c, e)), jnp.bfloat16)
    wg, wu = (jnp.asarray(0.1 * rng.normal(size=(e, c, f)), jnp.bfloat16)
              for _ in range(2))
    wd = jnp.asarray(0.1 * rng.normal(size=(e, f, c)), jnp.bfloat16)
    valid = jnp.arange(n) < 17
    y, sizes = jax.jit(lambda *a: dropless.dropless_moe(
        *a[:6], None, k, valid=a[6], scoring="softmax"))(
        x, wr, None, wg, wu, wd, valid)
    assert int(sizes.sum()) == 17 * k
    assert np.isfinite(np.asarray(y, np.float32)).all()
    paddle.set_flags({"FLAGS_pallas_interpret": False})
    want, _ = dropless.dropless_moe(x, wr, None, wg, wu, wd, None, k,
                                    valid=valid, scoring="softmax")
    np.testing.assert_allclose(np.asarray(y, np.float32)[:17],
                               np.asarray(want, np.float32)[:17],
                               rtol=0.02, atol=0.02)
