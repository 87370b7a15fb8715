"""One cached program per shape at the entry of the Pallas norm kernels
(ops/kernels.eager_call): an eager call with concrete arrays must not
trace or lower again for a shape it has seen, a traced caller must
inline exactly what it inlined before, and every call is counted.
CPU, interpret mode, tiny sizes."""
import importlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.framework import telemetry
from paddle_tpu.ops import kernels
from paddle_tpu.ops.kernels import kernel_dispatch_stats

rn = importlib.import_module("paddle_tpu.ops.kernels.rms_norm")

LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
H = 128


@pytest.fixture()
def interp():
    paddle.set_flags({"FLAGS_pallas_interpret": True})
    kernels._eager_program.cache_clear()
    kernel_dispatch_stats(reset=True)
    yield
    paddle.set_flags({"FLAGS_pallas_interpret": False})


@pytest.fixture()
def lowerings():
    """The names of the functions JAX lowers while the fixture lives."""
    seen = []

    def listener(event, secs, **kw):
        if event == LOWER:
            seen.append(str(kw.get("fun_name", "")))

    jax.monitoring.register_event_duration_secs_listener(listener)
    yield seen
    jax.monitoring.unregister_event_duration_listener(listener)


def _x(shape=(4, H), dtype=jnp.float32, seed=0):
    rng = np.random.RandomState(seed)
    return jnp.asarray(rng.randn(*shape) * 1.5 + 0.3, dtype)


def _vec(seed, dtype=jnp.float32):
    return _x((H,), dtype, seed)


# kernel -> (entry, the arguments after x, the reference formula)
KERNELS = {
    "rms_norm": (rn.rms_norm, lambda: (_vec(1),),
                 lambda x, w, eps=1e-6: rn._rms_ref(x, w, eps)),
    "layer_norm_fused": (
        rn.layer_norm_fused, lambda: (_vec(1), _vec(2)),
        lambda x, w, b, eps=1e-5: rn._ln_ref(x, w, b, eps)),
}


def _stats(kernel):
    got = kernel_dispatch_stats(reset=True)
    return tuple(got.get(f"{kernel}:{k}", 0) for k in
                 ("pallas", "xla_fallback", "program_hit", "program_miss"))


@pytest.mark.parametrize("kernel", sorted(KERNELS))
class TestEagerCallsHitOneProgram:
    def test_repeats_of_a_shape_build_nothing(self, interp, lowerings,
                                              kernel):
        fn, vecs, ref = KERNELS[kernel]
        x, vs = _x(), vecs()
        first = fn(x, *vs)
        assert _stats(kernel) == (1, 0, 0, 1)
        assert kernel in " ".join(lowerings)
        others = [_x(seed=3), _x(seed=4)]   # other values, same shape
        del lowerings[:]
        later = [fn(x2, *vs) for x2 in others]
        assert lowerings == []
        assert _stats(kernel) == (2, 0, 2, 0)
        for xi, got in zip([x] + others, [first] + later):
            np.testing.assert_allclose(got, ref(xi, *vs), atol=1e-5,
                                       rtol=1e-5)

    @pytest.mark.parametrize("what", ["shape", "dtype", "rank"])
    def test_a_new_shape_or_dtype_is_one_miss(self, interp, kernel, what):
        fn, vecs, _ = KERNELS[kernel]
        fn(_x(), *vecs())
        kernel_dispatch_stats(reset=True)
        if what == "dtype":
            bf = jnp.bfloat16
            args = (_x(dtype=bf),) + tuple(v.astype(bf) for v in vecs())
        else:
            args = (_x((8, H) if what == "shape" else (2, 4, H)),) + vecs()
        fn(*args)
        assert _stats(kernel) == (1, 0, 0, 1)
        fn(*args)
        assert _stats(kernel) == (1, 0, 1, 0)

    def test_stats_count_hits_and_misses_by_kernel(self, interp, kernel):
        """Counted in ``kernel_dispatch_stats()`` alone, whatever the
        registry's mode; under live spans each call is a ``kernel.eager``
        span whose ``key`` names the program, and its build's ranges
        carry that key."""
        fn, vecs, _ = KERNELS[kernel]
        paddle.set_flags({"FLAGS_telemetry": "trace"})
        telemetry.reset()
        try:
            for _ in range(3):
                fn(_x(), *vecs())
            assert _stats(kernel) == (3, 0, 2, 1)
            assert not any(k.startswith("program_cache") for k in
                           telemetry.registry().snapshot().get("kernel", {}))
            spans = telemetry.tracer().spans()
        finally:
            paddle.set_flags({"FLAGS_telemetry": "off"})
            telemetry.reset()
        keys = [s.attrs["key"] for s in spans if s.name == "kernel.eager"]
        eps = 1e-6 if kernel == "rms_norm" else 1e-5
        want = f"{kernel} ({eps},) float32[4, {H}]" \
            + f" float32[{H}]" * len(vecs())
        assert keys == [want] * 3
        built = {s.attrs.get("key") for s in spans if s.name == "xla.lower"
                 and kernel in s.attrs["fun"]}
        assert built == {keys[0]}


@pytest.mark.parametrize("kernel", sorted(KERNELS))
class TestWhatTheBodyReadsIsInTheKey:
    def test_interpret_flag_flip_takes_the_reference_path(self, interp,
                                                          kernel):
        fn, vecs, _ = KERNELS[kernel]
        x, vs = _x(), vecs()
        got = fn(x, *vs)
        assert _stats(kernel) == (1, 0, 0, 1)
        paddle.set_flags({"FLAGS_pallas_interpret": False})
        ref = fn(x, *vs)                # XLA reference, op by op
        assert _stats(kernel) == (0, 1, 0, 0)
        paddle.set_flags({"FLAGS_pallas_interpret": True})
        again = fn(x, *vs)
        assert _stats(kernel) == (1, 0, 1, 0)
        np.testing.assert_allclose(got, ref, atol=1e-6, rtol=1e-6)
        np.testing.assert_array_equal(got, again)

    def test_eps_is_another_program(self, interp, kernel):
        fn, vecs, ref = KERNELS[kernel]
        x, vs = _x() * 1e-3, vecs()
        small, large = fn(x, *vs, eps=1e-6), fn(x, *vs, eps=1e-2)
        assert _stats(kernel) == (2, 0, 0, 2)
        np.testing.assert_allclose(large, ref(x, *vs, eps=1e-2),
                                   atol=1e-5, rtol=1e-5)
        assert not np.allclose(small, large)
        fn(x, *vs, eps=1e-2)
        assert _stats(kernel) == (1, 0, 1, 0)

    def test_dropping_the_weight_is_another_program(self, interp, kernel):
        fn, vecs, ref = KERNELS[kernel]
        x, vs = _x(), vecs()
        none = (None,) * len(vs)
        with_w, without = fn(x, *vs), fn(x, *none)
        assert _stats(kernel) == (2, 0, 0, 2)
        np.testing.assert_allclose(without, ref(x, *none), atol=1e-5,
                                   rtol=1e-5)
        assert not np.allclose(with_w, without)
        fn(x, *none)
        fn(x, *vs)
        assert _stats(kernel) == (2, 0, 2, 0)

    def test_no_lane_multiple_stays_the_reference_uncached(self, interp,
                                                           kernel):
        fn = KERNELS[kernel][0]
        for _ in range(2):
            fn(_x((4, 100)))            # 100 % 128 != 0
        assert _stats(kernel) == (0, 2, 0, 0)


def _eqn_names(jaxpr, out=None):
    """Primitive names of a jaxpr and every jaxpr nested in it."""
    out = [] if out is None else out
    for eqn in jaxpr.eqns:
        out.append(eqn.primitive.name)
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _eqn_names(sub, out)
    return out


@pytest.mark.parametrize("kernel", sorted(KERNELS))
class TestTracedCallersInline:
    def test_under_jit_the_jaxpr_is_the_core_s(self, interp, kernel):
        fn, vecs, _ = KERNELS[kernel]
        core = {"rms_norm": rn._rms_norm_core,
                "layer_norm_fused": rn._layer_norm_core}[kernel]
        eps = {"rms_norm": 1e-6, "layer_norm_fused": 1e-5}[kernel]
        x, vs = _x(), vecs()
        fn(x, *vs)                      # a cached program exists
        kernel_dispatch_stats(reset=True)
        got = jax.make_jaxpr(jax.jit(fn))(x, *vs)
        want = jax.make_jaxpr(jax.jit(lambda *a: core(*a, eps)))(x, *vs)
        inner = got.jaxpr.eqns[0].params["jaxpr"].jaxpr
        names = _eqn_names(inner)
        assert "pallas_call" in names
        assert "pjit" not in names and "jit" not in names
        assert str(inner) == str(want.jaxpr.eqns[0].params["jaxpr"].jaxpr)
        # traced: counted once a trace, no program looked up
        assert _stats(kernel) == (1, 0, 0, 0)


def test_under_to_static_the_call_is_inlined(interp):
    import paddle_tpu.nn.functional as F

    w = paddle.to_tensor(np.asarray(_vec(1)))

    @paddle.jit.to_static
    def f(x):
        return F.rms_norm(x, w, 1e-6)

    x = paddle.to_tensor(np.asarray(_x()))
    entry = f.trace_for_analysis(x)
    names = _eqn_names(entry["pruned_jaxpr"].jaxpr)
    assert "pallas_call" in names
    assert "pjit" not in names and "jit" not in names
    assert _stats("rms_norm") == (1, 0, 0, 0)
    np.testing.assert_allclose(
        f(x).numpy(), rn._rms_ref(_x(), _vec(1), 1e-6), atol=1e-5,
        rtol=1e-5)


@pytest.mark.parametrize("kernel,has_w,has_b", [
    ("rms_norm", True, False), ("rms_norm", False, False),
    ("layer_norm_fused", False, False), ("layer_norm_fused", True, False),
    ("layer_norm_fused", True, True)])
def test_grad_through_the_entry_equals_the_reference(interp, kernel,
                                                     has_w, has_b):
    fn, _, ref = KERNELS[kernel]
    x = _x((8, H))
    vs = (_vec(5) if has_w else None,)
    if kernel == "layer_norm_fused":
        vs += (_vec(6) if has_b else None,)
    argnums = tuple(i for i, a in enumerate((x,) + vs) if a is not None)

    def loss(f):
        return lambda *a: jnp.sum(f(*a) ** 2)

    fn(x, *vs)                          # the eager program is cached
    got = jax.grad(loss(fn), argnums)(x, *vs)
    want = jax.grad(loss(ref), argnums)(x, *vs)
    for g, r in zip(got, want):
        np.testing.assert_allclose(g, r, atol=1e-4, rtol=1e-4)
    fn(x, *vs)
    assert _stats(kernel)[2:] == (1, 1)


@pytest.mark.parametrize("has_w", [True, False])
def test_tape_backward_through_the_cached_forward(interp, has_w):
    """The eager forward runs the cached program; the tape's GradNode
    recomputes through jax.vjp (a traced call of the same entry)."""
    import paddle_tpu.nn.functional as F

    x = paddle.to_tensor(np.asarray(_x((8, H))), stop_gradient=False)
    w = paddle.to_tensor(np.asarray(_vec(5)), stop_gradient=False) \
        if has_w else None
    for _ in range(2):
        y = F.rms_norm(x, w, 1e-6)
    assert _stats("rms_norm")[2:] == (1, 1)
    (y * y).sum().backward()
    raw = (x._data,) + ((w._data,) if has_w else ())
    want = jax.grad(
        lambda *a: jnp.sum(rn._rms_ref(a[0], a[1] if has_w else None,
                                       1e-6) ** 2),
        tuple(range(len(raw))))(*raw)
    np.testing.assert_allclose(x.grad.numpy(), want[0], atol=1e-4,
                               rtol=1e-4)
    if has_w:
        np.testing.assert_allclose(w.grad.numpy(), want[1], atol=1e-4,
                                   rtol=1e-4)


@pytest.mark.parametrize("body", ["eager", "program"])
def test_a_steady_decode_step_builds_nothing(interp, body):
    """The fault itself: PagedLlamaAdapter + BatchScheduler ran 2L+1
    eager rms_norm calls a step and each one re-lowered its program.
    After warm-up three decode steps record no xla.lower range and
    every norm of theirs is a program_hit on the op-by-op body (int8
    pages here); where a layer runs as one compiled program (ISSUE 33)
    its norms are inside it and no eager norm is dispatched at all."""
    from paddle_tpu.inference import (BatchScheduler, PagedLlamaAdapter,
                                      Request)
    from paddle_tpu.models import LlamaForCausalLM, llama_tiny

    layers = 2
    paddle.seed(5)
    model = LlamaForCausalLM(llama_tiny(
        hidden_size=H, num_hidden_layers=layers,
        max_position_embeddings=128))
    adapter = PagedLlamaAdapter(
        model, num_pages=64, page_size=16, max_length=128,
        kv_cache_dtype="int8" if body == "eager" else None)
    assert adapter._fusion_eligible() == (body == "program")
    sched = BatchScheduler(adapter, max_batch_size=4)
    rng = np.random.RandomState(0)
    for i in range(3):
        sched.submit(Request(f"r{i}", rng.randint(1, 500, 5 + i).tolist(),
                             max_new_tokens=8))
    for _ in range(3):                  # prefill, then the decode shapes
        sched.step()
    kernel_dispatch_stats(reset=True)
    telemetry.reset()
    telemetry.arm_tracer()
    try:
        for _ in range(3):
            sched.step()
        spans = telemetry.peek_tracer().spans()
    finally:
        telemetry.disarm_tracer()
        telemetry.reset()
    names = [s.name for s in spans]
    assert names.count("serving.step") == 3
    assert [s.attrs for s in spans if s.name == "xla.lower"] == []
    if body == "program":
        assert names.count("model.norm") == 0
        assert names.count("model.layer") == 3 * layers
        assert _stats("rms_norm") == (0, 0, 0, 0)
        return
    assert names.count("model.norm") == 3 * 2 * layers
    norms = 3 * (2 * layers + 1)
    assert _stats("rms_norm") == (norms, 0, norms, 0)
