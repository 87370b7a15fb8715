"""paddle_tpu — a TPU-native deep-learning framework with the API surface
of the reference (PaddlePaddle fork), built on JAX/XLA/Pallas.

Compute path: jnp/lax (XLA) + Pallas TPU kernels. Parallelism: named-axis
``jax.sharding.Mesh`` + shard_map collectives (the ProcessGroupNCCL
analog). Eager imperative API with tape autograd; the perf path is a
compiled whole-step trace (``paddle_tpu.jit.to_static``).
"""
from __future__ import annotations

__version__ = "0.1.0"

# Importing the package touches no backend: JAX picks the platform (and
# honours JAX_PLATFORMS) when the first array or ``jax.devices()`` asks.

# -- framework core ---------------------------------------------------------
from .framework import (
    Tensor,
    Parameter,
    EagerParamBase,
    no_grad,
    enable_grad,
    set_grad_enabled,
    is_grad_enabled,
    get_flags,
    set_flags,
    save,
    load,
    seed,
    get_rng_state,
    set_rng_state,
    in_dynamic_mode,
)
from .framework.conveniences import (  # noqa
    broadcast_shape,
    device_guard,
    disable_signal_handler,
    get_cudnn_version,
    is_compiled_with_cinn,
    is_compiled_with_custom_device,
    set_printoptions,
)
from .framework.dtype import finfo, iinfo  # noqa
from .framework.dtype import (  # noqa
    get_default_dtype,
    is_compiled_with_rocm,
    set_default_dtype,
)
from .framework.dtype import (
    bool_ as bool,  # noqa: A001
    uint8,
    int8,
    int16,
    int32,
    int64,
    float16,
    bfloat16,
    float32,
    float64,
    complex64,
    complex128,
    DType as dtype,
)
from .device import (
    set_device,
    get_device,
    device_count,
    is_compiled_with_cuda,
    is_compiled_with_tpu,
    is_compiled_with_xpu,
    CPUPlace,
    CUDAPlace,
    TPUPlace,
    CustomPlace,
)

# -- tensor op namespace (everything is also a Tensor method) --------------
from .tensor import *  # noqa: F401,F403
from .tensor.random import (
    rand,
    randn,
    randint,
    randint_like,
    randperm,
    normal,
    uniform,
    standard_normal,
    bernoulli,
    multinomial,
    poisson,
    binomial,
    standard_gamma,
    log_normal,
    rand_like,
    randn_like,
)
from .tensor import creation, linalg, logic, manipulation, math, search, stat

# -- subsystems -------------------------------------------------------------
from . import autograd
from . import device
from . import framework
from .autograd import grad
from .autograd.py_layer import PyLayer

# init-time crash handlers + VLOG tiers (upstream: platform/init.cc)
from .framework import log as _log  # noqa: E402

if framework.flags.flag("enable_signal_handler"):
    _log.install_signal_handlers()

def enable_static():
    """Enter static-graph mode: ops record into
    ``static.default_main_program()`` until ``disable_static()``."""
    from .static import _enable_static

    _enable_static()


def disable_static():
    """Back to dygraph (the default mode)."""
    from .static import _disable_static

    _disable_static()




def is_grad_enabled_():
    return is_grad_enabled()


def _lazy_imports():
    """Import heavier subpackages; called at end of module init."""
    global nn, optimizer, io, jit, static, vision, hapi, metric
    global distributed, incubate, amp, profiler, vision, callbacks, Model
    global DataParallel, utils, inference, sparse, flops, summary
    global hub, ParamAttr, LazyGuard
    from . import utils  # noqa
    from . import fft  # noqa
    from . import signal  # noqa
    from . import distribution  # noqa
    from . import audio  # noqa
    from . import quantization  # noqa
    from . import text  # noqa
    from . import geometric  # noqa
    from . import version  # noqa
    from . import regularizer  # noqa
    from . import inference  # noqa
    from . import sparse  # noqa
    from . import nn  # noqa
    from . import optimizer  # noqa
    from . import io  # noqa
    from . import amp  # noqa
    from . import jit  # noqa
    from . import static  # noqa
    from . import vision  # noqa
    from . import metric  # noqa
    from . import hapi  # noqa
    from . import hub  # noqa
    from .nn.param_attr import ParamAttr  # noqa (top-level like upstream)
    from .nn.layer.layers import LazyGuard  # noqa (paddle.LazyGuard)
    from .hapi import Model, callbacks, flops, summary  # noqa
    from . import distributed  # noqa
    from . import incubate  # noqa
    from . import profiler  # noqa
    from .distributed.parallel import DataParallel  # noqa


try:
    _lazy_imports()
except ImportError:  # during bootstrap some subpackages may not exist yet
    pass
