"""paddle.inference analog (upstream: paddle/fluid/inference/api/
analysis_predictor.cc + python/paddle/inference/).

The reference's AnalysisPredictor loads a saved Program, runs IR
optimization passes, and executes with zero-copy IO; TensorRT handles
subgraph offload. TPU-native, the saved artifact is a StableHLO
exported program (jit.save), the "analysis passes + TRT" role is XLA's
compiler, and the predictor is a thin zero-copy host<->device shim with
a persistent compiled call.
"""
from __future__ import annotations

import numpy as np

__all__ = [
    "Config",
    "Predictor",
    "Tensor",
    "create_predictor",
    "PlaceType",
    "Request",
    "BatchScheduler",
    "RequestState",
    "QueueFullError",
    "PagedLlamaAdapter",
    "PagedXing4Adapter",
    "RadixPrefixCache",
    "PrefixMatch",
    "bucket_packed_tokens",
    "ServingEngine",
    "TokenStream",
    "EngineClosedError",
    "EngineOverloadError",
    "PrefillWorker",
    "DecodeWorker",
    "DisaggReplica",
    "SessionRouter",
    "SessionStream",
    "apply_role_budgets",
    "role_scheduler_kwargs",
]

from .serving import (  # noqa: E402
    BatchScheduler,
    QueueFullError,
    Request,
    RequestState,
    bucket_packed_tokens,
)
from .engine import (  # noqa: E402
    EngineClosedError,
    EngineOverloadError,
    ServingEngine,
    TokenStream,
)
from .disagg import (  # noqa: E402
    DecodeWorker,
    DisaggReplica,
    PrefillWorker,
    SessionRouter,
    SessionStream,
    apply_role_budgets,
    role_scheduler_kwargs,
)
# the serving adapters BatchScheduler drives (docs/SERVING.md, "Page
# formats and the adapter contract"): PagedLlamaAdapter serves dense
# Llama/Mistral models from "kv" pages and, read off the model's config,
# a window-and-summary model (models.EvaByteForCausalLM) from "eva" pages;
# PagedXing4Adapter serves Xing4 from "latent" pages
from .paged_llama import PagedLlamaAdapter  # noqa: E402
from .paged_xing4 import PagedXing4Adapter  # noqa: E402
from .prefix_cache import RadixPrefixCache, PrefixMatch  # noqa: E402


class PlaceType:
    CPU = 0
    GPU = 1
    XPU = 2
    CUSTOM = 3  # tpu rides the custom slot upstream


class Config:
    """Predictor configuration (upstream: paddle_infer::Config).
    Model path conventions match jit.save: prefix or explicit
    (model_file, params_file)."""

    def __init__(self, model_path=None, params_path=None):
        if model_path is not None and model_path.endswith(".pdmodel"):
            model_path = model_path[: -len(".pdmodel")]
        self._prefix = model_path
        self._memory_pool_mb = 0
        self._device = "tpu"
        self._device_id = 0
        self._enabled_xla = True

    def set_model(self, model_path, params_path=None):
        if model_path.endswith(".pdmodel"):
            model_path = model_path[: -len(".pdmodel")]
        self._prefix = model_path

    def model_dir(self):
        return self._prefix

    def enable_use_gpu(self, memory_pool_mb=100, device_id=0):
        # accepted for API parity; placement is PJRT's
        self._memory_pool_mb = memory_pool_mb
        self._device_id = device_id

    def disable_gpu(self):
        self._device = "cpu"

    def enable_memory_optim(self):
        pass  # XLA buffer assignment owns this

    def switch_ir_optim(self, flag=True):
        pass  # XLA is always-on; there is no unoptimized interpreter

    def enable_tensorrt_engine(self, *a, **k):
        raise RuntimeError(
            "TensorRT does not exist on TPU; XLA compiles the whole "
            "program (the role TRT subgraphs play in the reference)"
        )

    def summary(self):
        return {
            "model": self._prefix,
            "device": self._device,
            "compiler": "XLA (StableHLO artifact)",
        }


class Tensor:
    """Zero-copy-style IO handle (upstream: paddle_infer::Tensor)."""

    def __init__(self, name):
        self.name = name
        self._value = None

    def copy_from_cpu(self, arr):
        self._value = np.ascontiguousarray(arr)

    def copy_to_cpu(self):
        return np.asarray(self._value)

    def reshape(self, shape):
        if self._value is not None:
            self._value = self._value.reshape(shape)

    def shape(self):
        return None if self._value is None else list(self._value.shape)


class Predictor:
    """Runs a jit.save artifact (upstream: AnalysisPredictor)."""

    def __init__(self, config: Config):
        from .. import jit

        if config.model_dir() is None:
            raise ValueError("Config has no model path")
        self._layer = jit.load(config.model_dir())
        self._n_inputs = getattr(self._layer, "_n_inputs", 1)
        self._inputs = [Tensor(f"input_{i}") for i in range(self._n_inputs)]
        self._outputs = []

    def get_input_names(self):
        return [t.name for t in self._inputs]

    def get_input_handle(self, name):
        for t in self._inputs:
            if t.name == name:
                return t
        raise KeyError(name)

    def run(self):
        unfed = [t.name for t in self._inputs if t._value is None]
        if unfed:
            raise ValueError(
                f"predictor inputs not set: {unfed}; fill every handle "
                "via get_input_handle(name).copy_from_cpu(...)"
            )
        args = [t._value for t in self._inputs]
        out = self._layer(*args)
        outs = out if isinstance(out, tuple) else (out,)
        self._outputs = []
        for i, o in enumerate(outs):
            h = Tensor(f"output_{i}")
            h._value = np.asarray(o._data)
            self._outputs.append(h)
        return True

    def get_output_names(self):
        return [t.name for t in self._outputs] or [
            f"output_{i}" for i in range(1)
        ]

    def get_output_handle(self, name):
        for t in self._outputs:
            if t.name == name:
                return t
        raise KeyError(name)


def create_predictor(config: Config) -> Predictor:
    return Predictor(config)
