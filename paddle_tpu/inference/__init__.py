"""Inference API (ref: paddle/fluid/inference/ AnalysisPredictor,
 python/paddle/inference/).

The reference's predictor runs analysis passes (op fusion, TensorRT subgraphs)
over a saved program, then executes with zero-copy input/output handles.  The
TPU-native analog: load the StableHLO artifact saved by ``jit.save`` /
``static.save_inference_model`` — XLA performs the fusion/layout work the
analysis passes did — and run it on the target device.  The handle-based API
(get_input_handle / copy_from_cpu / run / get_output_handle) is preserved.
"""
from __future__ import annotations

import os
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np


class PrecisionType:
    Float32 = "float32"
    Half = "float16"
    Bfloat16 = "bfloat16"
    Int8 = "int8"


class PlaceType:
    CPU = "cpu"
    GPU = "gpu"      # parity alias; maps to the accelerator
    TPU = "tpu"
    XPU = "xpu"


class Config:
    """ref: paddle_infer.Config. Device/memory knobs that map to XLA are
    honored; CUDA-specific ones are accepted and ignored."""

    def __init__(self, prog_file: Optional[str] = None,
                 params_file: Optional[str] = None):
        if prog_file and prog_file.endswith(".pdmodel"):
            prog_file = prog_file[:-len(".pdmodel")]
        self.model_prefix = prog_file
        self.params_file = params_file
        self._device = "tpu" if any(
            d.platform == "tpu" for d in jax.devices()) else "cpu"
        self._device_id = 0
        self._precision = PrecisionType.Float32
        self._profile = False
        self._glog = True

    # model path accessors (ref: Config::SetModel / model_dir / prog_file)
    def set_model(self, prog_or_dir: str, params_file: Optional[str] = None):
        if os.path.isdir(prog_or_dir):
            self.model_prefix = os.path.join(prog_or_dir, "model")
        else:
            p = prog_or_dir
            if p.endswith(".pdmodel"):
                p = p[:-len(".pdmodel")]
            self.model_prefix = p
        self.params_file = params_file

    def set_prog_file(self, path: str):
        self.set_model(path, params_file=self.params_file)

    def set_params_file(self, path: str):
        self.params_file = path

    def prog_file(self):
        return (self.model_prefix or "") + ".pdmodel"

    # device selection
    def enable_use_gpu(self, memory_pool_init_size_mb=100, device_id=0,
                       precision=PrecisionType.Float32):
        self._device_id = device_id
        self._precision = precision

    def disable_gpu(self):
        self._device = "cpu"

    def enable_xpu(self, *a, **k):
        pass

    def set_cpu_math_library_num_threads(self, n):
        pass

    def enable_memory_optim(self, flag=True):
        pass  # XLA's buffer assignment already does this

    def switch_ir_optim(self, flag=True):
        pass  # XLA fusion replaces IR passes

    def enable_tensorrt_engine(self, *a, **k):
        pass  # no TRT on TPU; XLA compiles the whole graph

    def enable_profile(self):
        """Per-run host+device timing via paddle.profiler (real wiring:
        Predictor.run brackets execution with RecordEvent)."""
        self._profile = True

    def disable_glog_info(self):
        self._glog = False

    def glog_info_disabled(self):
        return not self._glog

    def use_gpu(self):
        return False  # device is tpu/cpu here, never CUDA

    def gpu_device_id(self):
        return self._device_id

    def model_dir(self):
        return os.path.dirname(self.model_prefix or "")

    def summary(self) -> str:
        """ref: Config::Summary — a human-readable option table."""
        rows = [
            ("model_prefix", self.model_prefix),
            ("params_file", self.params_file),
            ("device", f"{self._device}:{self._device_id}"),
            ("precision", self._precision),
            ("profile", self._profile),
            ("backend", "XLA (fusion/memory passes in the compiler)"),
        ]
        w = max(len(k) for k, _ in rows)
        return "\n".join(f"{k:<{w}}  {v}" for k, v in rows)


class Tensor_:
    """I/O handle (ref: paddle_infer.Tensor): name + staged host array."""

    def __init__(self, name: str, shape=None, dtype=None):
        self.name = name
        self._shape = shape
        self._dtype = dtype
        self._value = None

    def copy_from_cpu(self, arr: np.ndarray):
        self._value = np.ascontiguousarray(arr)

    def copy_to_cpu(self) -> np.ndarray:
        return np.asarray(self._value)

    def shape(self):
        v = self._value
        return list(v.shape) if v is not None else list(self._shape or [])

    def reshape(self, shape):
        if self._value is not None:
            self._value = self._value.reshape(shape)
        else:
            self._shape = list(shape)


class Predictor:
    """ref: AnalysisPredictor via the handle API."""

    def __init__(self, config: Config, _shared_model=None):
        from ..static import load_inference_model
        self._config = config
        if _shared_model is not None:
            self._model = _shared_model
        else:
            if config.model_prefix is None:
                raise ValueError("Config needs a model path prefix")
            self._model = load_inference_model(config.model_prefix)
        self._inputs: Dict[str, Tensor_] = {
            n: Tensor_(n) for n in self._model.feed_names}
        self._outputs: List[np.ndarray] = []
        self._out_names = [f"fetch_{i}"
                           for i in range(self._model.meta["num_fetch"])]

    def clone(self):
        """A predictor over the SAME loaded/compiled model with its own I/O
        handles (ref: AnalysisPredictor::Clone — per-thread predictors share
        weights; here they also share XLA executables)."""
        return Predictor(self._config, _shared_model=self._model)

    def get_input_names(self) -> List[str]:
        return list(self._inputs)

    def get_input_handle(self, name: str) -> Tensor_:
        return self._inputs[name]

    def run(self, inputs: Optional[List[np.ndarray]] = None):
        if inputs is not None:
            for h, a in zip(self._inputs.values(), inputs):
                h.copy_from_cpu(np.asarray(a))
        feeds = {n: h._value for n, h in self._inputs.items()}
        missing = [n for n, v in feeds.items() if v is None]
        if missing:
            raise RuntimeError(f"inputs not set: {missing}")
        import contextlib
        if self._config._profile:
            from ..profiler import RecordEvent
            span = RecordEvent("inference::Predictor::run")
        else:
            span = contextlib.nullcontext()
        with span:
            self._outputs = self._model.run(feeds)
        if inputs is not None:
            return [np.asarray(o) for o in self._outputs]
        return None

    def get_output_names(self) -> List[str]:
        return list(self._out_names)

    def get_output_handle(self, name: str) -> Tensor_:
        idx = self._out_names.index(name)
        h = Tensor_(name)
        h._value = self._outputs[idx]
        return h


    def clear_intermediate_tensor(self):
        pass  # XLA frees intermediates after each executable run

    def try_shrink_memory(self):
        pass  # device arena is PJRT's


class PredictorPool:
    """N predictors sharing one loaded model (ref: services run one
    predictor per worker thread; paddle_infer.PredictorPool)."""

    def __init__(self, config: Config, size: int):
        if size < 1:
            raise ValueError(f"PredictorPool size must be >= 1, got {size}")
        first = Predictor(config)
        self._preds = [first] + [first.clone() for _ in range(size - 1)]

    def retrieve(self, idx: int) -> Predictor:
        return self._preds[idx]


def create_predictor(config: Config) -> Predictor:
    return Predictor(config)


def get_version() -> str:
    from .. import __version__
    return __version__


def get_num_bytes_of_data_type(dtype) -> int:
    return np.dtype(getattr(dtype, "value", dtype)).itemsize


__all__ = ["Config", "Predictor", "PredictorPool", "create_predictor",
           "get_version", "get_num_bytes_of_data_type", "PrecisionType",
           "PlaceType"]


# --- continuous-batching serving engine (paged KV cache) -------------------
from .kv_cache import (BlockPool, BlockPoolError, PrefixCache,  # noqa: E402
                       SlotPool, pad_table)
from .engine import (Admission, AdmissionController, InferenceEngine,  # noqa: E402
                     PoisonError, Request, ServeConfig)
from .journal import (EngineJournal, JournalCompatError,  # noqa: E402
                      read_journal)
from .fleet import FleetRouter  # noqa: E402

__all__ += ["BlockPool", "BlockPoolError", "PrefixCache", "SlotPool",
            "pad_table",
            "InferenceEngine", "Request", "ServeConfig", "Admission",
            "AdmissionController", "PoisonError", "EngineJournal",
            "JournalCompatError", "read_journal", "FleetRouter"]
