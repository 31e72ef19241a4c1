"""Inference API — the ``paddle_infer`` Predictor surface.

Reference counterpart: ``paddle/fluid/inference/`` ``AnalysisPredictor`` +
``paddle_infer::Config/Predictor/Tensor`` (SURVEY.md §2.1 "Inference
engine", §3.6): load a serialized program + params, run an IR optimisation
pass pipeline (fusions, constant folding, TensorRT subgraph replacement),
expose zero-copy input/output handles.

TPU-native mapping: the serialized program is a **StableHLO export**
(``paddle_tpu.jit.save``); the reference's whole analysis/fusion pass
pipeline and the TensorRT role are **XLA's compilation** of that program for
the target device — there is no separate IR pass layer to re-implement, and
that is the design, not a gap. ``Config`` keeps the reference's switches as
accepted-and-recorded no-ops where XLA subsumes them, so deployment scripts
port unchanged; handle objects give the same copy_from_cpu/copy_to_cpu
workflow.

Int8 deployment (the reference's PaddleSlim/TRT-int8 flow): quantize at
CONVERSION time — ``quantization.PTQ(...).quantize`` + calibrate +
``convert`` rewrites Linear layers to real int8 MXU matmuls, and the
converted model exports/serves through ``jit.save`` + ``Predictor``
unchanged (see tests/test_ckpt_inference.py).
"""

from __future__ import annotations

import os
from typing import Dict, List, Optional

import numpy as np

__all__ = ["Config", "Predictor", "InferTensor", "create_predictor",
           "PrecisionType", "PlaceType"]

# Online serving subsystem (r7/r11/r12/r13): imported lazily by
# consumers —
# ``from paddle_tpu.inference.serving import ServingEngine``,
# ``from paddle_tpu.inference.scheduler import OnlineScheduler /
# SLOScheduler`` (r13: priorities, preemption, deadline shedding),
# ``from paddle_tpu.inference.prefix_cache import PagedPrefixCache /
# make_prefix_cache``, ``from
# paddle_tpu.inference.paged_kv import PagedKVCache``, ``from
# paddle_tpu.inference.kv_tiers import HostTier`` (r19: the host-RAM
# spill tier + tier-transfer accounting), ``from
# paddle_tpu.inference.fleet import FleetRouter / build_fleet /
# CacheDirectory / FaultInjector`` (r13: health states + failover;
# r19: directed cache-hit steering), ``from
# paddle_tpu.inference.program_space import PROGRAM_SPACE /
# WorkloadEnvelope`` (r20: the declared program-key registry behind
# ``ServingEngine.program_space``/``aot_warmup`` and the
# analysis.coverage gate pass) — kept
# out of this namespace so importing the Predictor surface doesn't pull
# jax model code.


class PrecisionType:
    Float32 = "float32"
    Half = "float16"
    Bfloat16 = "bfloat16"
    Int8 = "int8"


class PlaceType:
    CPU = "cpu"
    GPU = "gpu"
    TPU = "tpu"
    XPU = "xpu"


class Config:
    """``paddle_infer.Config`` analog (model path + device/precision knobs)."""

    def __init__(self, prog_file: Optional[str] = None,
                 params_file: Optional[str] = None):
        # accept "path_prefix" style (jit.save prefix) or explicit files
        if prog_file and prog_file.endswith(".pdmodel"):
            prog_file = prog_file[: -len(".pdmodel")]
        self.model_prefix = prog_file
        self.params_file = params_file
        self.device = PlaceType.TPU
        self.device_id = 0
        self.precision = PrecisionType.Float32
        self._ir_optim = True
        self._memory_optim = True

    # --- device selection (XLA owns placement; we record intent) ---
    def enable_use_gpu(self, memory_pool_init_size_mb: int = 100,
                       device_id: int = 0, precision=PrecisionType.Float32):
        self.device, self.device_id, self.precision = PlaceType.TPU, device_id, precision

    def disable_gpu(self):
        self.device = PlaceType.CPU

    def enable_xpu(self, *a, **k):
        self.device = PlaceType.TPU

    def use_gpu(self) -> bool:
        return self.device != PlaceType.CPU

    # --- pass pipeline switches: XLA compiles the exported program; these
    # record intent for API parity (the reference toggles IR passes) ---
    def switch_ir_optim(self, flag: bool = True):
        self._ir_optim = flag

    def enable_memory_optim(self, flag: bool = True):
        self._memory_optim = flag

    def enable_tensorrt_engine(self, *a, **k):
        pass  # XLA is the whole-graph compiler on TPU

    def set_cpu_math_library_num_threads(self, n: int):
        pass

    def summary(self) -> str:
        return (f"Config(model={self.model_prefix!r}, device={self.device}, "
                f"precision={self.precision})")


class InferTensor:
    """Zero-copy-style handle (``paddle_infer.Tensor``)."""

    def __init__(self, name: str):
        self.name = name
        self._value: Optional[np.ndarray] = None

    def reshape(self, shape):
        pass  # shape comes from the copied array

    def copy_from_cpu(self, arr: np.ndarray):
        self._value = np.ascontiguousarray(arr)

    def copy_to_cpu(self) -> np.ndarray:
        return np.asarray(self._value)

    def shape(self):
        return list(self._value.shape) if self._value is not None else []


class Predictor:
    """Runs the exported StableHLO program (reference: AnalysisPredictor)."""

    def __init__(self, config: Config):
        from .. import jit

        if config.model_prefix is None:
            raise ValueError("Config needs the jit.save path prefix")
        self.config = config
        self._fn = jit.load(config.model_prefix)
        self._n_inputs = self._infer_n_inputs()
        self._inputs: List[InferTensor] = [
            InferTensor(f"input_{i}") for i in range(self._n_inputs)]
        self._outputs: List[InferTensor] = []

    def _infer_n_inputs(self) -> int:
        import pickle

        meta_path = self.config.model_prefix + ".pdmeta"
        if os.path.exists(meta_path):
            with open(meta_path, "rb") as f:
                meta = pickle.load(f)
            if "n_inputs" in meta:
                return meta["n_inputs"]
        return 1

    def get_input_names(self) -> List[str]:
        return [t.name for t in self._inputs]

    def get_input_handle(self, name: str) -> InferTensor:
        for t in self._inputs:
            if t.name == name:
                return t
        raise KeyError(name)

    def _ensure_output(self, i: int) -> "InferTensor":
        while len(self._outputs) <= i:
            self._outputs.append(InferTensor(f"output_{len(self._outputs)}"))
        return self._outputs[i]

    def run(self) -> bool:
        args = [t._value for t in self._inputs]
        if any(a is None for a in args):
            raise RuntimeError("copy_from_cpu all inputs before run()")
        out = self._fn(*args)
        outs = out if isinstance(out, (tuple, list)) else [out]
        # bind results onto PERSISTENT handles: deployment scripts grab
        # output handles once (possibly before the first run) and re-read
        # them after each run(), the paddle_infer pattern
        for i, o in enumerate(outs):
            h = self._ensure_output(i)
            h._value = np.asarray(o.numpy() if hasattr(o, "numpy") else o)
        return True

    def get_output_names(self) -> List[str]:
        return [t.name for t in self._outputs] or [self._ensure_output(0).name]

    def get_output_handle(self, name: str) -> InferTensor:
        for t in self._outputs:
            if t.name == name:
                return t
        if name.startswith("output_") and name[7:].isdigit():
            return self._ensure_output(int(name[7:]))
        raise KeyError(name)


def create_predictor(config: Config) -> Predictor:
    return Predictor(config)
