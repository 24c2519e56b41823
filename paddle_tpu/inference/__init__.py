"""Inference stack: predictor API + StableHLO export.

Reference: paddle/fluid/inference/api/analysis_predictor.h:47
AnalysisPredictor (Run/ZeroCopyRun over an analysed program),
paddle_analysis_config.h AnalysisConfig, ZeroCopyTensor, and the engine
bridges (tensorrt/anakin subgraph engines).

TPU-native design: the "analysis passes + engine" pipeline is XLA — a saved
inference model is pruned, loaded, jit-compiled once per feed signature,
and cached. The TensorRT/Anakin role (portable serving artifact compiled
outside Python) is played by **StableHLO export** via ``jax.export``: the
artifact embeds the weights and runs from any PJRT runtime without
paddle_tpu installed.
"""
from __future__ import annotations

import os
from typing import Dict, List, Optional, Sequence

import numpy as np

from .. import io as io_mod
from ..executor import CPUPlace, Executor, Scope, scope_guard

__all__ = ["AnalysisConfig", "AnalysisPredictor", "ZeroCopyTensor",
           "create_paddle_predictor", "export_stablehlo", "load_stablehlo",
           "StableHLOPredictor"]


class AnalysisConfig:
    """reference paddle_analysis_config.h — the knobs that still mean
    something plus accepted-for-parity switches (XLA owns fusion/memory)."""

    def __init__(self, model_dir: Optional[str] = None,
                 prog_file: Optional[str] = None,
                 params_file: Optional[str] = None):
        self._model_dir = model_dir
        self._prog_file = prog_file
        self._params_file = params_file
        self._use_accelerator = True
        self._memory_optim = True  # inert: XLA buffer assignment

    def set_model(self, model_dir: str):
        self._model_dir = model_dir

    def model_dir(self) -> str:
        return self._model_dir

    def enable_use_gpu(self, memory_pool_init_size_mb=100, device_id=0):
        self._use_accelerator = True  # the accelerator here is the TPU

    def disable_gpu(self):
        self._use_accelerator = False

    def use_gpu(self) -> bool:
        return self._use_accelerator

    def enable_memory_optim(self):
        self._memory_optim = True

    def switch_use_feed_fetch_ops(self, flag: bool):
        pass  # feed/fetch are executor-spliced, never ops

    def switch_ir_optim(self, flag: bool = True):
        pass  # XLA always optimises

    def enable_tensorrt_engine(self, **kw):
        raise NotImplementedError(
            "TensorRT has no TPU analogue — use export_stablehlo() for a "
            "portable compiled-serving artifact")


class ZeroCopyTensor:
    """reference api/paddle_api.h ZeroCopyTensor: named input/output handle
    with copy_from_cpu/copy_to_cpu."""

    def __init__(self, name: str, owner: "AnalysisPredictor", is_input: bool):
        self.name = name
        self._owner = owner
        self._is_input = is_input

    def copy_from_cpu(self, arr: np.ndarray) -> None:
        if not self._is_input:
            raise RuntimeError(f"'{self.name}' is an output tensor")
        self._owner._feeds[self.name] = np.asarray(arr)

    def copy_to_cpu(self) -> np.ndarray:
        if self._is_input:
            return np.asarray(self._owner._feeds[self.name])
        return np.asarray(self._owner._outputs[self.name])

    def shape(self):
        return list(self.copy_to_cpu().shape)


class AnalysisPredictor:
    """reference analysis_predictor.h:47. One predictor = one loaded
    inference program + its own scope + a compile cache (inside Executor)."""

    def __init__(self, config: AnalysisConfig):
        self._config = config
        # disable_gpu() pins the host; otherwise the default place — the
        # accelerator wherever JAX has one
        self._exe = Executor(None if config.use_gpu() else CPUPlace())
        self._scope = Scope()
        model_dir = config.model_dir()
        model_fn = params_fn = None
        if model_dir is None:
            # combined-file form: AnalysisConfig(prog_file, params_file)
            if not (config._prog_file and config._params_file):
                raise ValueError(
                    "AnalysisConfig needs model_dir or both prog_file and "
                    "params_file")
            model_dir = os.path.dirname(config._prog_file) or "."
            model_fn = os.path.basename(config._prog_file)
            params_fn = os.path.basename(config._params_file)
        with scope_guard(self._scope):
            self._program, self._feed_names, fetch_vars = \
                io_mod.load_inference_model(model_dir, self._exe,
                                            model_filename=model_fn,
                                            params_filename=params_fn)
        self._fetch_names = [v.name for v in fetch_vars]
        self._feeds: Dict[str, np.ndarray] = {}
        self._outputs: Dict[str, np.ndarray] = {}

    # -- names & handles --------------------------------------------------
    def get_input_names(self) -> List[str]:
        return list(self._feed_names)

    def get_output_names(self) -> List[str]:
        return list(self._fetch_names)

    def get_input_handle(self, name: str) -> ZeroCopyTensor:
        if name not in self._feed_names:
            raise KeyError(f"unknown input '{name}'; have {self._feed_names}")
        return ZeroCopyTensor(name, self, is_input=True)

    def get_output_handle(self, name: str) -> ZeroCopyTensor:
        if name not in self._fetch_names:
            raise KeyError(f"unknown output '{name}'")
        return ZeroCopyTensor(name, self, is_input=False)

    get_input_tensor = get_input_handle
    get_output_tensor = get_output_handle

    # -- execution --------------------------------------------------------
    def run(self, inputs: Optional[Sequence[np.ndarray]] = None):
        """With ``inputs``: positional arrays aligned with input names
        (reference Run(inputs, &outputs)); without: ZeroCopyRun over the
        handles filled via copy_from_cpu."""
        if inputs is not None:
            if len(inputs) != len(self._feed_names):
                raise ValueError(
                    f"expected {len(self._feed_names)} inputs "
                    f"({self._feed_names}), got {len(inputs)}")
            self._feeds = dict(zip(self._feed_names,
                                   (np.asarray(a) for a in inputs)))
        missing = [n for n in self._feed_names if n not in self._feeds]
        if missing:
            raise RuntimeError(f"inputs not set: {missing}")
        with scope_guard(self._scope):
            outs = self._exe.run(self._program, feed=dict(self._feeds),
                                 fetch_list=self._fetch_names)
        self._outputs = dict(zip(self._fetch_names, outs))
        return [self._outputs[n] for n in self._fetch_names]

    zero_copy_run = run


def create_paddle_predictor(config: AnalysisConfig) -> AnalysisPredictor:
    """reference CreatePaddlePredictor<AnalysisConfig>."""
    return AnalysisPredictor(config)


# ---------------------------------------------------------------------------
# StableHLO export (the TRT/Anakin replacement)
# ---------------------------------------------------------------------------

def export_stablehlo(program, feed_specs: Dict[str, tuple], fetch_list,
                     path: str, scope=None):
    """Serialize an inference program as a portable StableHLO artifact.

    feed_specs: {name: (shape, dtype)} fixing the signature. Writes
    ``<path>`` (jax.export binary, runs from any PJRT runtime via
    ``load_stablehlo``) and ``<path>.mlir`` (human-readable StableHLO).
    Weights are embedded as constants — the artifact is self-contained
    (the role of a frozen TRT engine)."""
    import jax
    from jax import export as jexport

    from ..executor import analyze_block_io, global_scope, make_step_fn

    scope = scope or global_scope()
    fetch_names = [f if isinstance(f, str) else f.name for f in fetch_list]
    feed_names = set(feed_specs)
    io = analyze_block_io(program.global_block, feed_names, fetch_names)
    step = make_step_fn(program.global_block, io, fetch_names)
    state = []
    for n in io["donated"] + io["ro"]:
        v = scope.find_var(n)
        if v is None:
            raise RuntimeError(f"var '{n}' not in scope — run startup/load "
                               f"params before exporting")
        state.append(np.asarray(v))
    n_don = len(io["donated"])

    def infer_fn(*feed_vals):
        feeds = list(feed_vals)
        fetches, _ = step(feeds, [jax.numpy.asarray(s)
                                  for s in state[:n_don]],
                          [jax.numpy.asarray(s) for s in state[n_don:]],
                          jax.random.key(0))
        return tuple(fetches)

    args = [jax.ShapeDtypeStruct(tuple(s), np.dtype(d))
            for n, (s, d) in ((n, feed_specs[n])
                              for n in io["feed_order"])]
    exported = jexport.export(jax.jit(infer_fn))(*args)
    with open(path, "wb") as f:
        f.write(exported.serialize())
    with open(path + ".mlir", "w") as f:
        f.write(exported.mlir_module())
    return {"feed_order": io["feed_order"], "fetch_names": fetch_names}


class StableHLOPredictor:
    """Run a serialized StableHLO artifact (no Program machinery needed)."""

    def __init__(self, path: str):
        from jax import export as jexport

        with open(path, "rb") as f:
            self._exported = jexport.deserialize(f.read())

    def run(self, *inputs):
        return [np.asarray(v) for v in self._exported.call(*inputs)]


def load_stablehlo(path: str) -> StableHLOPredictor:
    return StableHLOPredictor(path)
