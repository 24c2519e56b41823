"""Optimizer family (reference: python/paddle/fluid/optimizer.py:53 Optimizer
base, :634-2360 the 13 concrete optimizers).

Same architecture as the reference: ``minimize`` = ``append_backward`` +
``apply_gradients``; each optimizer appends per-param update OPS to the main
program and creates accumulator vars (persistable) initialised in the startup
program. Because the whole step compiles to one XLA executable, the
reference's fuse_optimizer_ops/coalesce_grad_tensor passes are unnecessary.
"""
from __future__ import annotations

import contextlib
from typing import Dict, List, Optional

from . import unique_name
from .backward import append_backward
from .clip import append_gradient_clip_ops, error_clip_callback
from .framework import (Parameter, Program, Variable, default_main_program,
                        default_startup_program, program_guard)
from .initializer import Constant
from .layer_helper import LayerHelper
from .regularizer import append_regularization_ops

__all__ = [
    "SGD", "Momentum", "Adagrad", "Adam", "Adamax", "AdamW", "DecayedAdagrad",
    "Adadelta", "RMSProp", "Ftrl", "Lamb", "LarsMomentum",
    "SGDOptimizer", "MomentumOptimizer", "AdagradOptimizer", "AdamOptimizer",
    "AdamaxOptimizer", "DecayedAdagradOptimizer", "AdadeltaOptimizer",
    "RMSPropOptimizer", "FtrlOptimizer", "LambOptimizer",
    "LarsMomentumOptimizer", "ExponentialMovingAverage", "ModelAverage",
    "LookaheadOptimizer", "RecomputeOptimizer", "PipelineOptimizer",
    "GradientMergeOptimizer", "DGCMomentumOptimizer",
]


class Optimizer:
    def __init__(self, learning_rate, regularization=None, name=None):
        self._learning_rate = learning_rate
        self.regularization = regularization
        self._name = name
        self._accumulators: Dict[str, Dict[str, Variable]] = {}
        self._eager_accumulators: Dict[int, dict] = {}  # dygraph-mode state
        self._learning_rate_var: Optional[Variable] = None
        self.type = "optimizer"

    # -- learning rate ---------------------------------------------------
    def _create_global_learning_rate(self):
        if isinstance(self._learning_rate, Variable):
            self._learning_rate_var = self._learning_rate
            return
        if self._learning_rate_var is not None:
            return
        name = unique_name.generate("learning_rate")
        main_block = default_main_program().global_block
        self._learning_rate_var = main_block.create_var(
            name=name, shape=(1,), dtype="float32", persistable=True,
            stop_gradient=True)
        startup = default_startup_program().global_block
        startup.create_var(name=name, shape=(1,), dtype="float32",
                           persistable=True)
        startup.append_op("fill_constant", outputs={"Out": name},
                          attrs={"shape": [1], "dtype": "float32",
                                 "value": float(self._learning_rate)})

    def _create_param_lr(self, param_and_grad):
        param = param_and_grad[0]
        mult = (param.optimize_attr or {}).get("learning_rate", 1.0)
        if mult == 1.0:
            return self._learning_rate_var
        helper = LayerHelper("param_lr")
        out = helper.create_variable_for_type_inference("float32", True)
        helper.append_op("scale", inputs={"X": self._learning_rate_var},
                         outputs={"Out": out}, attrs={"scale": float(mult)})
        return out

    # -- accumulators ----------------------------------------------------
    def _add_accumulator(self, name: str, param: Parameter, dtype=None,
                         fill_value=0.0, shape=None):
        if name in self._accumulators and param.name in self._accumulators[name]:
            return self._accumulators[name][param.name]
        shape = list(shape if shape is not None else param.shape)
        dtype = dtype or param.dtype
        var_name = unique_name.generate(f"{name}_{param.name}")
        main_block = default_main_program().global_block
        var = main_block.create_var(name=var_name, shape=tuple(shape),
                                    dtype=dtype, persistable=True,
                                    stop_gradient=True)
        # marks the var as shardable optimizer state for ZeRO-1
        # (BuildStrategy.ReduceStrategy.Reduce; ref build_strategy.h:58 kReduce)
        var.is_optimizer_state = True
        if (getattr(param, "is_distributed", False)
                and list(shape[:1]) == list(param.shape[:1])):
            # accumulators of a sharded embedding table shard with it
            var.is_distributed = True
        startup = default_startup_program().global_block
        startup.create_var(name=var_name, shape=tuple(shape), dtype=dtype,
                           persistable=True)
        startup.append_op("fill_constant", outputs={"Out": var_name},
                          attrs={"shape": shape, "dtype": dtype,
                                 "value": float(fill_value)})
        self._accumulators.setdefault(name, {})[param.name] = var
        return var

    def _get_accumulator(self, name: str, param: Parameter):
        return self._accumulators[name][param.name]

    # -- hooks implemented by subclasses ---------------------------------
    def _create_accumulators(self, block, parameters):
        pass

    def _append_optimize_op(self, block, param_and_grad):
        raise NotImplementedError

    def _finish_update(self, block, params_grads):
        pass

    # -- public API ------------------------------------------------------
    def backward(self, loss, startup_program=None, parameter_list=None,
                 no_grad_set=None, callbacks=None):
        return append_backward(loss, parameter_list, no_grad_set, callbacks)

    def apply_gradients(self, params_grads):
        # Guard on the program that owns the params, not whatever the global
        # default happens to be (reference optimizer.py apply_optimize wraps
        # in program_guard(loss.block.program, startup)).
        program = params_grads[0][0].block.program
        with program_guard(program), program._op_role_guard("optimize"):
            # current_block, not global: lets wrappers (AMP skip-update)
            # run the whole update inside a conditional sub-block
            block = program.current_block()
            params_grads = sorted(params_grads, key=lambda pg: pg[0].name)
            params_grads = append_gradient_clip_ops(params_grads)
            params_grads = append_regularization_ops(params_grads,
                                                     self.regularization)
            self._create_global_learning_rate()
            self._create_accumulators(block, [pg[0] for pg in params_grads])
            optimize_ops = []
            for pg in params_grads:
                optimize_ops.append(self._append_optimize_op(block, pg))
            self._finish_update(block, params_grads)
        return optimize_ops

    def apply_optimize(self, loss, startup_program, params_grads):
        return self.apply_gradients(params_grads)

    def minimize(self, loss, startup_program=None, parameter_list=None,
                 no_grad_set=None):
        from .dygraph import base as dy

        if dy.in_dygraph_mode():
            return self._dygraph_minimize(loss, parameter_list)
        program = loss.block.program
        with program_guard(program, startup_program):
            params_grads = self.backward(loss, startup_program,
                                         parameter_list, no_grad_set)
            optimize_ops = self.apply_gradients(params_grads)
        return optimize_ops, params_grads

    # -- dygraph (eager) path --------------------------------------------
    def _dygraph_minimize(self, loss, parameter_list=None):
        """Apply the update rule eagerly on (param, param._grad) pairs
        (reference dygraph minimize: optimizer ops run immediately on the
        grad twins). Reuses the SAME registry update-rule lowerings as the
        compiled path. Call loss.backward() first."""
        import jax.numpy as jnp

        from .core import registry
        from .dygraph import base as dy
        from .lowering import LowerCtx, eager_platform

        if parameter_list is None:
            raise ValueError(
                "dygraph minimize needs parameter_list (e.g. "
                "model.parameters()); the tape does not own the params")
        if type(self)._eager_slots is Optimizer._eager_slots and \
                self.type not in ("sgd",):
            raise NotImplementedError(
                f"{type(self).__name__} has no eager (dygraph) update path "
                f"yet — supported: SGD, Momentum, Adam/AdamW/Lamb")
        params = [p for p in parameter_list if p._grad is not None]
        if not params:
            raise RuntimeError(
                "no gradients found — call loss.backward() before minimize")
        clipped = self._eager_clip_grads(params)
        lr = self._current_lr()
        ctx = LowerCtx(platform=eager_platform())
        updated = []
        for p in params:
            # static-path order (reference _create_optimization_pass):
            # clip first, then fold regularization into the clipped grad
            base_grad = clipped[id(p)] if clipped is not None else p._grad
            grad = self._eager_regularized_grad(p, base_grad)
            slots = self._eager_slots(p)
            ins = {"Param": [p.value],
                   "Grad": [grad],
                   "LearningRate": [jnp.asarray([lr], p.value.dtype)]}
            for k, v in slots.items():
                ins[k] = [v]
            outs = registry.get_op_def(self.type).lower(
                ctx, ins, self._eager_attrs())
            p.set_value(outs["ParamOut"][0])
            self._eager_store(p, outs)
            updated.append(p)
        return updated, [(p, p._grad) for p in params]

    def _eager_clip_grads(self, params):
        """Apply set_gradient_clip eagerly (the static path's
        append_gradient_clip_ops, over jnp values): returns {id(p): grad}
        or None when no clip is installed."""
        import jax.numpy as jnp

        from .clip import (GradientClipByGlobalNorm, GradientClipByNorm,
                           GradientClipByValue, _clip_attr)

        clip = _clip_attr.get("__global__")
        if clip is None:
            return None
        grads = {id(p): p._grad for p in params}
        if isinstance(clip, GradientClipByValue):
            return {k: jnp.clip(g, clip.min, clip.max)
                    for k, g in grads.items()}
        if isinstance(clip, GradientClipByNorm):
            out = {}
            for k, g in grads.items():
                norm = jnp.sqrt(jnp.sum(jnp.square(g)))
                s = jnp.minimum(clip.clip_norm / jnp.maximum(norm, 1e-12),
                                1.0)
                out[k] = g * s
            return out
        if isinstance(clip, GradientClipByGlobalNorm):
            total = sum(jnp.sum(jnp.square(g)) for g in grads.values())
            gnorm = jnp.sqrt(total)
            scale = clip.clip_norm / jnp.maximum(gnorm, clip.clip_norm)
            return {k: g * scale for k, g in grads.items()}
        raise NotImplementedError(
            f"dygraph clip for {type(clip).__name__}")

    def _eager_regularized_grad(self, p, g=None):
        """L1/L2 weight decay folded into the grad, matching the static
        append_regularization_ops semantics."""
        import jax.numpy as jnp

        from .regularizer import L1DecayRegularizer, L2DecayRegularizer

        g = p._grad if g is None else g
        reg = self.regularization
        if reg is None:
            return g
        if isinstance(reg, L2DecayRegularizer):
            return g + reg._coeff * p.value
        if isinstance(reg, L1DecayRegularizer):
            return g + reg._coeff * jnp.sign(p.value)
        raise NotImplementedError(
            f"dygraph regularization for {type(reg).__name__}")

    def _current_lr(self) -> float:
        lr = self._learning_rate
        from .dygraph.learning_rate_scheduler import LearningRateDecay

        if isinstance(lr, LearningRateDecay):
            return lr()  # evaluates current rate, advances step_num
        if isinstance(lr, Variable):
            raise TypeError("dygraph mode needs a float learning rate or a "
                            "dygraph LearningRateDecay scheduler")
        return float(lr)

    def _eager_state(self, p) -> dict:
        # keyed per optimizer INSTANCE (like the static _accumulators) and
        # by the VarBase's stable uid — id(p) could be recycled after GC
        # and hand a new parameter a dead one's moments
        st = self._eager_accumulators.setdefault(p.uid, {})
        return st

    def _eager_slots(self, p) -> dict:
        """Extra input slots (accumulators) for this rule; default none."""
        return {}

    def _eager_store(self, p, outs) -> None:
        """Persist accumulator outputs after the update; default none."""

    def _eager_attrs(self) -> dict:
        return {}


class SGDOptimizer(Optimizer):
    def __init__(self, learning_rate, regularization=None, name=None):
        super().__init__(learning_rate, regularization, name)
        self.type = "sgd"

    def _append_optimize_op(self, block, param_and_grad):
        p, g = param_and_grad
        return block.append_op(
            "sgd",
            inputs={"Param": p, "Grad": g,
                    "LearningRate": self._create_param_lr(param_and_grad)},
            outputs={"ParamOut": p})


class MomentumOptimizer(Optimizer):
    def __init__(self, learning_rate, momentum, use_nesterov=False,
                 regularization=None, name=None):
        super().__init__(learning_rate, regularization, name)
        self.type = "momentum"
        self._momentum = momentum
        self._use_nesterov = use_nesterov

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator("velocity", p)

    def _append_optimize_op(self, block, param_and_grad):
        p, g = param_and_grad
        vel = self._get_accumulator("velocity", p)
        return block.append_op(
            "momentum",
            inputs={"Param": p, "Grad": g, "Velocity": vel,
                    "LearningRate": self._create_param_lr(param_and_grad)},
            outputs={"ParamOut": p, "VelocityOut": vel},
            attrs={"mu": self._momentum, "use_nesterov": self._use_nesterov})

    def _eager_attrs(self):
        return {"mu": self._momentum, "use_nesterov": self._use_nesterov}

    def _eager_slots(self, p):
        import jax.numpy as jnp

        st = self._eager_state(p)
        if "velocity" not in st:
            st["velocity"] = jnp.zeros_like(p.value)
        return {"Velocity": st["velocity"]}

    def _eager_store(self, p, outs):
        self._eager_state(p)["velocity"] = outs["VelocityOut"][0]


class LarsMomentumOptimizer(Optimizer):
    def __init__(self, learning_rate, momentum, lars_coeff=0.001,
                 lars_weight_decay=0.0005, regularization=None, name=None):
        super().__init__(learning_rate, regularization, name)
        self.type = "lars_momentum"
        self._momentum = momentum
        self._lars_coeff = lars_coeff
        self._lars_weight_decay = lars_weight_decay

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator("velocity", p)

    def _append_optimize_op(self, block, param_and_grad):
        p, g = param_and_grad
        vel = self._get_accumulator("velocity", p)
        return block.append_op(
            "lars_momentum",
            inputs={"Param": p, "Grad": g, "Velocity": vel,
                    "LearningRate": self._create_param_lr(param_and_grad)},
            outputs={"ParamOut": p, "VelocityOut": vel},
            attrs={"mu": self._momentum, "lars_coeff": self._lars_coeff,
                   "lars_weight_decay": self._lars_weight_decay})


class AdagradOptimizer(Optimizer):
    def __init__(self, learning_rate, epsilon=1e-6, regularization=None,
                 name=None, initial_accumulator_value=0.0):
        super().__init__(learning_rate, regularization, name)
        self.type = "adagrad"
        self._epsilon = epsilon
        self._initial = initial_accumulator_value

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator("moment", p, fill_value=self._initial)

    def _append_optimize_op(self, block, param_and_grad):
        p, g = param_and_grad
        m = self._get_accumulator("moment", p)
        return block.append_op(
            "adagrad",
            inputs={"Param": p, "Grad": g, "Moment": m,
                    "LearningRate": self._create_param_lr(param_and_grad)},
            outputs={"ParamOut": p, "MomentOut": m},
            attrs={"epsilon": self._epsilon})


class DecayedAdagradOptimizer(Optimizer):
    """reference optimizer.py DecayedAdagrad: moment tracks a DECAYED average
    of grad^2 (decayed_adagrad_op.h), not adagrad's monotone sum."""

    def __init__(self, learning_rate, decay=0.95, epsilon=1e-6,
                 regularization=None, name=None):
        super().__init__(learning_rate, regularization, name)
        self.type = "decayed_adagrad"
        self._decay = decay
        self._epsilon = epsilon

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator("moment", p)

    def _append_optimize_op(self, block, param_and_grad):
        p, g = param_and_grad
        m = self._get_accumulator("moment", p)
        return block.append_op(
            "decayed_adagrad",
            inputs={"Param": p, "Grad": g, "Moment": m,
                    "LearningRate": self._create_param_lr(param_and_grad)},
            outputs={"ParamOut": p, "MomentOut": m},
            attrs={"decay": self._decay, "epsilon": self._epsilon})


class AdamOptimizer(Optimizer):
    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, regularization=None, name=None,
                 lazy_mode=False):
        super().__init__(learning_rate, regularization, name)
        self.type = "adam"
        self._beta1, self._beta2, self._epsilon = beta1, beta2, epsilon

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator("moment1", p)
            self._add_accumulator("moment2", p)
            self._add_accumulator("beta1_pow_acc", p, shape=[1],
                                  fill_value=self._beta1)
            self._add_accumulator("beta2_pow_acc", p, shape=[1],
                                  fill_value=self._beta2)

    def _append_optimize_op(self, block, param_and_grad):
        p, g = param_and_grad
        m1 = self._get_accumulator("moment1", p)
        m2 = self._get_accumulator("moment2", p)
        b1p = self._get_accumulator("beta1_pow_acc", p)
        b2p = self._get_accumulator("beta2_pow_acc", p)
        return block.append_op(
            self.type if self.type in ("adam", "lamb") else "adam",
            inputs={"Param": p, "Grad": g,
                    "LearningRate": self._create_param_lr(param_and_grad),
                    "Moment1": m1, "Moment2": m2,
                    "Beta1Pow": b1p, "Beta2Pow": b2p},
            outputs={"ParamOut": p, "Moment1Out": m1, "Moment2Out": m2,
                     "Beta1PowOut": b1p, "Beta2PowOut": b2p},
            attrs=self._op_attrs())

    def _op_attrs(self):
        return {"beta1": self._beta1, "beta2": self._beta2,
                "epsilon": self._epsilon}

    def _eager_attrs(self):
        return self._op_attrs()

    def _eager_slots(self, p):
        import jax.numpy as jnp

        st = self._eager_state(p)
        if "moment1" not in st:
            st["moment1"] = jnp.zeros_like(p.value)
            st["moment2"] = jnp.zeros_like(p.value)
            st["beta1_pow"] = jnp.asarray([self._beta1], p.value.dtype)
            st["beta2_pow"] = jnp.asarray([self._beta2], p.value.dtype)
        return {"Moment1": st["moment1"], "Moment2": st["moment2"],
                "Beta1Pow": st["beta1_pow"], "Beta2Pow": st["beta2_pow"]}

    def _eager_store(self, p, outs):
        st = self._eager_state(p)
        st["moment1"] = outs["Moment1Out"][0]
        st["moment2"] = outs["Moment2Out"][0]
        st["beta1_pow"] = outs["Beta1PowOut"][0]
        st["beta2_pow"] = outs["Beta2PowOut"][0]


class AdamWOptimizer(AdamOptimizer):
    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, weight_decay=0.01, regularization=None,
                 name=None):
        super().__init__(learning_rate, beta1, beta2, epsilon, regularization,
                         name)
        self.type = "adamw"
        self._weight_decay = weight_decay

    def _op_attrs(self):
        a = super()._op_attrs()
        a["weight_decay"] = self._weight_decay
        return a

    def _append_optimize_op(self, block, param_and_grad):
        p, g = param_and_grad
        m1 = self._get_accumulator("moment1", p)
        m2 = self._get_accumulator("moment2", p)
        b1p = self._get_accumulator("beta1_pow_acc", p)
        b2p = self._get_accumulator("beta2_pow_acc", p)
        return block.append_op(
            "adamw",
            inputs={"Param": p, "Grad": g,
                    "LearningRate": self._create_param_lr(param_and_grad),
                    "Moment1": m1, "Moment2": m2,
                    "Beta1Pow": b1p, "Beta2Pow": b2p},
            outputs={"ParamOut": p, "Moment1Out": m1, "Moment2Out": m2,
                     "Beta1PowOut": b1p, "Beta2PowOut": b2p},
            attrs=self._op_attrs())


class LambOptimizer(AdamOptimizer):
    def __init__(self, learning_rate=0.001, lamb_weight_decay=0.01,
                 beta1=0.9, beta2=0.999, epsilon=1e-6, regularization=None,
                 name=None):
        super().__init__(learning_rate, beta1, beta2, epsilon, regularization,
                         name)
        self.type = "lamb"
        self._weight_decay = lamb_weight_decay

    def _op_attrs(self):
        a = super()._op_attrs()
        a["weight_decay"] = self._weight_decay
        return a


class AdamaxOptimizer(Optimizer):
    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, regularization=None, name=None):
        super().__init__(learning_rate, regularization, name)
        self.type = "adamax"
        self._beta1, self._beta2, self._epsilon = beta1, beta2, epsilon

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator("moment", p)
            self._add_accumulator("inf_norm", p)
            self._add_accumulator("beta1_pow_acc", p, shape=[1],
                                  fill_value=self._beta1)

    def _append_optimize_op(self, block, param_and_grad):
        p, g = param_and_grad
        return block.append_op(
            "adamax",
            inputs={"Param": p, "Grad": g,
                    "LearningRate": self._create_param_lr(param_and_grad),
                    "Moment": self._get_accumulator("moment", p),
                    "InfNorm": self._get_accumulator("inf_norm", p),
                    "Beta1Pow": self._get_accumulator("beta1_pow_acc", p)},
            outputs={"ParamOut": p,
                     "MomentOut": self._get_accumulator("moment", p),
                     "InfNormOut": self._get_accumulator("inf_norm", p)},
            attrs={"beta1": self._beta1, "beta2": self._beta2,
                   "epsilon": self._epsilon})

    def _finish_update(self, block, params_grads):
        for p, _ in params_grads:
            b1p = self._get_accumulator("beta1_pow_acc", p)
            block.append_op("scale", inputs={"X": b1p},
                            outputs={"Out": b1p},
                            attrs={"scale": self._beta1})


class AdadeltaOptimizer(Optimizer):
    def __init__(self, learning_rate, epsilon=1e-6, rho=0.95,
                 regularization=None, name=None):
        super().__init__(learning_rate, regularization, name)
        self.type = "adadelta"
        self._epsilon, self._rho = epsilon, rho

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator("avg_squared_grad", p)
            self._add_accumulator("avg_squared_update", p)

    def _append_optimize_op(self, block, param_and_grad):
        p, g = param_and_grad
        return block.append_op(
            "adadelta",
            inputs={"Param": p, "Grad": g,
                    "AvgSquaredGrad": self._get_accumulator("avg_squared_grad", p),
                    "AvgSquaredUpdate": self._get_accumulator("avg_squared_update", p)},
            outputs={"ParamOut": p,
                     "AvgSquaredGradOut": self._get_accumulator("avg_squared_grad", p),
                     "AvgSquaredUpdateOut": self._get_accumulator("avg_squared_update", p)},
            attrs={"epsilon": self._epsilon, "rho": self._rho})


class RMSPropOptimizer(Optimizer):
    def __init__(self, learning_rate, rho=0.95, epsilon=1e-6, momentum=0.0,
                 centered=False, regularization=None, name=None):
        super().__init__(learning_rate, regularization, name)
        self.type = "rmsprop"
        self._rho, self._epsilon = rho, epsilon
        self._momentum, self._centered = momentum, centered

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator("mean_square", p)
            self._add_accumulator("mean_grad", p)
            self._add_accumulator("momentum", p)

    def _append_optimize_op(self, block, param_and_grad):
        p, g = param_and_grad
        return block.append_op(
            "rmsprop",
            inputs={"Param": p, "Grad": g,
                    "MeanSquare": self._get_accumulator("mean_square", p),
                    "MeanGrad": self._get_accumulator("mean_grad", p),
                    "Moment": self._get_accumulator("momentum", p),
                    "LearningRate": self._create_param_lr(param_and_grad)},
            outputs={"ParamOut": p,
                     "MomentOut": self._get_accumulator("momentum", p),
                     "MeanSquareOut": self._get_accumulator("mean_square", p),
                     "MeanGradOut": self._get_accumulator("mean_grad", p)},
            attrs={"decay": self._rho, "epsilon": self._epsilon,
                   "momentum": self._momentum, "centered": self._centered})


class FtrlOptimizer(Optimizer):
    def __init__(self, learning_rate, l1=0.0, l2=0.0, lr_power=-0.5,
                 regularization=None, name=None):
        super().__init__(learning_rate, regularization, name)
        self.type = "ftrl"
        self._l1, self._l2, self._lr_power = l1, l2, lr_power

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator("squared", p)
            self._add_accumulator("linear", p)

    def _append_optimize_op(self, block, param_and_grad):
        p, g = param_and_grad
        return block.append_op(
            "ftrl",
            inputs={"Param": p, "Grad": g,
                    "SquaredAccumulator": self._get_accumulator("squared", p),
                    "LinearAccumulator": self._get_accumulator("linear", p),
                    "LearningRate": self._create_param_lr(param_and_grad)},
            outputs={"ParamOut": p,
                     "SquaredAccumOut": self._get_accumulator("squared", p),
                     "LinearAccumOut": self._get_accumulator("linear", p)},
            attrs={"l1": self._l1, "l2": self._l2, "lr_power": self._lr_power})


# ---------------------------------------------------------------------------
# Meta optimizers / averaging (reference optimizer.py:2361-3367)
# ---------------------------------------------------------------------------

class ExponentialMovingAverage:
    """EMA of params (reference optimizer.py:2551). Maintains shadow vars
    updated by ops appended to the main program; apply()/restore() are
    context managers swapping params <-> shadow in the scope."""

    def __init__(self, decay=0.999, thres_steps=None, name=None):
        self._decay = decay
        self._name = name or ""
        self._ema_vars: Dict[str, Variable] = {}
        self._params: List[Parameter] = []
        program = default_main_program()
        block = program.global_block
        for p in program.all_parameters():
            if not p.trainable:
                continue
            self._params.append(p)
            ema_name = self._name + p.name + ".ema"
            ema = block.create_var(name=ema_name, shape=p.shape,
                                   dtype=p.dtype, persistable=True,
                                   stop_gradient=True)
            startup = default_startup_program().global_block
            startup.create_var(name=ema_name, shape=p.shape, dtype=p.dtype,
                               persistable=True)
            startup.append_op("fill_constant", outputs={"Out": ema_name},
                              attrs={"shape": list(p.shape),
                                     "dtype": p.dtype, "value": 0.0})
            self._ema_vars[p.name] = ema
            # ema = decay*ema + (1-decay)*param
            tmp = block.create_var(
                name=unique_name.generate(ema_name + ".tmp"),
                shape=p.shape, dtype=p.dtype, stop_gradient=True)
            block.append_op("scale", inputs={"X": ema}, outputs={"Out": tmp},
                            attrs={"scale": decay})
            tmp2 = block.create_var(
                name=unique_name.generate(ema_name + ".tmp"),
                shape=p.shape, dtype=p.dtype, stop_gradient=True)
            block.append_op("scale", inputs={"X": p}, outputs={"Out": tmp2},
                            attrs={"scale": 1.0 - decay})
            block.append_op("sum", inputs={"X": [tmp, tmp2]},
                            outputs={"Out": ema})

    def update(self):
        pass  # updates are appended into the main program at construction

    @contextlib.contextmanager
    def apply(self, executor, need_restore=True):
        from .executor import global_scope

        scope = global_scope()
        # validate BEFORE mutating so a missing shadow var can't leave the
        # scope half-swapped with no restore
        for p in self._params:
            if scope.find_var(self._ema_vars[p.name].name) is None:
                raise RuntimeError(
                    f"EMA shadow var '{self._ema_vars[p.name].name}' is not "
                    f"in the scope — construct ExponentialMovingAverage "
                    f"before training and run the startup+main programs that "
                    f"contain its ops")
        saved = {}
        for p in self._params:
            saved[p.name] = scope.find_var(p.name)
            scope.set_var(p.name, scope.find_var(self._ema_vars[p.name].name))
        try:
            yield
        finally:
            if need_restore:
                for name, v in saved.items():
                    scope.set_var(name, v)

    def restore(self, executor):
        pass


class ModelAverage(Optimizer):
    """reference optimizer.py:2361 — TRUE windowed average of params via the
    average_accumulates op (reference average_accumulates_op.h), not EMA.

    Must be constructed AFTER minimize() but BEFORE training runs: like the
    reference, construction appends accumulation ops to the main program, so
    the sums only exist if the accumulating program is what trains. apply()
    raises if the accumulators never ran."""

    def __init__(self, average_window_rate=0.15, min_average_window=10000,
                 max_average_window=10000, regularization=None, name=None):
        super().__init__(0.0, regularization, name)
        self._avg_window_rate = average_window_rate
        self._min_window = min_average_window
        self._max_window = max_average_window
        self._params: List[Parameter] = []
        self._acc_names: Dict[str, Dict[str, str]] = {}
        program = default_main_program()
        block = program.global_block
        startup = default_startup_program().global_block
        for p in program.all_parameters():
            if not p.trainable or getattr(p, "do_model_average", None) is False:
                continue
            self._params.append(p)
            names = {}
            for slot, shape, dtype in (
                    ("sum_1", p.shape, p.dtype), ("sum_2", p.shape, p.dtype),
                    ("sum_3", p.shape, p.dtype),
                    ("num_accumulates", (1,), "int64"),
                    ("old_num_accumulates", (1,), "int64"),
                    ("num_updates", (1,), "int64")):
                vname = unique_name.generate(f"{p.name}.{slot}")
                names[slot] = vname
                block.create_var(name=vname, shape=tuple(shape), dtype=dtype,
                                 persistable=True, stop_gradient=True)
                startup.create_var(name=vname, shape=tuple(shape), dtype=dtype,
                                   persistable=True)
                startup.append_op("fill_constant", outputs={"Out": vname},
                                  attrs={"shape": list(shape), "dtype": dtype,
                                         "value": 0.0})
            self._acc_names[p.name] = names
            block.append_op(
                "average_accumulates",
                inputs={"Param": p.name, "InSum1": names["sum_1"],
                        "InSum2": names["sum_2"], "InSum3": names["sum_3"],
                        "InNumAccumulates": names["num_accumulates"],
                        "InOldNumAccumulates": names["old_num_accumulates"],
                        "InNumUpdates": names["num_updates"]},
                outputs={"OutSum1": names["sum_1"], "OutSum2": names["sum_2"],
                         "OutSum3": names["sum_3"],
                         "OutNumAccumulates": names["num_accumulates"],
                         "OutOldNumAccumulates": names["old_num_accumulates"],
                         "OutNumUpdates": names["num_updates"]},
                attrs={"average_window": average_window_rate,
                       "min_average_window": min_average_window,
                       "max_average_window": max_average_window})

    def minimize(self, loss, **kw):
        raise RuntimeError("ModelAverage wraps a trained program; call apply()")

    @contextlib.contextmanager
    def apply(self, executor, need_restore=True):
        """Swap params to (sum_1+sum_2+sum_3)/(num+old_num) in the scope."""
        import numpy as np

        from .executor import global_scope

        scope = global_scope()
        # compute every average BEFORE mutating the scope so a missing or
        # empty accumulator can't leave params half-swapped with no restore
        averaged = {}
        for p in self._params:
            names = self._acc_names[p.name]
            s1 = scope.find_var(names["sum_1"])
            if s1 is None:
                raise RuntimeError(
                    f"ModelAverage accumulator '{names['sum_1']}' is not in "
                    f"the scope — the accumulating program never ran. "
                    f"Construct ModelAverage before training (after "
                    f"optimizer.minimize) and train the SAME program.")
            s2 = scope.find_var(names["sum_2"])
            s3 = scope.find_var(names["sum_3"])
            n = int(np.asarray(scope.find_var(names["num_accumulates"]))[0])
            old_n = int(np.asarray(
                scope.find_var(names["old_num_accumulates"]))[0])
            total = n + old_n
            if total == 0:
                raise RuntimeError(
                    "ModelAverage.apply: zero accumulated steps — train "
                    "before applying the average")
            averaged[p.name] = (
                np.asarray(s1) + np.asarray(s2) + np.asarray(s3)) / total
        saved = {}
        for p in self._params:
            saved[p.name] = scope.find_var(p.name)
            scope.set_var(p.name, averaged[p.name].astype(
                np.asarray(saved[p.name]).dtype))
        try:
            yield
        finally:
            if need_restore:
                for name, v in saved.items():
                    scope.set_var(name, v)

    def restore(self, executor):
        pass


class LookaheadOptimizer:
    """reference optimizer.py:3367: slow/fast weights. slow_k sync period."""

    def __init__(self, inner_optimizer, alpha=0.5, k=5):
        self.inner_optimizer = inner_optimizer
        self.alpha = alpha
        self.k = k

    def minimize(self, loss, startup_program=None):
        ops, pgs = self.inner_optimizer.minimize(
            loss, startup_program=startup_program)
        program = default_main_program()
        block = program.global_block
        startup = default_startup_program().global_block
        # step counter
        step_name = unique_name.generate("lookahead_step")
        block.create_var(name=step_name, shape=(1,), dtype="float32",
                         persistable=True, stop_gradient=True)
        startup.create_var(name=step_name, shape=(1,), dtype="float32",
                           persistable=True)
        startup.append_op("fill_constant", outputs={"Out": step_name},
                          attrs={"shape": [1], "dtype": "float32", "value": 0.0})
        block.append_op("increment", inputs={"X": step_name},
                        outputs={"Out": step_name}, attrs={"step": 1.0})
        for p, _ in pgs:
            slow_name = p.name + ".slow"
            block.create_var(name=slow_name, shape=p.shape, dtype=p.dtype,
                             persistable=True, stop_gradient=True)
            startup.create_var(name=slow_name, shape=p.shape, dtype=p.dtype,
                               persistable=True)
            # initialize slow = fast initial value: copy via assign after init
            startup.append_op("assign", inputs={"X": p.name},
                              outputs={"Out": slow_name})
            # every k steps: slow += alpha*(fast-slow); fast = slow.
            # branch-free gate: frac(step/k) == 0
            helper = LayerHelper("lookahead")
            inv = helper.create_variable_for_type_inference("float32", True)
            block.append_op("scale", inputs={"X": step_name},
                            outputs={"Out": inv}, attrs={"scale": 1.0 / self.k})
            flo = helper.create_variable_for_type_inference("float32", True)
            block.append_op("floor", inputs={"X": inv}, outputs={"Out": flo})
            frac = helper.create_variable_for_type_inference("float32", True)
            block.append_op("elementwise_sub", inputs={"X": inv, "Y": flo},
                            outputs={"Out": frac}, attrs={"axis": -1})
            # is_sync = 1 if frac == 0
            iszero = helper.create_variable_for_type_inference("bool", True)
            zero = helper.create_variable_for_type_inference("float32", True)
            block.append_op("fill_constant", outputs={"Out": zero},
                            attrs={"shape": [1], "dtype": "float32",
                                   "value": 0.0})
            block.append_op("equal", inputs={"X": frac, "Y": zero},
                            outputs={"Out": iszero})
            gate = helper.create_variable_for_type_inference("float32", True)
            block.append_op("cast", inputs={"X": iszero},
                            outputs={"Out": gate},
                            attrs={"in_dtype": "bool", "out_dtype": "float32"})
            # new_slow = slow + gate*alpha*(fast - slow)
            diff = helper.create_variable_for_type_inference(p.dtype, True)
            block.append_op("elementwise_sub", inputs={"X": p.name,
                                                       "Y": slow_name},
                            outputs={"Out": diff}, attrs={"axis": -1})
            sdiff = helper.create_variable_for_type_inference(p.dtype, True)
            block.append_op("scale", inputs={"X": diff}, outputs={"Out": sdiff},
                            attrs={"scale": self.alpha})
            gated = helper.create_variable_for_type_inference(p.dtype, True)
            block.append_op("elementwise_mul", inputs={"X": sdiff, "Y": gate},
                            outputs={"Out": gated}, attrs={"axis": 0})
            block.append_op("sum", inputs={"X": [slow_name, gated]},
                            outputs={"Out": slow_name})
            # new_fast = gate*slow + (1-gate)*fast
            #          = fast + gate*(slow - fast)
            diff2 = helper.create_variable_for_type_inference(p.dtype, True)
            block.append_op("elementwise_sub", inputs={"X": slow_name,
                                                       "Y": p.name},
                            outputs={"Out": diff2}, attrs={"axis": -1})
            gated2 = helper.create_variable_for_type_inference(p.dtype, True)
            block.append_op("elementwise_mul", inputs={"X": diff2, "Y": gate},
                            outputs={"Out": gated2}, attrs={"axis": 0})
            block.append_op("sum", inputs={"X": [p.name, gated2]},
                            outputs={"Out": p.name})
        return ops, pgs


class GradientMergeOptimizer:
    """Microbatched gradient accumulation (reference
    ir/multi_batch_merge_pass.cc: repeat fwd/bwd k times before one
    update): the forward+backward ops run under a lax.scan over
    num_microbatches slices of every feed, accumulating parameter
    gradients; the optimizer step then runs once on the average
    (executor.make_pipeline_step_fn). With a mean loss this is numerically
    the plain step on the full batch — it trades peak activation memory
    for steps."""

    def __init__(self, optimizer, num_microbatches=2, k_steps=None,
                 avg=True):
        self._optimizer = optimizer
        self._num_microbatches = int(k_steps or num_microbatches)
        self._avg = bool(avg)

    def minimize(self, loss, startup_program=None, parameter_list=None,
                 no_grad_set=None):
        result = self._optimizer.minimize(loss, startup_program,
                                          parameter_list, no_grad_set)
        program = loss.block.program
        _, params_grads = result
        program._pipeline_microbatches = self._num_microbatches
        program._grad_merge_avg = self._avg  # False: SUM like ref avg=False
        program._pipeline_param_grads = [(p.name, g.name)
                                         for p, g in params_grads]
        program._bump_version()
        return result


class PipelineOptimizer(GradientMergeOptimizer):
    """Reference optimizer.py:2781 PipelineOptimizer: cut the program into
    device-placed sections run by SectionWorker threads passing scopes
    through queues (trainer.h:110 PipelineTrainer, device_worker.h:267).

    TPU-native split of that job into its two halves:

    - the MICROBATCH SCHEDULE (this class, via GradientMergeOptimizer):
      fwd/bwd scan over microbatch slices with gradient accumulation —
      numerically identical to pipelining, minus inter-stage concurrency;
    - real STAGE PLACEMENT over a 'pp' mesh axis: author the repeated
      stage with ``layers.PipelineRegion`` — its [num_stages, ...]-stacked
      params shard one slice per pp rank and the `pipeline` op runs the
      GPipe schedule with lax.ppermute'd activations
      (ops/pipeline_op.py, parallel/pipeline.py).

    ``cut_list`` names the section-boundary vars of the reference API. A
    program whose repeated section is a PipelineRegion already carries its
    stage structure; for a plain cut-list program the cuts are recorded on
    the program (``_pipeline_cut_names``) and the schedule is gradient
    accumulation — placement of heterogeneous hand-cut sections has no
    faithful single-program GSPMD encoding."""

    def __init__(self, optimizer, cut_list=None, num_microbatches=2,
                 start_cpu_core_id=0):
        super().__init__(optimizer, num_microbatches=num_microbatches)
        self._cut_list = cut_list

    def minimize(self, loss, startup_program=None, parameter_list=None,
                 no_grad_set=None):
        result = super().minimize(loss, startup_program, parameter_list,
                                  no_grad_set)
        program = loss.block.program
        if self._cut_list:
            names = []
            for cut in self._cut_list:
                for v in (cut if isinstance(cut, (list, tuple)) else [cut]):
                    names.append(v if isinstance(v, str) else v.name)
            missing = [n for n in names
                       if not program.global_block.has_var(n)]
            if missing:
                raise ValueError(
                    f"PipelineOptimizer cut_list names unknown vars: "
                    f"{missing}")
            program._pipeline_cut_names = names
        return result


class RecomputeOptimizer:
    """Gradient checkpointing (reference optimizer.py:3074 RecomputeOptimizer,
    backward.py:555 _append_backward_ops_with_checkpoints_).

    Before the backward is appended, forward ops up to each user checkpoint
    collapse into ``recompute_segment`` ops lowered under jax.checkpoint —
    activations between checkpoints are never saved across the fwd/bwd gap;
    the backward rebuilds them from the checkpoint tensors (see
    ops/recompute.py for the trade against the reference's op-duplication)."""

    def __init__(self, optimizer):
        self._optimizer = optimizer
        self._checkpoints = None

    def _set_checkpoints(self, checkpoints):
        if not isinstance(checkpoints, (list, tuple)):
            raise TypeError("checkpoints must be a list of Variables/names")
        self._checkpoints = list(checkpoints)

    def backward(self, loss, startup_program=None, parameter_list=None,
                 no_grad_set=None, callbacks=None):
        from .ops.recompute import insert_recompute_segments

        if self._checkpoints:
            insert_recompute_segments(loss, self._checkpoints)
        return self._optimizer.backward(loss, startup_program,
                                        parameter_list, no_grad_set,
                                        callbacks)

    def apply_gradients(self, params_grads):
        return self._optimizer.apply_gradients(params_grads)

    def minimize(self, loss, startup_program=None, parameter_list=None,
                 no_grad_set=None):
        program = loss.block.program
        with program_guard(program, startup_program):
            params_grads = self.backward(loss, startup_program,
                                         parameter_list, no_grad_set)
            optimize_ops = self._optimizer.apply_gradients(params_grads)
        return optimize_ops, params_grads


class DGCMomentumOptimizer(Optimizer):
    """Deep Gradient Compression momentum — intentionally unsupported on
    TPU; this class IS the decision surface (the async-PS/GEO pattern).

    The reference (operators/optimizers/dgc_momentum_op / framework/details/
    sparse_all_reduce_op_handle.h:30) sparsifies each gradient to its top-k
    entries before all-reduce to save NETWORK bandwidth on commodity
    interconnects, trading exactness plus host-side encode/decode for fewer
    bytes on the wire. On a TPU pod the economics invert: dense all-reduce
    rides ICI at hundreds of GB/s with zero host involvement, while top-k
    selection + irregular gather/scatter are the expensive part — DGC is a
    pessimization, not an optimization, on this hardware. Momentum
    correction/clipping exist solely to patch DGC's convergence, so there
    is nothing worth keeping.

    Migration: plain ``Momentum`` (dense ICI all-reduce is cheap), or
    ``fleet.DistributedStrategy(use_local_sgd=True)`` when communication
    frequency — not volume — is the constraint (multi-host over DCN).
    """

    def __init__(self, learning_rate, momentum, rampup_begin_step,
                 rampup_step=1, sparsity=(0.999,), use_nesterov=False,
                 local_grad_clip_norm=None, num_trainers=None,
                 regularization=None, name=None):
        raise NotImplementedError(
            "DGCMomentumOptimizer is intentionally unsupported on TPU: "
            "top-k gradient sparsification saves network bytes at the cost "
            "of top-k + irregular scatter compute, which on ICI-connected "
            "chips is slower than the dense all-reduce it replaces. Use "
            "Momentum (dense collectives), or fleet.DistributedStrategy("
            "use_local_sgd=True) to cut communication FREQUENCY instead.")


# canonical short aliases (v2-style names)
SGD = SGDOptimizer
Momentum = MomentumOptimizer
Adagrad = AdagradOptimizer
Adam = AdamOptimizer
AdamW = AdamWOptimizer
Adamax = AdamaxOptimizer
DecayedAdagrad = DecayedAdagradOptimizer
Adadelta = AdadeltaOptimizer
RMSProp = RMSPropOptimizer
Ftrl = FtrlOptimizer
Lamb = LambOptimizer
LarsMomentum = LarsMomentumOptimizer
