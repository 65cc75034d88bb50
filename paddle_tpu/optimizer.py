"""Optimizers-as-ops (reference: python/paddle/fluid/optimizer.py:50-475).

``minimize`` = append_backward + append optimizer update ops with per-param
accumulators; the whole update is part of the compiled step function, so XLA
fuses it with the backward pass (the analog of the reference's fused
optimizer goal, SURVEY.md section 7 hard part 7).
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

from paddle_tpu import monitor as _monitor
from paddle_tpu import unique_name
from paddle_tpu.backward import append_backward
from paddle_tpu.framework import (
    Parameter,
    Variable,
    _normalize_slots,
    default_main_program,
    op_role_guard,
    program_guard,
)
from paddle_tpu.layer_helper import LayerHelper


class Optimizer:
    def __init__(self, learning_rate, regularization=None, name=None):
        self._lr_input = learning_rate
        self._lr_var: Optional[Variable] = None
        self.regularization = regularization
        self._name = name
        # {param_name: {acc_name: Variable}}
        self._accumulators: Dict[str, Dict[str, Variable]] = {}
        self.helper: Optional[LayerHelper] = None

    # --- learning rate ---

    def _create_lr_var(self):
        if isinstance(self._lr_input, Variable):
            self._lr_var = self._lr_input
            return
        from paddle_tpu.layers import tensor

        self._lr_var = tensor.create_global_var(
            shape=[1],
            value=float(self._lr_input),
            dtype="float32",
            persistable=True,
            name=unique_name.generate("learning_rate"),
        )

    @property
    def learning_rate(self):
        return self._lr_var

    def _param_lr(self, param: Parameter):
        mult = (param.optimize_attr or {}).get("learning_rate", 1.0)
        if mult == 1.0:
            return self._lr_var
        from paddle_tpu.layers import nn

        return nn.scale(self._lr_var, scale=float(mult))

    # --- accumulators ---

    def _add_accumulator(self, name, param, fill_value=0.0, shape=None, dtype=None):
        from paddle_tpu.layers import tensor

        shape = list(shape if shape is not None else param.shape)
        var = tensor.create_global_var(
            shape=shape,
            value=fill_value,
            dtype=dtype or param.dtype,
            persistable=True,
            name=unique_name.generate(f"{param.name}_{name}"),
        )
        self._accumulators.setdefault(name, {})[param.name] = var
        return var

    def _get_accumulator(self, name, param):
        return self._accumulators[name][param.name]

    def slot_descriptor(self) -> Dict[str, Dict[str, str]]:
        """{slot var name -> {"param": owning param, "slot": kind}} for
        every accumulator this optimizer created (moments, velocities,
        beta pows, ...), plus the auto-created learning-rate var.

        This is the identity that survives a rebuild: slot var NAMES
        come from ``unique_name.generate`` and drift whenever a program
        is rebuilt differently (per-stage pipeline programs, a
        differently-ordered build, a warm process's shifted counters),
        but (param, kind) does not. The checkpoint manifest records the
        descriptor per entry (``save_checkpoint(slots=)``), and
        ``checkpoint.reshard_optimizer_state`` re-keys saved slot state
        onto the RESTORING program's names through it."""
        out: Dict[str, Dict[str, str]] = {}
        for kind, d in self._accumulators.items():
            for pname, var in d.items():
                out[var.name] = {"param": pname, "slot": kind}
        if self._lr_var is not None and \
                not isinstance(self._lr_input, Variable):
            # only the var WE created (a user LR-schedule Variable
            # belongs to the program, not the optimizer state)
            out[self._lr_var.name] = {"param": "", "slot": "learning_rate"}
        return out

    # --- hooks for subclasses ---

    def _create_accumulators(self, block, parameters):
        pass

    def _append_optimize_op(self, block, param_and_grad):
        raise NotImplementedError

    def _finish_update(self, block, parameters_and_grads):
        pass

    # --- public API (reference: optimizer.py:352-475) ---

    def backward(self, loss, startup_program=None, parameter_list=None,
                 no_grad_set=None, callbacks=None):
        return append_backward(loss, parameter_list, no_grad_set, callbacks)

    def apply_gradients(self, params_grads):
        """Returns the optimizer update Operators appended to the block."""
        # clip, regularizer, learning-rate and update ops: the step's
        # optimizer phase
        with _monitor.span("optimizer.apply_gradients"), \
                op_role_guard(default_main_program(), "opt"):
            return self._apply_gradients(params_grads)

    def _apply_gradients(self, params_grads):
        prog = default_main_program()
        block = prog.global_block()
        self._create_lr_var()

        from paddle_tpu import clip as clip_mod
        from paddle_tpu import regularizer as reg_mod

        # Row-sparse (SelectedRows-style) grads bypass clip/regularization
        # and dispatch to the optimizer's sparse op. Silently skipping
        # user-REQUESTED decay/clipping would also skew a global-norm clip
        # (computed over dense grads only), so that combination errors out
        # instead.
        sparse = [(p, g) for p, g in params_grads
                  if getattr(g, "is_selected_rows", False)]
        dense = [(p, g) for p, g in params_grads
                 if not getattr(g, "is_selected_rows", False)]
        for p, _ in sparse:
            if self.regularization is not None or \
                    getattr(p, "regularizer", None) is not None:
                raise NotImplementedError(
                    f"regularization on row-sparse parameter '{p.name}' is "
                    f"not supported; use is_sparse=False for this embedding"
                )
            if clip_mod.clip_applies_to(p.name):
                raise NotImplementedError(
                    f"gradient clipping with row-sparse parameter "
                    f"'{p.name}' is not supported (a global-norm clip over "
                    f"dense grads only would under-clip); use "
                    f"is_sparse=False"
                )
        pre_clip_dense = list(dense)
        dense = clip_mod.append_gradient_clip_ops(dense)
        dense = reg_mod.append_regularization_ops(
            dense, self.regularization
        )
        params_grads = dense + sparse
        self._maybe_instrument_grad_norm(prog, pre_clip_dense)

        self._create_accumulators(block, [p for p, _ in params_grads])
        n_before = len(block.ops)
        for pg in params_grads:
            if getattr(pg[1], "is_selected_rows", False):
                self._append_sparse_optimize_op(block, pg)
            elif not self._fold_into_experts_grad(block, pg):
                self._append_optimize_op(block, pg)
        self._finish_update(block, params_grads)
        # what the step moves without a gradient (Program._step_updates)
        for spec in prog._step_updates:
            block.append_op(**spec)
        prog._step_updates = []
        return block.ops[n_before:]

    def _fold_into_experts_grad(self, block, param_and_grad) -> bool:
        """True where the parameter's update was made part of the op
        that computes its gradient and no update op is to be appended
        (AdamOptimizer, for the matrices of a top-k MoE layer's
        experts)."""
        return False

    def _append_sparse_optimize_op(self, block, param_and_grad):
        raise NotImplementedError(
            f"{type(self).__name__} has no row-sparse update op; use "
            f"SGD/Momentum/Adam for is_sparse=True embeddings, or build "
            f"the embedding with is_sparse=False"
        )

    @staticmethod
    def _maybe_instrument_grad_norm(prog, dense):
        """Numerics-plane grad-norm instrument: with the ``numerics``
        flag on at graph-BUILD time (and no GradientClipByGlobalNorm
        already exporting the norm), append a global-norm reduction over
        the PRE-clip, pre-decay dense gradients — the same semantics the
        clip path exports, so ``pt_grad_global_norm`` always means the
        raw-gradient norm — and register it as an aux var. Flag-gated at
        build so default-off programs carry zero extra ops; unused the
        ops are DCE'd by XLA anyway."""
        from paddle_tpu import flags as _flags

        if not _flags.get_flag("numerics"):
            return
        from paddle_tpu import numerics

        if any(k == "grad_global_norm"
               for k, _ in getattr(prog, "_numerics_aux", ())):
            return
        grads = [g for _, g in dense if g is not None]
        if not grads:
            return
        from paddle_tpu.layer_helper import LayerHelper
        from paddle_tpu.layers import nn

        helper = LayerHelper("grad_norm_instrument")
        sq = []
        for g in grads:
            out = helper.create_variable_for_type_inference(dtype=g.dtype)
            helper.append_op("squared_l2_norm", inputs={"X": g},
                             outputs={"Out": out})
            sq.append(out)
        norm = nn.sqrt(nn.sums(sq))
        numerics.register_aux(prog, "grad_global_norm", norm.name)

    def minimize(self, loss, startup_program=None, parameter_list=None,
                 no_grad_set=None):
        from paddle_tpu.dygraph import base as dy_base

        if dy_base._in_dygraph_mode():
            return self._dygraph_minimize(loss, parameter_list)
        params_grads = self.backward(
            loss, startup_program, parameter_list, no_grad_set
        )
        opt_ops = self.apply_gradients(params_grads)
        return opt_ops, params_grads

    # --- dygraph (eager) path ---
    #
    # The eager twin of apply_gradients (reference: optimizer.py dygraph
    # branch of backward()/minimize()): the per-class _append_optimize_op
    # logic is reused verbatim by tracing it once into a throwaway Program
    # whose vars mirror the eager parameters by name, then jitting one
    # function (params, grads, state) -> (params', state') over the traced
    # op list. Accumulator state lives on the optimizer as jax arrays.

    def _dygraph_build(self, params):
        import jax
        import numpy as np

        from paddle_tpu.core.interp import exec_ops
        from paddle_tpu.framework import Program

        if isinstance(self._lr_input, Variable):
            raise TypeError(
                "dygraph minimize needs a float learning rate (static LR "
                "schedule variables belong to a Program)"
            )
        # Carry accumulator state (moments, beta pows, ...) across rebuilds
        # triggered by a changed trainable-parameter set: state is keyed by
        # (accumulator kind, param name), which survives var renaming.
        old_acc = {}
        if getattr(self, "_dy_state", None) is not None:
            for kind, d in self._accumulators.items():
                for pname, var in d.items():
                    if var.name in self._dy_state:
                        old_acc[(kind, pname)] = self._dy_state[var.name]

        prog, startup = Program(), Program()
        with program_guard(prog, startup):
            block = prog.global_block()
            fake_pgs = []
            for p in params:
                dtype = str(np.dtype(p.dtype))
                fp = block.create_parameter(
                    p.name,
                    list(p.shape),
                    dtype,
                    optimize_attr=getattr(
                        p, "optimize_attr", {"learning_rate": 1.0}
                    ),
                    regularizer=getattr(p, "regularizer", None),
                )
                g = block.create_var(
                    name=fp.grad_name, shape=list(p.shape), dtype=dtype
                )
                fake_pgs.append((fp, g))
            opt_ops = self.apply_gradients(fake_pgs)
        del opt_ops  # the full main-block op list includes clip/reg ops
        update_ops = list(prog.global_block().ops)
        state0 = exec_ops(
            list(startup.global_block().ops), {}, key=None, amp=False
        )
        for kind, d in self._accumulators.items():
            for pname, var in d.items():
                if (kind, pname) in old_acc and var.name in state0:
                    state0[var.name] = old_acc[(kind, pname)]
        state_names = sorted(state0)
        param_names = [p.name for p in params]

        def step(state, param_vals, grad_vals):
            env = dict(state)
            for n, v, g in zip(param_names, param_vals, grad_vals):
                env[n] = v
                env[n + "@GRAD"] = g
            exec_ops(update_ops, env, key=None, amp=False)
            return (
                [env[n] for n in param_names],
                {n: env[n] for n in state_names},
            )

        self._dy_state = {n: state0[n] for n in state_names}
        self._dy_step = jax.jit(step)
        self._dy_param_names = param_names

    def _dygraph_minimize(self, loss, parameter_list):
        if not parameter_list:
            raise ValueError(
                "minimize() in dygraph mode requires parameter_list "
                "(e.g. model.parameters())"
            )
        # Only parameters reached by this step's backward get updated —
        # matching the static path, where apply_gradients sees exactly the
        # params on the loss's op path (untouched params must not drift
        # from regularization/moment updates).
        params = [
            p
            for p in parameter_list
            if not p.stop_gradient and p._grad is not None
        ]
        if not params:
            # The reference's eager contract: the user calls
            # loss.backward() first, then minimize() applies the collected
            # gradients. Auto-running backward here would silently reuse
            # stale gradients on later iterations.
            raise RuntimeError(
                "minimize() in dygraph mode found no gradients; call "
                "loss.backward() before minimize(), and "
                "clear_gradients() after each step"
            )
        if getattr(self, "_dy_step", None) is None or [
            p.name for p in params
        ] != self._dy_param_names:
            self._dygraph_build(params)
        grads = [p._grad for p in params]
        new_vals, self._dy_state = self._dy_step(
            self._dy_state, [p._value for p in params], grads
        )
        for p, v in zip(params, new_vals):
            p._value = v
        return [], [(p, p._grad) for p in params]


class SGDOptimizer(Optimizer):
    def __init__(self, learning_rate, regularization=None, name=None):
        super().__init__(learning_rate, regularization, name)

    def _append_optimize_op(self, block, param_and_grad):
        p, g = param_and_grad
        block.append_op(
            "sgd",
            inputs={"Param": p, "Grad": g, "LearningRate": self._param_lr(p)},
            outputs={"ParamOut": p.name},
        )

    def _append_sparse_optimize_op(self, block, param_and_grad):
        p, g = param_and_grad
        block.append_op(
            "sgd_sparse",
            inputs={"Param": p, "Rows": g.sparse_rows_name,
                    "Values": g.sparse_values_name,
                    "LearningRate": self._param_lr(p)},
            outputs={"ParamOut": p.name},
        )


class MomentumOptimizer(Optimizer):
    def __init__(self, learning_rate, momentum, use_nesterov=False,
                 regularization=None, name=None):
        super().__init__(learning_rate, regularization, name)
        self._momentum = momentum
        self._use_nesterov = use_nesterov

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator("velocity", p)

    def _append_optimize_op(self, block, param_and_grad):
        p, g = param_and_grad
        v = self._get_accumulator("velocity", p)
        block.append_op(
            "momentum",
            inputs={"Param": p, "Grad": g, "Velocity": v,
                    "LearningRate": self._param_lr(p)},
            outputs={"ParamOut": p.name, "VelocityOut": v.name},
            attrs={"mu": self._momentum, "use_nesterov": self._use_nesterov},
        )

    def _append_sparse_optimize_op(self, block, param_and_grad):
        p, g = param_and_grad
        v = self._get_accumulator("velocity", p)
        block.append_op(
            "momentum_sparse",
            inputs={"Param": p, "Rows": g.sparse_rows_name,
                    "Values": g.sparse_values_name, "Velocity": v,
                    "LearningRate": self._param_lr(p)},
            outputs={"ParamOut": p.name, "VelocityOut": v.name},
            attrs={"mu": self._momentum, "use_nesterov": self._use_nesterov},
        )


class DGCMomentumOptimizer(MomentumOptimizer):
    """Momentum with Deep Gradient Compression (reference:
    optimizer.py:696 DGCMomentumOptimizer; paper arXiv:1712.01887).
    Gradients are momentum-corrected into residual accumulators, only
    the top-k entries are exchanged each step (allgather of
    (index, value) pairs over the data/slice axis — see
    parallel/dgc.py for the TPU collective design and the static-k
    divergence note), and the rest accumulate locally until large
    enough to send. Sparsity ramps per ``sparsity``/``rampup_step``
    after ``rampup_begin_step``; before that the update is exactly
    dense momentum.

    Reference parity notes: parameters under 16384 elements or with
    non-fp32 dtype stay on the dense momentum path (the reference's
    _append_dgc_ops gate); ``local_grad_clip_norm`` clips the
    pre-compression gradient to ``local_grad_clip_norm /
    num_trainers**2`` past rampup (dgc_clip_by_norm_op.h). Static
    graph only, like the reference."""

    _DGC_MIN_NUMEL = 16384

    def __init__(self, learning_rate, momentum, rampup_begin_step,
                 rampup_step=1, sparsity=(0.999,), use_nesterov=False,
                 local_grad_clip_norm=None, num_trainers=None,
                 regularization=None, name=None):
        super().__init__(learning_rate, momentum, use_nesterov,
                         regularization, name)
        self._sparsity = list(sparsity)
        self._rampup_begin_step = float(rampup_begin_step)
        self._rampup_step = float(rampup_step)
        self._clip_norm = None
        if local_grad_clip_norm is not None:
            if not isinstance(num_trainers, int) or num_trainers <= 0:
                raise ValueError(
                    "local_grad_clip_norm needs num_trainers (the world "
                    "size the clip is scaled by)")
            self._clip_norm = float(local_grad_clip_norm) / (
                num_trainers * num_trainers)
        self._step_var = None

    def _dgc_eligible(self, param) -> bool:
        numel = 1
        for d in param.shape or ():
            numel *= int(d)
        return (numel >= self._DGC_MIN_NUMEL
                and str(param.dtype) in ("float32", "FP32"))

    def _create_accumulators(self, block, parameters):
        from paddle_tpu.layers import tensor

        super()._create_accumulators(block, parameters)
        for p in parameters:
            if self._dgc_eligible(p):
                self._add_accumulator("dgc_u", p)
                self._add_accumulator("dgc_v", p)
        if self._step_var is None:
            # the reference's kDGCCounterName global counter: starts at
            # -1, a prepended increment makes it 0 on the first step
            self._step_var = tensor.create_global_var(
                shape=[1], value=-1.0, dtype="float32", persistable=True,
                name=unique_name.generate("dgc_counter"))
            block._prepend_op(
                "increment", inputs={"X": [self._step_var.name]},
                outputs={"Out": [self._step_var.name]},
                attrs={"step": 1.0})

    def _append_optimize_op(self, block, param_and_grad):
        p, g = param_and_grad
        if not self._dgc_eligible(p):
            return super()._append_optimize_op(block, param_and_grad)
        v = self._get_accumulator("velocity", p)
        u_acc = self._get_accumulator("dgc_u", p)
        v_acc = self._get_accumulator("dgc_v", p)
        attrs = {"mu": self._momentum,
                 "use_nesterov": self._use_nesterov,
                 "sparsity": list(self._sparsity),
                 "rampup_begin_step": self._rampup_begin_step,
                 "rampup_step": self._rampup_step}
        if self._clip_norm is not None:
            attrs["local_grad_clip_norm"] = self._clip_norm
        block.append_op(
            "dgc_momentum",
            inputs={"Param": p, "Grad": g, "Velocity": v, "U": u_acc,
                    "V": v_acc, "LearningRate": self._param_lr(p),
                    "CurrentStep": self._step_var},
            outputs={"ParamOut": p.name, "VelocityOut": v.name,
                     "UOut": u_acc.name, "VOut": v_acc.name},
            attrs=attrs,
        )

    def _dygraph_build(self, params):
        raise NotImplementedError(
            "DGCMomentumOptimizer is static-graph only (as in the "
            "reference); use MomentumOptimizer in dygraph mode")


class LarsMomentumOptimizer(Optimizer):
    def __init__(self, learning_rate, momentum=0.9, lars_coeff=0.001,
                 lars_weight_decay=0.0005, regularization=None, name=None):
        super().__init__(learning_rate, regularization, name)
        self._momentum = momentum
        self._lars_coeff = lars_coeff
        self._lars_weight_decay = lars_weight_decay

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator("velocity", p)

    def _append_optimize_op(self, block, param_and_grad):
        p, g = param_and_grad
        v = self._get_accumulator("velocity", p)
        block.append_op(
            "lars_momentum",
            inputs={"Param": p, "Grad": g, "Velocity": v,
                    "LearningRate": self._param_lr(p)},
            outputs={"ParamOut": p.name, "VelocityOut": v.name},
            attrs={"mu": self._momentum, "lars_coeff": self._lars_coeff,
                   "lars_weight_decay": self._lars_weight_decay},
        )


class AdamOptimizer(Optimizer):
    _op_type = "adam"

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, regularization=None, name=None, lazy_mode=False):
        super().__init__(learning_rate, regularization, name)
        self._beta1, self._beta2, self._epsilon = beta1, beta2, epsilon

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator("moment1", p)
            self._add_accumulator("moment2", p)
            self._add_accumulator("beta1_pow", p, fill_value=1.0, shape=[1])
            self._add_accumulator("beta2_pow", p, fill_value=1.0, shape=[1])

    def _extra_attrs(self):
        return {}

    def _update_op(self, p, g):
        """(inputs, outputs, attrs) of the parameter's update op."""
        m1 = self._get_accumulator("moment1", p)
        m2 = self._get_accumulator("moment2", p)
        b1p = self._get_accumulator("beta1_pow", p)
        b2p = self._get_accumulator("beta2_pow", p)
        return ({"Param": p, "Grad": g, "Moment1": m1, "Moment2": m2,
                 "Beta1Pow": b1p, "Beta2Pow": b2p,
                 "LearningRate": self._param_lr(p)},
                {"ParamOut": p.name, "Moment1Out": m1.name,
                 "Moment2Out": m2.name, "Beta1PowOut": b1p.name,
                 "Beta2PowOut": b2p.name},
                {"beta1": self._beta1, "beta2": self._beta2,
                 "epsilon": self._epsilon, **self._extra_attrs()})

    def _append_optimize_op(self, block, param_and_grad):
        inputs, outputs, attrs = self._update_op(*param_and_grad)
        block.append_op(self._op_type, inputs=inputs, outputs=outputs,
                        attrs=attrs)

    def _fold_into_experts_grad(self, block, param_and_grad) -> bool:
        """An expert matrix's Adam is taken by the op that makes its
        gradient (``moe_experts_grad``, ops/moe_ops.py: inside the
        weight-gradient kernel where the call has one, so the gradient
        never goes to memory) where the program's graph allows it: the
        gradient is written by that op as the gradient of this very
        matrix and read by nothing (no clip, regulariser, sum of partial
        gradients or norm has taken it: each of those hands on a
        variable of its own), the learning rate is the optimizer's own
        variable, and between that op and the end of the block nothing
        reads or writes the weight or its state, so taking the step
        there and at the end are one program. The op then takes the
        ``adam`` op's inputs and writes its outputs, under the same
        variable names, one entry a matrix (attr ``adam_slots``), and
        the gradient leaves its outputs."""
        p, g = param_and_grad
        if self._op_type not in ("adam", "adamw") or \
                (p.optimize_attr or {}).get("learning_rate", 1.0) != 1.0:
            return False
        at = next((i for i, op in enumerate(block.ops)
                   if op.type == "moe_experts_grad"
                   and g.name in op.output_arg_names), None)
        if at is None:
            return False
        op = block.ops[at]
        slot = next((s for s in ("WGate", "WUp", "WDown")
                     if op.output("GRAD::" + s) == [g.name]
                     and op.input(s) == [p.name]), None)
        inputs, outputs, attrs = self._update_op(p, g)
        attrs = {"adam_op": self._op_type, **attrs}
        state = {v.name for k, v in inputs.items() if k != "Grad"}
        lr = inputs["LearningRate"].name
        if slot is None or any(
                op.attrs.get(k, v) != v for k, v in attrs.items()):
            return False
        for b in block.program.blocks:
            for i, other in enumerate(b.ops):
                reads = set(other.input_arg_names)
                writes = set(other.output_arg_names)
                behind = b is not block or i > at
                if g.name in reads or (g.name in writes and other is not op) \
                        or (behind and ((reads | writes) & (state - {lr})
                                        or lr in writes)):
                    return False
        # (and what apply_gradients appends behind the update ops)
        for spec in block.program._step_updates:
            for slots in (spec.get("inputs"), spec.get("outputs")):
                if state & {n for names in _normalize_slots(slots).values()
                            for n in names}:
                    return False
        del op.outputs["GRAD::" + slot]
        op.attrs.update(attrs)
        op.attrs["adam_slots"] = [*op.attrs.get("adam_slots", ()), slot]
        op.attrs["adam_grads"] = [*op.attrs.get("adam_grads", ()), g.name]
        for k, v in inputs.items():
            if k != "Grad":
                op.inputs.setdefault(k, []).append(v.name)
        for k, name in outputs.items():
            op.outputs.setdefault(k, []).append(name)
        block.program._bump_version()
        return True

    def _append_sparse_optimize_op(self, block, param_and_grad):
        # Lazy Adam on the touched rows (reference: adam_op.h lazy_mode)
        p, g = param_and_grad
        m1 = self._get_accumulator("moment1", p)
        m2 = self._get_accumulator("moment2", p)
        b1p = self._get_accumulator("beta1_pow", p)
        b2p = self._get_accumulator("beta2_pow", p)
        block.append_op(
            "adam_sparse",
            inputs={"Param": p, "Rows": g.sparse_rows_name,
                    "Values": g.sparse_values_name, "Moment1": m1,
                    "Moment2": m2, "Beta1Pow": b1p, "Beta2Pow": b2p,
                    "LearningRate": self._param_lr(p)},
            outputs={"ParamOut": p.name, "Moment1Out": m1.name,
                     "Moment2Out": m2.name, "Beta1PowOut": b1p.name,
                     "Beta2PowOut": b2p.name},
            attrs={"beta1": self._beta1, "beta2": self._beta2,
                   "epsilon": self._epsilon},
        )


class AdamWOptimizer(AdamOptimizer):
    _op_type = "adamw"

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, weight_decay=0.01, regularization=None,
                 name=None):
        super().__init__(learning_rate, beta1, beta2, epsilon, regularization, name)
        self._weight_decay = weight_decay

    def _extra_attrs(self):
        return {"weight_decay": self._weight_decay}

    def _append_sparse_optimize_op(self, block, param_and_grad):
        # inheriting adam_sparse would silently drop the decoupled decay
        return Optimizer._append_sparse_optimize_op(
            self, block, param_and_grad)


class LambOptimizer(AdamOptimizer):
    _op_type = "lamb"

    def __init__(self, learning_rate=0.001, lamb_weight_decay=0.01,
                 beta1=0.9, beta2=0.999, epsilon=1e-6, regularization=None,
                 name=None):
        super().__init__(learning_rate, beta1, beta2, epsilon, regularization, name)
        self._weight_decay = lamb_weight_decay

    def _extra_attrs(self):
        return {"weight_decay": self._weight_decay}

    def _append_sparse_optimize_op(self, block, param_and_grad):
        # inheriting adam_sparse would silently drop the trust-ratio rule
        return Optimizer._append_sparse_optimize_op(
            self, block, param_and_grad)


class AdagradOptimizer(Optimizer):
    def __init__(self, learning_rate, epsilon=1e-6, regularization=None,
                 name=None, initial_accumulator_value=0.0):
        super().__init__(learning_rate, regularization, name)
        self._epsilon = epsilon
        self._initial = initial_accumulator_value

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator("moment", p, fill_value=self._initial)

    def _append_optimize_op(self, block, param_and_grad):
        p, g = param_and_grad
        m = self._get_accumulator("moment", p)
        block.append_op(
            "adagrad",
            inputs={"Param": p, "Grad": g, "Moment": m,
                    "LearningRate": self._param_lr(p)},
            outputs={"ParamOut": p.name, "MomentOut": m.name},
            attrs={"epsilon": self._epsilon},
        )


class DecayedAdagradOptimizer(Optimizer):
    def __init__(self, learning_rate, decay=0.95, epsilon=1e-6,
                 regularization=None, name=None):
        super().__init__(learning_rate, regularization, name)
        self._decay = decay
        self._epsilon = epsilon

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator("moment", p)

    def _append_optimize_op(self, block, param_and_grad):
        p, g = param_and_grad
        m = self._get_accumulator("moment", p)
        block.append_op(
            "decayed_adagrad",
            inputs={"Param": p, "Grad": g, "Moment": m,
                    "LearningRate": self._param_lr(p)},
            outputs={"ParamOut": p.name, "MomentOut": m.name},
            attrs={"decay": self._decay, "epsilon": self._epsilon},
        )


class RMSPropOptimizer(Optimizer):
    def __init__(self, learning_rate, rho=0.95, epsilon=1e-6, momentum=0.0,
                 centered=False, regularization=None, name=None):
        super().__init__(learning_rate, regularization, name)
        self._rho, self._epsilon = rho, epsilon
        self._momentum, self._centered = momentum, centered

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator("mean_square", p)
            self._add_accumulator("moment", p)
            if self._centered:
                self._add_accumulator("mean_grad", p)

    def _append_optimize_op(self, block, param_and_grad):
        p, g = param_and_grad
        ms = self._get_accumulator("mean_square", p)
        mom = self._get_accumulator("moment", p)
        inputs = {"Param": p, "Grad": g, "MeanSquare": ms, "Moment": mom,
                  "LearningRate": self._param_lr(p)}
        outputs = {"ParamOut": p.name, "MeanSquareOut": ms.name,
                   "MomentOut": mom.name}
        if self._centered:
            mg = self._get_accumulator("mean_grad", p)
            inputs["MeanGrad"] = mg
            outputs["MeanGradOut"] = mg.name
        block.append_op(
            "rmsprop", inputs=inputs, outputs=outputs,
            attrs={"decay": self._rho, "epsilon": self._epsilon,
                   "momentum": self._momentum, "centered": self._centered},
        )


class FtrlOptimizer(Optimizer):
    def __init__(self, learning_rate, l1=0.0, l2=0.0, lr_power=-0.5,
                 regularization=None, name=None):
        super().__init__(learning_rate, regularization, name)
        self._l1, self._l2, self._lr_power = l1, l2, lr_power

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator("squared", p)
            self._add_accumulator("linear", p)

    def _append_optimize_op(self, block, param_and_grad):
        p, g = param_and_grad
        sq = self._get_accumulator("squared", p)
        lin = self._get_accumulator("linear", p)
        block.append_op(
            "ftrl",
            inputs={"Param": p, "Grad": g, "SquaredAccumulator": sq,
                    "LinearAccumulator": lin,
                    "LearningRate": self._param_lr(p)},
            outputs={"ParamOut": p.name, "SquaredAccumOut": sq.name,
                     "LinearAccumOut": lin.name},
            attrs={"l1": self._l1, "l2": self._l2, "lr_power": self._lr_power},
        )


class AdamaxOptimizer(Optimizer):
    """Adamax (reference: optimizer.py:41 'Adamax', AdamaxOptimizer)."""

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, regularization=None, name=None):
        super().__init__(learning_rate, regularization, name)
        self._beta1, self._beta2, self._epsilon = beta1, beta2, epsilon

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator("moment", p)
            self._add_accumulator("inf_norm", p)
            self._add_accumulator("beta1_pow", p, fill_value=1.0, shape=[1])

    def _append_optimize_op(self, block, param_and_grad):
        p, g = param_and_grad
        m = self._get_accumulator("moment", p)
        u = self._get_accumulator("inf_norm", p)
        b1p = self._get_accumulator("beta1_pow", p)
        block.append_op(
            "adamax",
            inputs={"Param": p, "Grad": g, "Moment": m, "InfNorm": u,
                    "Beta1Pow": b1p, "LearningRate": self._param_lr(p)},
            outputs={"ParamOut": p.name, "MomentOut": m.name,
                     "InfNormOut": u.name, "Beta1PowOut": b1p.name},
            attrs={"beta1": self._beta1, "beta2": self._beta2,
                   "epsilon": self._epsilon},
        )


class AdadeltaOptimizer(Optimizer):
    """Adadelta (reference: optimizer.py:41 'Adadelta'); the op applies
    the classic learning-rate-free rule, matching the reference kernel."""

    def __init__(self, learning_rate=1.0, epsilon=1e-6, rho=0.95,
                 regularization=None, name=None):
        super().__init__(learning_rate, regularization, name)
        self._epsilon, self._rho = epsilon, rho

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator("avg_squared_grad", p)
            self._add_accumulator("avg_squared_update", p)

    def _append_optimize_op(self, block, param_and_grad):
        p, g = param_and_grad
        eg2 = self._get_accumulator("avg_squared_grad", p)
        edx2 = self._get_accumulator("avg_squared_update", p)
        block.append_op(
            "adadelta",
            inputs={"Param": p, "Grad": g, "AvgSquaredGrad": eg2,
                    "AvgSquaredUpdate": edx2,
                    "LearningRate": self._param_lr(p)},
            outputs={"ParamOut": p.name, "AvgSquaredGradOut": eg2.name,
                     "AvgSquaredUpdateOut": edx2.name},
            attrs={"rho": self._rho, "epsilon": self._epsilon},
        )


# Short aliases matching the reference's public names.
SGD = SGDOptimizer
Momentum = MomentumOptimizer
DGCMomentum = DGCMomentumOptimizer
Adam = AdamOptimizer
AdamW = AdamWOptimizer
Adagrad = AdagradOptimizer
Adamax = AdamaxOptimizer
Adadelta = AdadeltaOptimizer
DecayedAdagrad = DecayedAdagradOptimizer
RMSProp = RMSPropOptimizer
Ftrl = FtrlOptimizer
Lamb = LambOptimizer
LarsMomentum = LarsMomentumOptimizer


class ExponentialMovingAverage:
    """EMA of parameters (reference: optimizer.py:2292). ``update()`` appends
    shadow-update ops to the main program; ``apply(executor)``/``restore``
    swap shadow and live values in the scope for evaluation."""

    def __init__(self, decay=0.999, name=None):
        self._decay = decay
        self._name = name or "ema"
        self._shadows: List[Tuple[Variable, Variable]] = []
        self._backup: Dict[str, object] = {}
        self._step_var = None

    def update(self):
        from paddle_tpu.layers import nn, tensor

        prog = default_main_program()
        block = prog.global_block()
        # Step counter for zero-debiasing: shadows start at 0, so the raw
        # EMA is biased low by (1 - decay^t) (reference: optimizer.py:2292).
        self._step_var = tensor.create_global_var(
            shape=[1], value=0.0, dtype="float32", persistable=True,
            name=unique_name.generate(f"{self._name}_step"),
        )
        bumped = nn.scale(block.var(self._step_var.name), scale=1.0, bias=1.0)
        block.append_op("assign", inputs={"X": bumped},
                        outputs={"Out": self._step_var.name})
        for p in prog.all_parameters():
            if not p.trainable:
                continue
            shadow = tensor.create_global_var(
                shape=list(p.shape), value=0.0, dtype=p.dtype,
                persistable=True,
                name=unique_name.generate(f"{self._name}_{p.name}"),
            )
            # shadow = decay*shadow + (1-decay)*param
            scaled = nn.scale(block.var(shadow.name), scale=self._decay)
            contrib = nn.scale(block.var(p.name), scale=1.0 - self._decay)
            summed = nn.elementwise_add(scaled, contrib)
            block.append_op("assign", inputs={"X": summed},
                            outputs={"Out": shadow.name})
            self._shadows.append((p, shadow))

    def apply(self, executor=None, need_restore: bool = True):
        """Swap EMA values into the live parameters (scope-level).

        Values are copied to host arrays: Executor runs donate scope buffers
        to XLA, so aliasing one jax.Array under two scope names (or keeping a
        reference across a run) would leave dangling device buffers."""
        import contextlib

        import numpy as np

        from paddle_tpu.executor import global_scope

        scope = global_scope()
        # zero-debias: shadow / (1 - decay^t)
        correction = 1.0
        if self._step_var is not None:
            sv = scope.find_var(self._step_var.name)
            t = float(np.asarray(sv).reshape(-1)[0]) if sv is not None else 0.0
            if t > 0:
                correction = 1.0 / (1.0 - self._decay ** t)
        for p, shadow in self._shadows:
            if need_restore:
                self._backup[p.name] = np.asarray(scope.find_var(p.name))
            sv = scope.find_var(shadow.name)
            if sv is not None:
                scope.set(p.name, np.asarray(sv) * correction)

        @contextlib.contextmanager
        def _guard():
            try:
                yield
            finally:
                if need_restore:
                    self.restore()

        return _guard()

    def restore(self, executor=None):
        from paddle_tpu.executor import global_scope

        scope = global_scope()
        for name, val in self._backup.items():
            scope.set(name, val)
        self._backup.clear()


class ModelAverage(Optimizer):
    """Placeholder for reference optimizer.py:2132; full averaging windows
    land with the high-level Trainer."""

    def __init__(self, average_window_rate, min_average_window=10000,
                 max_average_window=10000, regularization=None, name=None):
        super().__init__(0.0, regularization, name)
