"""OpTest harness: run one op and check outputs + numeric gradients.

Re-creation of the reference's per-op test harness
(reference: python/paddle/fluid/tests/unittests/op_test.py:45-82
``get_numeric_gradient`` / ``check_output`` / ``check_grad``): builds a
single-op program, compares the kernel against a numpy reference, and
validates the auto-derived grad kernel against central finite differences.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence

import numpy as np

import paddle_tpu as fluid
from paddle_tpu import layers


class OpHarness:
    def __init__(
        self,
        op_type: str,
        inputs: Dict[str, np.ndarray],
        attrs: Optional[dict] = None,
        out_slots: Sequence[str] = ("Out",),
        multi_input_slots: Sequence[str] = (),
    ):
        self.op_type = op_type
        self.inputs = {
            k: (
                [np.asarray(x) for x in v]
                if k in multi_input_slots
                else [np.asarray(v)]
            )
            for k, v in inputs.items()
        }
        self.attrs = attrs or {}
        self.out_slots = list(out_slots)

    def _build(self, with_grad: bool, grad_wrt: Sequence[str]):
        main, startup = fluid.Program(), fluid.Program()
        feed = {}
        with fluid.program_guard(main, startup):
            in_vars = {}
            for slot, arrs in self.inputs.items():
                vs = []
                for i, a in enumerate(arrs):
                    name = f"{slot.lower()}_{i}"
                    v = main.global_block().create_var(
                        name=name,
                        shape=a.shape,
                        dtype=a.dtype.name,
                        stop_gradient=not np.issubdtype(a.dtype, np.floating),
                    )
                    feed[name] = a
                    vs.append(v)
                in_vars[slot] = vs
            out_vars = {
                slot: main.global_block().create_var(
                    name=f"out_{slot.lower()}", dtype="float32"
                )
                for slot in self.out_slots
            }
            main.global_block().append_op(
                self.op_type,
                inputs={k: v for k, v in in_vars.items()},
                outputs={k: [v] for k, v in out_vars.items()},
                attrs=dict(self.attrs),
            )
            fetch = [out_vars[s] for s in self.out_slots]
            grad_fetch = []
            if with_grad:
                # Scalar objective: sum of fixed pseudo-random projections of
                # each float output (catches grads a plain mean would miss).
                proj = []
                rng = np.random.RandomState(1234)
                outs0 = self.forward()
                for s, o0 in zip(self.out_slots, outs0):
                    if not np.issubdtype(o0.dtype, np.floating):
                        continue
                    w = rng.uniform(0.1, 1.0, o0.shape).astype(o0.dtype)
                    wv = layers.assign(w)
                    proj.append(
                        layers.reduce_sum(
                            layers.elementwise_mul(out_vars[s], wv)
                        )
                    )
                self._proj_weights = rng
                loss = proj[0] if len(proj) == 1 else layers.sums(proj)
                loss = layers.reshape(loss, [1])
                fluid.append_backward(loss, parameter_list=[])
                for name in grad_wrt:
                    g = name + "@GRAD"
                    grad_fetch.append(g)
        return main, startup, feed, fetch, grad_fetch

    def forward(self) -> List[np.ndarray]:
        main, startup, feed, fetch, _ = self._build(False, [])
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        return exe.run(main, feed=feed, fetch_list=fetch)

    def check_output(self, expected: Dict[str, np.ndarray], atol=1e-5, rtol=1e-4):
        outs = self.forward()
        for slot, exp in expected.items():
            got = outs[self.out_slots.index(slot)]
            np.testing.assert_allclose(
                got, exp, atol=atol, rtol=rtol,
                err_msg=f"{self.op_type} output {slot} mismatch",
            )

    def _objective(self, feed) -> float:
        """Scalar objective used for numeric gradients (same projections)."""
        outs = self._fwd_exe.run(self._fwd_main, feed=feed, fetch_list=self._fwd_fetch)
        rng = np.random.RandomState(1234)
        total = 0.0
        for o in outs:
            o = np.asarray(o)
            if not np.issubdtype(o.dtype, np.floating):
                continue
            w = rng.uniform(0.1, 1.0, o.shape).astype(o.dtype)
            total += float(np.sum(o.astype(np.float64) * w))
        return total

    def check_grad(
        self,
        wrt: Sequence[str],  # feed names like "x_0"
        delta: float = 1e-3,
        atol: float = 1e-4,
        rtol: float = 2e-3,
    ):
        main, startup, feed, fetch, grad_fetch = self._build(True, wrt)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        res = exe.run(main, feed=feed, fetch_list=list(fetch) + grad_fetch)
        analytic = res[len(fetch):]

        # forward-only program for numeric diff
        self._fwd_main, fs, _, self._fwd_fetch, _ = self._build(False, [])
        self._fwd_exe = fluid.Executor(fluid.CPUPlace())
        self._fwd_exe.run(fs)

        for name, a_grad in zip(wrt, analytic):
            x = feed[name].astype(np.float64)
            num = np.zeros_like(x)
            flat = x.reshape(-1)
            nflat = num.reshape(-1)
            for i in range(flat.size):
                orig = flat[i]
                flat[i] = orig + delta
                f_plus = self._objective({**feed, name: x.astype(feed[name].dtype)})
                flat[i] = orig - delta
                f_minus = self._objective({**feed, name: x.astype(feed[name].dtype)})
                flat[i] = orig
                nflat[i] = (f_plus - f_minus) / (2 * delta)
            np.testing.assert_allclose(
                a_grad.astype(np.float64).reshape(-1),
                nflat,
                atol=atol,
                rtol=rtol,
                err_msg=f"{self.op_type} grad wrt {name} mismatch",
            )


def delta_rule_op(**attrs):
    """q, k, v, g, beta, dO -> (Out, the five gradients), States of
    ``gated_delta_rule`` and its grad op as the Program runs them: ONE
    jitted computation (op by op a case is some 240 executables), a
    fresh one a call of this: the interpreter hook is read while it is
    traced."""
    import jax

    from paddle_tpu.ops import linear_attention_ops as L

    def both(q, k, v, g, beta, do):
        ins = {"Q": [q], "K": [k], "V": [v], "G": [g], "Beta": [beta]}
        out = L._gated_delta_rule(ins, attrs)
        grads = L._gated_delta_rule_grad(
            {**ins, "States": out["States"], "GRAD::Out": [do]}, attrs)
        return (out["Out"][0], *(grads[f"GRAD::{s}"][0] for s in (
            "Q", "K", "V", "G", "Beta"))), out["States"][0]

    return jax.jit(both)


def delta_rule_recurrence(q, k, v, g, beta, do):
    """The float32 recurrence on the operands as given, and jax's vjp."""
    import jax

    from paddle_tpu.ops import linear_attention_ops as L

    @jax.jit
    def both(q, k, v, g, beta, do):
        f32 = (x.astype("float32") for x in (q, k, v))
        out, vjp = jax.vjp(L.recurrent_gated_delta_rule, *f32, g, beta)
        return (out, *vjp(do.astype("float32")))

    with jax.default_matmul_precision("highest"):
        return both(q, k, v, g, beta, do)
