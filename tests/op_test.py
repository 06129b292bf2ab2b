"""OpTest base: the reference's op-unit-test mechanism, TPU-native.

Mirrors `test/legacy_test/op_test.py` in the reference (SURVEY.md §4): each op
is checked two ways —
  * ``check_output``: framework op vs a NumPy reference implementation;
  * ``check_grad``: analytic gradients from the autograd tape vs central
    finite differences of the op itself.
Dtype parametrization (fp32/fp64, and bf16 with loose tolerances) happens in
the concrete suites via pytest parametrize.
"""
from __future__ import annotations

import numpy as np

import paddle_tpu as paddle


class OpTest:
    """Check one op against a NumPy reference and numeric gradients.

    Concrete tests call :meth:`check_output` / :meth:`check_grad` with the
    framework-level callable (operating on ``paddle.Tensor``) and plain
    ``np.ndarray`` inputs.
    """

    atol = 1e-5
    rtol = 1e-5
    grad_atol = 1e-2
    grad_rtol = 1e-2
    fd_eps = 1e-3

    # ---- output check -----------------------------------------------------

    def check_output(self, fn, ref, inputs, atol=None, rtol=None):
        """``fn(*tensors)`` must match ``ref(*arrays)``.

        Either may return a tensor/array or a tuple of them.
        """
        tensors = [paddle.to_tensor(x) for x in inputs]
        got = fn(*tensors)
        want = ref(*inputs)
        got = got if isinstance(got, (tuple, list)) else (got,)
        want = want if isinstance(want, (tuple, list)) else (want,)
        assert len(got) == len(want), f"{len(got)} outputs vs {len(want)} refs"
        for g, w in zip(got, want):
            np.testing.assert_allclose(
                np.asarray(g.numpy(), dtype=np.asarray(w).dtype), w,
                atol=atol if atol is not None else self.atol,
                rtol=rtol if rtol is not None else self.rtol)

    # ---- gradient check ---------------------------------------------------

    def _scalarize(self, fn, seeds):
        """Reduce (possibly multi-output) op to a scalar with fixed weights so
        FD and analytic grads see the same loss surface."""
        def loss_t(*tensors):
            out = fn(*tensors)
            outs = out if isinstance(out, (tuple, list)) else (out,)
            total = None
            for o, s in zip(outs, seeds):
                term = (o * paddle.to_tensor(s)).sum()
                total = term if total is None else total + term
            return total
        return loss_t

    def check_grad(self, fn, inputs, grad_inputs=None, atol=None, rtol=None,
                   eps=None):
        """Analytic grad (tape) vs central finite differences, in float64."""
        eps = eps if eps is not None else self.fd_eps
        inputs = [np.asarray(x, dtype=np.float64) for x in inputs]
        grad_inputs = (list(range(len(inputs)))
                       if grad_inputs is None else grad_inputs)

        # fixed projection weights per output
        probe = fn(*[paddle.to_tensor(x) for x in inputs])
        probe = probe if isinstance(probe, (tuple, list)) else (probe,)
        rng = np.random.RandomState(7)
        seeds = [rng.uniform(0.5, 1.5, size=tuple(p.shape)).astype(np.float64)
                 for p in probe]
        loss_t = self._scalarize(fn, seeds)

        # analytic
        tensors = [paddle.to_tensor(x, stop_gradient=(i not in grad_inputs))
                   for i, x in enumerate(inputs)]
        loss = loss_t(*tensors)
        loss.backward()
        analytic = {i: np.asarray(tensors[i].grad.numpy(), dtype=np.float64)
                    for i in grad_inputs}

        # numeric, central difference over every element
        def loss_np(arrs):
            ts = [paddle.to_tensor(a) for a in arrs]
            return float(loss_t(*ts).numpy())

        for i in grad_inputs:
            num = np.zeros_like(inputs[i])
            flat = num.reshape(-1)
            for j in range(flat.size):
                plus = [a.copy() for a in inputs]
                minus = [a.copy() for a in inputs]
                plus[i].reshape(-1)[j] += eps
                minus[i].reshape(-1)[j] -= eps
                flat[j] = (loss_np(plus) - loss_np(minus)) / (2 * eps)
            np.testing.assert_allclose(
                analytic[i], num,
                atol=atol if atol is not None else self.grad_atol,
                rtol=rtol if rtol is not None else self.grad_rtol,
                err_msg=f"grad mismatch for input {i}")


def max_ulps(got, want) -> float:
    """Largest |got - want| over two pytrees, in ULPs of each leaf's
    largest magnitude (float leaves; other leaves must be equal). The
    unit for "the same maths in another accumulation order": a sum
    re-associated by the backend moves by a few ULPs of its terms, which
    for an element near zero is many ULPs of that element, so the yardstick
    is the leaf, not the element."""
    import jax
    worst = 0.0
    la = jax.tree_util.tree_leaves(got)
    lb = jax.tree_util.tree_leaves(want)
    assert len(la) == len(lb), (len(la), len(lb))
    for a, b in zip(la, lb):
        a, b = np.asarray(a), np.asarray(b)
        assert a.shape == b.shape, (a.shape, b.shape)
        if a.dtype.kind != "f":
            assert np.array_equal(a, b)
            continue
        diff = np.abs(a.astype(np.float64) - b.astype(np.float64)).max()
        scale = np.asarray(np.maximum(np.abs(a).max(), np.abs(b).max()),
                           a.dtype)
        if diff:
            worst = max(worst, float(diff / np.spacing(scale)))
    return worst

