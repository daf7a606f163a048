"""Shared test utilities.

``grad_check`` is the finite-difference gradient checker — the role of the
reference's ``TEST/nn/GradientChecker.scala``.  Golden comparisons use
independent numpy implementations (the role of the live-Torch oracle in
``TEST/torch/TH.scala``, per SURVEY.md section 7's test mapping).
"""

import jax
import jax.numpy as jnp
import numpy as np


def grad_check(f, x, eps=1e-2, tol=3e-2, seed=0):
    """Check jax.grad(f) against central finite differences at x.

    f: scalar-valued function of one array.  Relative error must be < tol
    (matching the reference checker's 1e-2 default on float32).  The FD
    sweep is one vmapped+jitted batch over all perturbation directions, not
    a python loop (2*N eager evals would dominate the suite's wall time).
    """
    x = jnp.asarray(x, jnp.float32)
    analytic = np.asarray(jax.grad(f)(x))
    n = x.size
    dirs = (jnp.eye(n, dtype=jnp.float32) * eps).reshape((n,) + x.shape)

    try:
        fp = jax.jit(jax.vmap(lambda d: f(x + d)))(dirs)
        fm = jax.jit(jax.vmap(lambda d: f(x - d)))(dirs)
    except Exception:  # non-vmappable f: jitted loop fallback
        fj = jax.jit(f)
        fp = jnp.stack([fj(x + d) for d in dirs])
        fm = jnp.stack([fj(x - d) for d in dirs])
    numeric = (np.asarray(fp, np.float64) -
               np.asarray(fm, np.float64)).reshape(x.shape) / (2 * eps)
    denom = np.maximum(np.abs(numeric) + np.abs(analytic), 1e-3)
    rel = np.abs(numeric - analytic) / denom
    assert rel.max() < tol, \
        f"grad mismatch: max rel err {rel.max():.4f}\n" \
        f"analytic={analytic}\nnumeric={numeric}"
    return True


def module_grad_check(module, x, wrt="input", seed=0, eps=1e-2, tol=3e-2,
                      training=False, rng=None):
    """Gradient-check a module's input or parameter gradients through a
    sum-of-outputs scalar head."""
    module.build(seed=seed)

    if wrt == "input":
        def f(xx):
            y, _ = module.apply(module.params, module.state, xx,
                                training=training, rng=rng)
            return jnp.sum(y)
        return grad_check(f, x, eps=eps, tol=tol)

    flat_leaves, treedef = jax.tree_util.tree_flatten(module.params)
    for li in range(len(flat_leaves)):
        def f(leaf):
            leaves = list(flat_leaves)
            leaves[li] = leaf
            params = jax.tree_util.tree_unflatten(treedef, leaves)
            y, _ = module.apply(params, module.state, x,
                                training=training, rng=rng)
            return jnp.sum(y)
        grad_check(f, flat_leaves[li], eps=eps, tol=tol)
    return True


def assert_close(a, b, rtol=1e-5, atol=1e-5, msg=""):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=rtol, atol=atol, err_msg=msg)


def graftlint_clean(*paths):
    """Assert the given paths (default: the whole package) lint clean
    under the committed baseline — the fast-tier static-analysis gate
    (``pytest -m lint`` selects it alone; see docs/static-analysis.md).
    Returns the LintResult so callers can assert on suppression counts.
    """
    from bigdl_tpu.analysis import run_lint
    from bigdl_tpu.analysis.engine import default_baseline_path
    res = run_lint(list(paths) or None,
                   baseline_path=default_baseline_path())
    assert not res.errors, "graftlint internal errors: " + "; ".join(
        res.errors)
    assert not res.findings, "graftlint findings:\n" + "\n".join(
        f.render() for f in res.findings)
    return res


def record_steps(opt):
    """``(stepno, data)`` of every step a trainer dispatches from here on:
    the batch its step program is handed, as the loop holds it (on the
    device).  Wraps the one place all three trainer loops run a step."""
    seen, run_step = [], opt._run_step

    def recording(feed, stepno, label, data, dispatch, **attrs):
        seen.append((stepno, data))
        return run_step(feed, stepno, label, data, dispatch, **attrs)

    opt._run_step = recording
    return seen
