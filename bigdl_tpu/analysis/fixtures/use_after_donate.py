# graftlint fixture corpus: use-after-donate.  Parsed, never executed.
# Known-bad functions are named bad_*; known-good good_*; suppressed
# cases carry an explicit disable comment.  tests/test_lint.py asserts
# the exact finding set for this file.
import jax


def make_train_step():
    def _step(w, g):
        return w - g
    step = jax.jit(_step, donate_argnums=(0,))
    return step, "layout"


def bad_read_after_donate(w, g):
    step = jax.jit(lambda a, b: (a - b, b), donate_argnums=(0,))
    new_w, _ = step(w, g)
    return w.sum()                      # BAD: w's buffer was donated


def bad_loop_no_rebind(w, batches):
    step = jax.jit(lambda a, b: a - b, donate_argnums=(0,))
    outs = []
    for b in batches:
        outs.append(step(w, b))         # BAD: iter 2 passes a dead buffer
    return outs


def bad_factory_step(w, g):
    step, _layout = make_train_step()
    out = step(w, g)
    return w * 2                        # BAD: factory-jitted step donated w


def bad_argnames_read(w, g):
    step = jax.jit(lambda *, weights, grads: weights - grads,
                   donate_argnames=("weights",))
    out = step(weights=w, grads=g)
    return w + out                      # BAD: donated via donate_argnames


class Trainer:
    """The factory as a METHOD (``LocalOptimizer._build_step``): the loop
    gets its donating step from ``self._build_step()``."""

    def _build_step(self):
        def _step(params, opt_state, data):
            return params - data, opt_state
        donate = (0, 1) if self.accelerated else ()
        step = jax.jit(_step, donate_argnums=donate)
        return step

    def bad_loop_reads_donated_params(self, batches):
        params, opt_state = self.params, self.opt_state
        step = self._build_step()
        for data in batches:
            new_params, opt_state = step(params, opt_state, data)
            self.params = params        # BAD: params' buffer was donated
            params = new_params
        return params

    def good_trainer_rebinding(self, batches):
        params, opt_state = self.params, self.opt_state
        step = self._build_step()
        for data in batches:
            def dispatch(data):
                nonlocal params, opt_state
                params, opt_state = step(params, opt_state, data)
                self.params = params    # OK: the call's result
                return params, opt_state
            dispatch(data)
        return params


def good_rebind_same_statement(w, g):
    step = jax.jit(lambda a, b: (a - b, b), donate_argnums=(0,))
    w, _ = step(w, g)
    return w.sum()                      # OK: rebound from the result


def good_loop_rebind(w, batches):
    step = jax.jit(lambda a, b: a - b, donate_argnums=(0,))
    for b in batches:
        w = step(w, b)                  # OK: rebound every iteration
    return w


def good_no_donation(w, g):
    step = jax.jit(lambda a, b: a - b)
    out = step(w, g)
    return w.sum()                      # OK: nothing donated


def suppressed_shape_read(w, g):
    step = jax.jit(lambda a, b: (a - b, b), donate_argnums=(0,))
    out, _ = step(w, g)
    # metadata-only read of a donated array is safe (shape survives
    # donation); the suppression documents exactly that
    return w.shape                      # graftlint: disable=use-after-donate
