"""Readings of the trainer's state after its first steps.

``fit`` owns its state from start to end and hands no step's state to its
hooks, so the check of the first steps reads it where the step is built:
for the length of one ``fit`` call, ``tpudist.train.make_train_step`` is
wrapped so that the step it returns also leaves, after chosen calls, a few
per-leaf norms on the device (tiny outputs; the state itself goes on to the
next step untouched). The step that is timed in the window is this same
object. See PERF.md Open questions: a hook of ``fit``'s own would replace
this."""

from __future__ import annotations

import contextlib

from benchmarks import weights


def first_moment(opt_state):
    """The Adam first-moment tree inside an optimizer state, wherever the
    chain keeps it."""
    if hasattr(opt_state, "mu"):
        return opt_state.mu
    if isinstance(opt_state, (tuple, list)):
        for part in opt_state:
            found = first_moment(part)
            if found is not None:
                return found
    return None


class StepTap:
    """``grad_after`` / ``change_after``: the calls (1-based) after which the
    first-moment norms and the norms of the parameters' change since
    ``start`` are read. ``start`` is a copy of the initial leaves of the
    tap's own, held on the device until that reading and then let go."""

    def __init__(self, start, *, grad_after: int = 1, change_after: int = 3):
        self.start = start
        self.grad_after = grad_after
        self.change_after = change_after
        self.calls = 0
        self.built = 0
        self.moment_norms: dict | None = None
        self.change_norms: dict | None = None

    def _wrap(self, step):
        def tapped(state, batch):
            out_state, metrics = step(state, batch)
            self.calls += 1
            if self.calls == self.grad_after:
                mu = first_moment(out_state.opt_state)
                if mu is not None:
                    self.moment_norms = weights.leaf_norms(mu)
            if self.calls == self.change_after:
                self.change_norms = weights.change_norms(
                    out_state.params, self.start
                )
                self.start = None
            return out_state, metrics

        tapped.__dict__.update(step.__dict__)
        return tapped

    @contextlib.contextmanager
    def installed(self):
        import tpudist.train as train

        original = train.make_train_step

        def make_train_step(*args, **kwargs):
            self.built += 1
            return self._wrap(original(*args, **kwargs))

        train.make_train_step = make_train_step
        try:
            yield self
        finally:
            train.make_train_step = original

    def readings(self) -> tuple[dict, dict]:
        """Host floats; raises when ``fit`` never went through the tap."""
        if self.built != 1 or self.moment_norms is None \
                or self.change_norms is None:
            raise RuntimeError(
                f"the step tap was not driven as expected: built "
                f"{self.built} steps, {self.calls} calls"
            )
        to_host = lambda d: {k: float(v) for k, v in d.items()}
        return to_host(self.moment_norms), to_host(self.change_norms)
