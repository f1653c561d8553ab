"""What the step builders share: the one object set-up builds and the window
drives, and the check that the benchmark's seeded tree has the program's shape."""

from __future__ import annotations

import jax


class TrainProgram:
    """One compiled step with its state: set-up drives it through its first
    steps and hands this same object to the window."""

    def __init__(self, step, state, batch, items_per_step, describe):
        self._step = step
        self.state = state
        self.batch = batch
        self.items_per_step = items_per_step
        self.describe = describe

    def advance(self):
        """One step through the window's own call and feed; returns the
        loss as a device scalar (not fetched)."""
        *self.state, loss = self._step(*self.state, self.batch)
        return loss

    def params(self):
        return self.state[0]

    def first_gradient(self):
        """After exactly one step: the gradient as the optimizer got it."""
        raise NotImplementedError

    def free(self):
        self.state = self.batch = self._step = None


def check_tree(ours, theirs, what):
    a = jax.tree_util.tree_map(lambda x: (x.shape, str(x.dtype)), ours)
    b = jax.tree_util.tree_map(lambda x: (x.shape, str(x.dtype)), theirs)
    if a != b:
        raise RuntimeError(
            f"{what}: the benchmark's seeded tree does not have the "
            f"program's shape; the configuration and the program disagree")
