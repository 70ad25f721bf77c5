"""Faults planted in the timed path, for the tests that see ``correct``
come out false. Each is a ``harness.Hooks``."""
from __future__ import annotations

from bench import harness


def state_unchanged() -> harness.Hooks:
    """The step computes its loss but returns params and state as given."""
    def wrap(step_fn, cfg):
        inner = harness.make_step(cfg, donate=False)

        def step(params, opt, batch):
            return params, opt, inner(params, opt, batch)[2]
        return step
    return harness.Hooks(wrap_step=wrap)


def half_batch() -> harness.Hooks:
    """The step trains on the first half of the batch's rows only, the
    mean taken over them."""
    def wrap(step_fn, cfg):
        micro = max(1, cfg["train"]["microbatches"] // 2)
        inner = harness.make_step(cfg, microbatches=micro)
        rows = cfg["train"]["global_batch"] // 2

        def step(params, opt, batch):
            return inner(params, opt, {"tokens": batch["tokens"][:rows]})
        return step
    return harness.Hooks(wrap_step=wrap)


def token_altered() -> harness.Hooks:
    """One token of one committed TGB differs from the generator's."""
    def grid(gen, producer, seq):
        g = gen.grid(producer, seq)
        if (producer, seq) == (0, 1):
            g = g.copy()
            g[0, 5] = (g[0, 5] + 1) % (g.max() + 1)
        return g
    return harness.Hooks(grid=grid)


FAULTS = {"state_unchanged": state_unchanged, "half_batch": half_batch,
          "token_altered": token_altered}
