"""Plain references the benchmark compares the timed path with.

``dataplane`` checks what the readers delivered. Every other module here is
the reference of one model, named by the ``"reference"`` key of each
configuration that runs it (``configs/<config>.json``), and is the one place
in the benchmark that knows that model. ``harness.reference(cfg)`` imports
it; nothing else names it. A reference module gives:

- ``program_config(cfg)``: the program's ``ModelConfig`` for the
  configuration;
- ``seed_words(seed)``: the seed as the argument of the jitted draw;
- ``make_init(model)``: ``init(seed_words) -> params``, the weights that the
  program and the reference both start from;
- ``leaf_paths(params)``, ``leaf_norms(tree)``: each leaf's path and norm,
  in one order;
- ``change_norms(model)``: jitted ``(params, seed_words) -> [norm of the
  change from the seed's draw]`` per leaf;
- ``reference_steps(model, opt, seed, grids, matmul="f32",
  rows=slice(None))``: the reference's losses, first gradient's norms and
  change's norms over ``grids``; ``matmul="fp8"`` is the control, and
  ``rows`` keeps part of each grid (the half-batch fault);
- ``flops_per_token(model, seq_len)``: the model FLOPs of one trained token,
  forward and backward, that ``mfu`` rests on;
- ``SMOKE``: the overrides, by section of the configuration, that cut it to
  the CPU tests' size, with the limits read at that size.
"""

CONTRACT = ("program_config", "seed_words", "make_init", "leaf_paths",
            "leaf_norms", "change_norms", "reference_steps",
            "flops_per_token", "SMOKE")
