"""What a program adapter hands a generator: the system under test, built
through the program's normal entry points, with the few probes the
correctness check reads from its state.

A FAMILY is three files named after it (``traffic/<mix>.json`` and
``configs/<config>.json`` both say ``"family": "<family>"``), found by
that name through ``lib/registry.py``; a PR that brings a new one adds
them, with its configuration, traffic, limits and cell, and edits no file
(``tests/benchmarks/second_family/`` is a worked example that the
self-tests run).  What the generators, the readers, the check and the
tools call on each, with the signatures the code has:

``programs/<family>.py`` - the system under test
  ``depth(config, traffic)`` -> layers of this layout
      (``config["num_hidden_layers"][traffic["layout"]]``).
  ``build_serve(run, clock=time.monotonic)`` -> ``ServeProgram``; for
      ``generators/serve-closed.py``.  Weights: ``lib/weights.make`` over
      the reference's ``weight_shapes`` with ``tref.weights_key(run.seed)``,
      in the served type, in one jitted call; ``run.setup_item(name)``
      around each part of set-up.
  ``build_train(run)`` -> ``TrainProgram``; for ``generators/train.py``.
  ``train_step(config, traffic, devices)``, ``transformer_config``,
      ``to_tree`` - only ``tools/size_cells.py`` asks for them, to compile
      a cell ahead of time; a family without them is sized by hand.
  A module of another root cannot be imported as ``benchmarks.<kind>.
  <family>``: reach the family's reference through ``run.registry.module(
  "reference", run.traffic["family"])``.

``reference/<family>.py`` - the plain reference; imports nothing of the
program and takes nothing it made
  ``weight_shapes(cfg, layers)`` -> ordered ``{leaf name: shape}``.
      ``lib/weights.py`` draws by position and scales by name:
      ``*.scale`` 1 + 0.1 N(0,1), ``*.branch_scale`` a fifth of that,
      ``*.bias`` 0.1 N(0,1), ``embed`` N(0,1), anything else
      N(0,1)/sqrt(fan_in) with fan_in the product of all but the last
      dimension.  Name new leaves so that the rule that fits them applies.
  ``logits_at(w, tokens, rows, cfg, layers, quant=None)`` -> float32
      ``[len(rows), vocab]``: logits of ONE sequence ``tokens`` [max_len]
      at positions ``rows``; the served check (``serve-closed.py``
      ``reference_logits``).  ``w`` arrives in the SERVED type (bfloat16
      leaves, as the program got them) and is never copied to float32
      whole: widen a leaf where it is used (exact), compute in float32
      (the caller sets ``jax.default_matmul_precision("highest")``), and
      work in blocks - over the vocabulary, over positions, over layers -
      where that is what fits beside the leaves.  ``quant`` names the
      control (``"int8"`` under a bf16 served model).
  ``batch_loss(w, batch, cfg, layers, quant=None, **traffic["reference"])``
      -> scalar; differentiated in float32 by ``lib/train_reference.py``
      (``"fp8"`` is its control).  ``make_batch(key, traffic, cfg)`` -> the
      step's resident batch.

``counts/<family>.py`` - required operations and bytes, from shapes
  ``decode_step_bytes(cfg, layers, live_tokens, rows=None)`` -> bytes one
      decode step has to move (``readers/decode_roofline.py`` passes the
      mean live tokens and the mean rows that decode; a family with
      per-row state counts its read and write per row).
  ``train_flops_per_item(cfg, layers, traffic)`` (``readers/mfu.py``);
  ``flash_forward``/``flash_backward`` only where a cell lists the flash
      rooflines.

What ``serve-closed.py`` reads off ``ServeProgram.engine``: ``submit(
Request) -> bool``, ``step() -> [RequestResult]`` (``request_id``,
``outcome``, ``reason``, ``tokens``, ``ttft_s``, ``finish_ts``,
``phase_ms``), ``run_to_completion()``, ``len(engine.queue)``,
``active_count``, ``kv.ledger.length(slot)`` over ``scheduler.active``;
``describe["layers"]``; ``compiles()`` before and after the window;
``free()`` once the window has closed and before the reference runs.  The
step records come from the program's tracer (``lib/step_phases.py``).
"""

import dataclasses
from typing import Any, Callable


@dataclasses.dataclass
class TrainProgram:
    """``step(params, opt_state, batch) -> (params, opt_state, loss)`` is
    the compiled step itself (it donates its state); the probes are small
    jitted readers that donate nothing."""
    step: Callable
    params: Any
    opt_state: Any
    batch: Any
    items_per_step: int          # tokens or images, over all chips
    chips: int
    first_grad_norms: Callable   # opt_state -> {leaf: norm} after step 1
    delta_norms: Callable        # params -> {leaf: norm} against the seed
    describe: dict               # shapes and sizes, for the earlier lines

    def compiles(self):
        return self.step._cache_size()

    def free(self):
        import jax
        for leaf in jax.tree_util.tree_leaves(
                (self.params, self.opt_state, self.batch)):
            leaf.delete()
        self.params = self.opt_state = self.batch = None


@dataclasses.dataclass
class ServeProgram:
    engine: Any
    compiles: Callable           # () -> {"prefill": n, "decode": n}
    describe: dict
    free: Callable               # drops weights and cache from the device
