"""What a program adapter hands a generator: the system under test, built
through the program's normal entry points, with the few probes the
correctness check reads from its state."""

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
