"""Training traffic: one device-resident seeded batch, per-step dispatch,
the window cut into blocks of a fixed number of steps.

Set-up builds ONE object (the compiled step with its state), drives it
through its first steps from the seed through the same ``_call`` the
window uses, and hands that object to the window.  Each block ends in a
barrier on its last loss (a scalar read).  The loop keeps up to
``blocks_in_flight`` blocks dispatched: later blocks are queued on the
device before the host waits for this one, as a training loop that logs
its loss some steps late does, so that a host that is held up for less
than the queued work does not leave the chip without any.  (A one-chip
machine shares its host's cores: the process was seen held for 3-6 s
while the chip finished what it had; chip runs, PR 23.)  The host stops
sending when the blocks in flight would end past ``--seconds``; the
window closes when they have all ended, at the first block boundary
after ``--seconds``.  ``train_rate`` is all the window's items over all
its time: a stall longer than the queued work, a slow dispatch or a
barrier inside the step lowers it.  The median block's rate
(``step.block_rate_p50``) and what it leaves out (``step.stall_share``)
stand beside it as per-layer metrics, so that a stalled run can be told
from a slower step.  After the window the program's state is freed and
the family's plain reference follows the same first steps
(``lib/train_reference``).

Traffic parameters: ``family`` (programs/ and reference/ module),
``layout``, ``mesh``, ``global_batch``, ``seq_len`` (LM), ``optimizer``,
``steps_per_block``, ``blocks_in_flight`` (left out or 1: the host waits
for each block before it sends the next), ``check_steps``, ``reference``
(memory-only options of the reference).
"""

import collections
import math
import time


from benchmarks.lib import stats
from benchmarks.lib import train_reference as tref


class Generator:
    def __init__(self, run):
        self.run = run
        self.prog = None
        self.steps_done = 0
        self.bad_steps = 0
        self.first = {}
        self.block_s = 0.0  # median block of the last window

    # -- the window's own call and feed -----------------------------------

    def _call(self):
        p = self.prog
        p.params, p.opt_state, loss = p.step(p.params, p.opt_state, p.batch)
        self.steps_done += 1
        return loss

    def setup(self):
        run, traffic = self.run, self.run.traffic
        adapter = run.registry.module("programs", traffic["family"])
        with run.setup_item("build"):
            self.prog = adapter.build_train(run)
        run.log("program", **self.prog.describe)
        n = traffic["check_steps"]
        losses = []
        for i in range(n):
            with run.setup_item("compile_or_cache_load_and_first_step"
                                if i == 0 else "warm_steps"):
                losses.append(float(self._call()))
            if i == 0:
                with run.setup_item("probes"):
                    grad = self.prog.first_grad_norms(self.prog.opt_state)
        with run.setup_item("probes"):
            delta = self.prog.delta_norms(self.prog.params)
        self.first = {"losses": losses, "grad_norms": grad,
                      "delta_norms": delta}
        run.log("first_steps", losses=losses)
        self.compiles_before = self.prog.compiles()

    def _dispatch_block(self, per_block, dispatch):
        for _ in range(per_block):
            t0 = time.perf_counter()
            with self.run.span("dispatch"):
                loss = self._call()
            dispatch.append(time.perf_counter() - t0)
        return loss

    def window(self, seconds):
        run = self.run
        per_block = run.traffic["steps_per_block"]
        in_flight = run.traffic.get("blocks_in_flight", 1)
        blocks, dispatch, losses = [], [], []
        pending = collections.deque()
        t_open = t_end = time.perf_counter()
        while True:
            # send blocks while those in flight would end inside the window
            # (a block's time: this window's median, else the last one's)
            while len(pending) < in_flight:
                block_s = stats.median(blocks) if blocks else self.block_s
                ends = t_end - t_open + len(pending) * block_s
                if ends >= seconds and (blocks or pending):
                    break
                pending.append(self._dispatch_block(per_block, dispatch))
            if not pending:
                break
            t_block = t_end
            with run.span("block"):
                value = float(pending.popleft())  # scalar read: the barrier
            t_end = time.perf_counter()
            blocks.append(t_end - t_block)
            losses.append(value)
            if not math.isfinite(value):
                self.bad_steps += per_block
        self.block_s = stats.median(blocks)
        window_s = t_end - t_open
        p = self.prog
        items = p.items_per_step * per_block
        rates = [items / b / p.chips for b in blocks]
        total = stats.total_rate(len(blocks), items, window_s, p.chips)
        run.log("blocks", count=len(blocks), steps_per_block=per_block,
                blocks_in_flight=in_flight, window_s=window_s,
                median_block_s=stats.median(blocks),
                total_rate=total,
                median_block_rate=stats.block_rate(blocks, items, p.chips),
                rates=[round(r, 2) for r in rates])
        obs = {"kind": "train", "block_s": blocks, "dispatch_s": dispatch,
               "window_s": window_s, "items_per_block": items,
               "chips": p.chips, "steps": len(blocks) * per_block,
               "last_loss": losses[-1],
               "compiles": p.compiles(),
               "compiled_in_window": p.compiles() - self.compiles_before}
        obs["end_to_end"] = {"train_rate": total}
        return obs

    def memory_analysis(self):
        p = self.prog
        ma = p.step.lower(p.params, p.opt_state, p.batch).compile() \
            .memory_analysis()
        return {k: getattr(ma, k) for k in (
            "argument_size_in_bytes", "output_size_in_bytes",
            "alias_size_in_bytes", "temp_size_in_bytes")
            if hasattr(ma, k)}

    def check(self):
        """(checks, attempted, failed): each check is (name, value, limit)."""
        run, traffic = self.run, self.run.traffic
        self.prog.free()
        ref = run.registry.module("reference", traffic["family"])
        adapter = run.registry.module("programs", traffic["family"])
        layers = adapter.depth(run.config, traffic)
        t0 = time.perf_counter()
        want = tref.run(ref, run.config, layers, traffic, run.seed,
                        run.devices, steps=traffic["check_steps"])
        run.log("reference", seconds=time.perf_counter() - t0,
                losses=want["losses"])
        return compare(self.first, want, run.limits), self.steps_done, \
            self.bad_steps


def compare(got, want, limits):
    """The numbers a training cell is held to, each beside its limit.
    ``limits`` holds ``loss_rel``, ``grad_norm_gap`` and ``delta_norm_gap``
    (worst leaf of all) and may hold further ``grad_norm_gap.<tag>`` or
    ``delta_norm_gap.<tag>`` entries, ``{"leaves": [prefixes], "limit":
    x}``: the worst leaf among those named, under a limit of its own."""
    checks = []
    for i, (a, b) in enumerate(zip(got["losses"], want["losses"])):
        checks.append((f"loss_rel.step{i + 1}", abs(a - b) / abs(b),
                       limits["loss_rel"]))
    for name, limit in limits.items():
        kind = name.split(".")[0]
        if kind not in ("grad_norm_gap", "delta_norm_gap"):
            continue
        prefixes = limit["leaves"] if isinstance(limit, dict) else None
        norms = kind.replace("_gap", "s")  # grad_norms, delta_norms
        gap, leaf = stats.worst_leaf_gap(got[norms], want[norms], prefixes)
        checks.append((f"{name}[{leaf}]", gap,
                       limit["limit"] if prefixes else limit))
    return checks
