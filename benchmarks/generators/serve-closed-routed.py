"""``serve-closed`` for a model whose layers ROUTE their tokens to experts:
the same callers, loop, window, sample and float32 reference (all of it
``serve-closed.py``'s own code, loaded from beside this file), and another
statistic of the same per-token numbers in the served check.

``serve-closed`` holds the WIDEST gap by which a served token's reference
logit lies below the reference's best to a limit.  Routing is discrete: a
bfloat16 program and a float32 reference give a token another last expert
wherever its last chosen and first unchosen score lie closer than
bfloat16 rounds its hidden state, which seeded weights at the published
widths do in one choice of twenty-five, and one such choice moves that
token's logits by 1-2 (a quarter of its routed output is another
expert's).  The widest gap over a thousand served tokens is then one
flipped token's, in a sound run and in the int8 control alike, and no
limit lies between them (``PERF.md`` section 6, PR 42).  A scale on the
router does not cure it: margins and rounding scale together.

So this check reads the gap that ``QUANTILE`` of the served tokens stay
within, under the same name and unit and against the same key of the
limits file.  On the cell this was written for, 14-18 served tokens in a
hundred are not the reference's first choice and 8-12 lie more than 0.13
below it (flips at near ties, and what they leave in the cache for later
tokens); under the int8 control 42-47 and 33-36 do: a program that
computes in a lower precision, takes the wrong expert, weighs it wrongly
or attends wrongly moves every token.  What the statistic cannot see is a
fault in fewer tokens than ``1 - QUANTILE`` of them: the CPU tests hold
each path to the reference token by token.  The widest gap is still
logged (the ``reference`` line), and held to nothing.

``benchmarks/control.py`` reads this module's ``reference_logits`` and
``widest_gap`` as it reads ``serve-closed``'s, so a cell's two readings
are taken the same way.
"""

import importlib.util
import math
import os
import time

import numpy as np

_spec = importlib.util.spec_from_file_location(
    "benchmarks_generators_serve_closed",
    os.path.join(os.path.dirname(os.path.abspath(__file__)),
                 "serve-closed.py"))
closed = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(closed)

reference_logits = closed.reference_logits

# the share of the scored tokens that has to lie within the limit
QUANTILE = 0.85


def widest_gap(sample, logits, tokens=None):
    """(the gap that ``QUANTILE`` of the scored tokens stay within, which
    quantile of how many requests, tokens scored).  A token's gap is how
    far below the reference's best logit its reference logit lies;
    ``tokens`` defaults to the served tokens.  The quantile is a token's
    own gap (the lowest one with ``QUANTILE`` of them at or under it), not
    an interpolation."""
    gaps = []
    for i, (req, lg) in enumerate(zip(sample, logits)):
        toks = np.asarray(req["tokens"] if tokens is None else tokens[i])
        gaps.append(lg.max(axis=-1) - lg[np.arange(len(toks)), toks])
    where = f"p{100 * QUANTILE:g} of {len(gaps)} requests"
    if not gaps:
        return 0.0, where, 0
    gaps = np.concatenate(gaps)
    if not np.isfinite(gaps).all():
        return math.inf, where, len(gaps)
    return float(np.quantile(gaps, QUANTILE, method="higher")), where, \
        len(gaps)


class Generator(closed.Generator):
    def check(self):
        run = self.run
        sample = self.sample()
        self.prog.free()
        t0 = time.perf_counter()
        logits = reference_logits(run, sample)
        gap, where, served = widest_gap(sample, logits)
        worst, at, _ = closed.widest_gap(sample, logits)
        run.log("reference", seconds=time.perf_counter() - t0,
                requests=len(sample), served_tokens=served,
                widest_gap=worst, widest_gap_at=at)
        checks = [(f"served_logit_gap[{where}]", gap,
                   run.limits["served_logit_gap"])]
        if not sample:
            checks = [("finished_requests", math.inf, 0)]
        return checks, self.attempted, self.failed
