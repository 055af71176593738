"""The system under test for the second family: ``programs/baichuan.py``'s
engine, from weights named the way THIS family's reference names them
(found through ``run.registry``, by the family's name in the traffic
file)."""

import time

from benchmarks.programs import baichuan as base

depth = base.depth


def build_serve(run, clock=time.monotonic):
    ref = run.registry.module("reference", run.traffic["family"])
    return base.build_serve(
        run, clock, to_tree=lambda w, layers: base.to_tree(
            {ref.to_base(n): a for n, a in w.items()}, layers))
