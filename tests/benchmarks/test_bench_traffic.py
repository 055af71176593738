"""The closed-loop generator's lengths: a fixed multiset that the seed
only permutes."""

import itertools
import json
import os

from benchmarks.lib.registry import Registry
from bench_tiny import REPO

GEN = Registry([REPO]).module("generators", "serve-closed")
with open(os.path.join(REPO, "benchmarks", "traffic",
                       "serve-closed16.json")) as f:
    MIX = json.load(f)


def _cycle(seed, cycles=1):
    n = MIX["requests_per_cycle"] * cycles
    return list(itertools.islice(GEN.request_stream(MIX, 64000, seed), n))


def test_every_seed_offers_the_same_multiset_in_another_order():
    a, b = _cycle(1), _cycle(2 ** 31 + 5)
    for pick in (lambda r: len(r[0]), lambda r: r[1]):
        assert sorted(map(pick, a)) == sorted(map(pick, b))
    assert [len(r[0]) for r in a] != [len(r[0]) for r in b]
    assert [r[0] for r in a] != [r[0] for r in b]          # other tokens
    assert _cycle(1) == a                                   # same seed


def test_each_cycle_is_the_whole_multiset_again():
    two = _cycle(3, cycles=2)
    n = MIX["requests_per_cycle"]
    assert sorted(r[1] for r in two[:n]) == sorted(r[1] for r in two[n:])


def test_lengths_are_mid_quantiles_of_the_clipped_laws():
    prompts = GEN.stratified_lengths(MIX["prompt_tokens"], 64)
    outputs = GEN.stratified_lengths(MIX["output_tokens"], 64)
    assert min(prompts) == 23 and max(prompts) == 1024      # clipped above
    assert prompts == sorted(prompts) and outputs == sorted(outputs)
    assert prompts[31] < 256 < prompts[32]                  # median 256
    assert outputs[31] < 96 < outputs[32]
    assert min(outputs) >= 16 and max(outputs) <= 384
    e = MIX["engine"]
    shapes = {GEN.padded(p, e["kv_block"], e["max_len"]) for p in prompts}
    assert shapes == {128 * k for k in range(1, 9)}         # 8 programs
    assert max(prompts) + max(outputs) - 1 <= e["max_len"]  # never refused
