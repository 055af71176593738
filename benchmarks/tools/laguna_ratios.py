#!/usr/bin/env python3
"""What the seeded weights of ``configs/laguna-xs.2.json`` give at the
published widths, by the plain reference on the CPU in float32:

    JAX_PLATFORMS=cpu python3 benchmarks/tools/laguna_ratios.py \
        [layers] [tokens] [vocab] [seed]

Per layer rms(branch) / rms(residual) for the gated attention, the shared
expert (the dense SwiGLU in the leading dense layer) and the routed experts
as they join the residual, the routed against the shared expert, and how
the tokens spread over the experts (``glm_moe_lite_ratios.spread``, the
same statistics): the share of the experts that at least one of ``rows``
tokens is routed to (the cell's "87% touched at 64 rows" rests on
near-uniform routing: ``counts/laguna.py expected_touched``), the fullest
expert's load against the mean, and the margin by which a token's last
chosen expert's score beats its first unchosen one's (a bfloat16 program
flips a choice where that is under its rounding; top 8 of 256 has more
near ties than top 4 of 64).  Then the logits' standard deviation.
``vocab`` cuts the embedding and the head to their first rows and columns
(the branches do not see the vocabulary's size).  Gains only as exact
powers of two, only under ``assumed.init``.  No device number comes from
here.
"""

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from benchmarks.lib import weights  # noqa: E402
from benchmarks.reference import laguna as ref  # noqa: E402
from benchmarks.tools.glm_moe_lite_ratios import (  # noqa: E402
    ROWS, rms, spread)


def main(layers=5, tokens=512, vocab=4096, seed=1):
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "laguna-xs.2.json")) as f:
        cfg = dict(json.load(f), vocab_size=vocab)
    shapes = ref.weight_shapes(cfg, layers)
    w = jax.jit(lambda k: weights.make(shapes, k, jnp.bfloat16))(
        weights.seed_key(seed))
    toks = jnp.asarray(np.random.default_rng(seed).integers(0, vocab, tokens))
    x = w["embed"][toks].astype(jnp.float32)
    step = jax.jit(lambda w, x, i: ref.branches(w, i, x, cfg, None),
                   static_argnums=2)
    for i in range(layers):
        attended, dense, experts, route = step(w, x, i)
        line = {"layer": i, "residual_rms": rms(x),
                "attention": rms(attended) / rms(x),
                "shared_or_dense": rms(dense) / rms(x + attended)}
        x = x + attended + dense
        if experts is not None:
            idx, _, chosen = route
            line.update(routed=rms(experts) / rms(x - dense),
                        routed_over_shared=rms(experts) / rms(dense),
                        **spread(idx, chosen, cfg["num_experts"]))
            x = x + experts
        print(json.dumps(line), flush=True)
    x = ref.rms_norm(x, w["ln_f.scale"], cfg["rms_norm_eps"])
    logits = ref.head(w, x[-64:], None)
    print(json.dumps({"logits_std": float(jnp.std(logits)),
                      "expected_touched_share_uniform":
                          1.0 - (1.0 - cfg["num_experts_per_tok"]
                                 / cfg["num_experts"]) ** ROWS}))


if __name__ == "__main__":
    with jax.default_matmul_precision("highest"):
        main(*(int(a) for a in sys.argv[1:]))
