#!/usr/bin/env python3
"""What the seeded weights of ``configs/glm-4.7-flash.json`` give at the
published widths, by the plain reference on the CPU in float32:

    JAX_PLATFORMS=cpu python3 benchmarks/tools/glm_moe_lite_ratios.py [layers] [tokens] [vocab] [seed]

Per layer rms(branch) / rms(residual) for attention, the shared expert
(the dense SwiGLU in a leading dense layer) and the routed experts as they
join the residual, the routed against the shared expert, and how the
tokens spread over the experts: the share of the experts that at least
one of ``rows`` tokens is routed to (the cell's "98% touched at 64 rows"
rests on near-uniform routing: ``counts/glm_moe_lite.py
expected_touched``), the fullest expert's load against the mean, and the
margin by which a token's last chosen expert beats its first unchosen one
(a bfloat16 program flips a choice where that is under its rounding).
Then the logits' standard deviation.  ``vocab`` cuts the embedding and the
head to their first rows and columns (the branches do not see the
vocabulary's size).  Gains only as exact powers of two, only under
``assumed.init``.  No device number comes from here.
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
from benchmarks.reference import glm_moe_lite as ref  # noqa: E402

ROWS = 64  # decoding rows of the cell: tokens a step routes at once


def rms(x):
    return float(jnp.sqrt(jnp.mean(jnp.square(x))))


def spread(idx, chosen, experts, rows=ROWS):
    """How ``idx`` [s, k] spreads over the experts, in groups of ``rows``
    tokens (what one decode step routes)."""
    idx, chosen = np.asarray(idx), np.asarray(chosen)
    groups = [idx[i:i + rows] for i in range(0, len(idx) - rows + 1, rows)]
    touched = [len(np.unique(g)) / experts for g in groups]
    fullest = [np.bincount(g.ravel(), minlength=experts).max()
               for g in groups]
    load = np.bincount(idx.ravel(), minlength=experts)
    ranked = np.sort(chosen, axis=-1)
    k = idx.shape[1]
    margin = ranked[:, -k] - ranked[:, -k - 1]
    return {"touched_share_mean": float(np.mean(touched)),
            "touched_share_min": float(np.min(touched)),
            "fullest_expert_mean": float(np.mean(fullest)),
            "load_max_over_mean": float(load.max() / load.mean()),
            "load_min_over_mean": float(load.min() / load.mean()),
            "margin_p01": float(np.quantile(margin, 0.01)),
            "margin_p10": float(np.quantile(margin, 0.1)),
            "margin_median": float(np.median(margin))}


def main(layers=7, tokens=512, vocab=4096, seed=1):
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "glm-4.7-flash.json")) as f:
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
                        **spread(idx, chosen, cfg["n_routed_experts"]))
            x = x + experts
        print(json.dumps(line), flush=True)
    x = ref.rms_norm(x, w["ln_f.scale"], cfg["rms_norm_eps"])
    logits = ref.head(w, x[-64:], None)
    print(json.dumps({"logits_std": float(jnp.std(logits)),
                      "expected_touched_share_uniform":
                          1.0 - (1.0 - cfg["num_experts_per_tok"]
                                 / cfg["n_routed_experts"]) ** ROWS}))


if __name__ == "__main__":
    with jax.default_matmul_precision("highest"):
        main(*(int(a) for a in sys.argv[1:]))
