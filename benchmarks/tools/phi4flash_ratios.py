#!/usr/bin/env python3
"""What the seeded weights of ``configs/phi-4-mini-flash.json`` give at the
published widths and FULL depth, by the plain reference on the CPU in
float32:

    JAX_PLATFORMS=cpu python3 benchmarks/tools/phi4flash_ratios.py \
        [tokens] [vocab] [seed]

Per layer rms(branch) / rms(residual) for the layer's mixer (Mamba-1,
window or full differential attention, gated memory unit, differential
cross-attention) and for its SwiGLU as they join the residual, and the
layer's lambda; then the logits' standard deviation under the embedding's
gain, and the mean logit of each position's OWN input token (the head is
the embedding: at unit gain it is the largest by far).  One layer's leaves are drawn at a time (the whole model is
15 GB in float32: it is never held here), so the draws are not the cell's;
the law is.  ``vocab`` cuts the embedding to its first rows (the branches
do not see the vocabulary's size).  Gains only as exact powers of two,
only under ``assumed.init``.  No device number comes from here.
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
from benchmarks.reference import phi4flash as ref  # noqa: E402


def rms(x):
    return float(jnp.sqrt(jnp.mean(jnp.square(x))))


def main(tokens=256, vocab=4096, seed=1):
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "phi-4-mini-flash.json")) as f:
        cfg = dict(json.load(f), vocab_size=vocab)
    layers = cfg["num_hidden_layers"]
    shapes = ref.weight_shapes(cfg, layers)
    key = weights.seed_key(seed)

    def leaves(prefix, fold):
        part = {n: s for n, s in shapes.items() if n.startswith(prefix)}
        return jax.jit(lambda k: weights.make(part, k, jnp.bfloat16))(
            jax.random.fold_in(key, fold))
    toks = jnp.asarray(np.random.default_rng(seed).integers(0, vocab, tokens))
    embed = leaves("embed", 0)
    x = embed["embed"][toks].astype(jnp.float32) * ref.embed_gain(cfg)
    carried = {}
    for i in range(layers):
        w = leaves(f"layers.{i}.", i + 1)
        p = f"layers.{i}."
        mixed, carried = jax.jit(
            lambda w, x, c, i=i: ref.mix(w, i, x, c, cfg, None))(
                w, x, carried)
        line = {"layer": i, "kind": ref.layer_kind(cfg, i),
                "residual_rms": rms(x), "mixer": rms(mixed) / rms(x)}
        x = x + mixed
        fed = jax.jit(lambda w, x, p=p: ref.swiglu(
            w, p, ref.layer_norm(w, p + "ln_mlp", x, cfg), cfg, None))(w, x)
        line["swiglu"] = rms(fed) / rms(x)
        x = x + fed
        if p + "attn.lambda" in w:
            lq1, lk1, lq2, lk2 = np.asarray(w[p + "attn.lambda"],
                                            np.float32).T
            line["lambda"] = float(np.exp(lq1 @ lk1) - np.exp(lq2 @ lk2)
                                   + ref.lambda_init(i))
        if "memory" in carried and i == layers // 2:
            line["memory_rms"] = rms(carried["memory"])
        print(json.dumps(line), flush=True)
    final = leaves("ln_f", layers + 1)
    hidden = ref.layer_norm(final, "ln_f", x, cfg)
    logits = ref.head(embed, hidden[-64:], cfg, None)
    # the head is the embedding: how far a token's OWN row stands out
    own = logits[jnp.arange(64), toks[-64:]]
    print(json.dumps({"logits_std": float(jnp.std(logits)),
                      "own_token_logit_mean": float(jnp.mean(own)),
                      "embed_gain": ref.embed_gain(cfg)}))


if __name__ == "__main__":
    with jax.default_matmul_precision("highest"):
        main(*(int(a) for a in sys.argv[1:]))
