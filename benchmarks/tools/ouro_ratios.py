#!/usr/bin/env python3
"""What the seeded weights of ``configs/ouro-2.6b.json`` give at the
published widths, by the plain reference on the CPU in float32:

    JAX_PLATFORMS=cpu python3 benchmarks/tools/ouro_ratios.py [layers] [tokens] [vocab] [seed]

Per pass and layer rms(branch) / rms(residual) for attention and the
SwiGLU as they join the residual (each behind its closing norm; the rule
wants each between 0.1 and 1, so that leaving out a pass, a norm or a
branch fails ``served_logit_gap``), how far each pass's hidden state lies
from the pass before it, the exit pdf's mean per pass, and the last pass's
logits' standard deviation.  ``vocab`` cuts the embedding and the head to
their first rows and columns (the branches do not see the vocabulary's
size).  No device number comes from here.
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
from benchmarks.reference import ouro as ref  # noqa: E402


def rms(x):
    return float(jnp.sqrt(jnp.mean(jnp.square(x))))


def main(layers=6, tokens=512, vocab=4096, seed=1):
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "ouro-2.6b.json")) as f:
        cfg = dict(json.load(f), vocab_size=vocab)
    shapes = ref.weight_shapes(cfg, layers)
    w = jax.jit(lambda k: weights.make(shapes, k, jnp.bfloat16))(
        weights.seed_key(seed))
    toks = jnp.asarray(np.random.default_rng(seed).integers(0, vocab, tokens))
    x = w["embed"][toks].astype(jnp.float32)
    hidden = []
    for t in range(cfg["total_ut_steps"]):
        for i in range(layers):
            attended, fed = ref.branches(w, f"layers.{i}.", x, cfg, None)
            print(json.dumps({
                "pass": t, "layer": i, "residual_rms": rms(x),
                "attention": rms(attended) / rms(x),
                "mlp": rms(fed) / rms(x + attended)}), flush=True)
            x = x + attended + fed
        x = ref.rms_norm(x, w["ln_f.scale"], cfg["rms_norm_eps"])
        hidden.append(x)
        print(json.dumps({
            "pass": t, "hidden_rms": rms(x),
            "moved_from_last_pass": rms(x - hidden[-2]) / rms(x)
            if t else None}), flush=True)
    pdf = ref.exit_pdf(w, jnp.stack(hidden))
    logits = ref.head(w, x[-64:], None)
    print(json.dumps({"exit_pdf_mean": [float(p) for p in pdf.mean(axis=1)],
                      "logits_std": float(jnp.std(logits))}))


if __name__ == "__main__":
    with jax.default_matmul_precision("highest"):
        main(*(int(a) for a in sys.argv[1:]))
