#!/usr/bin/env python3
"""What the seeded-weight rule of ``configs/falcon-h1-34b.json``
(``assumed.init``) gives at the published widths, by the plain reference
on the CPU in float32:

    JAX_PLATFORMS=cpu python3 benchmarks/tools/falcon_h1_ratios.py [layers] [tokens] [vocab] [seed]

Per layer rms(multiplier * branch) / rms(residual) for the mixer,
attention and the SwiGLU (the rule wants each between 0.1 and 1), the
share of the mixer's y that comes through the recurrent state (and not
the D x skip), the quartiles of exp(dt A) over heads and positions, and
the logits' standard deviation.  ``vocab`` cuts the embedding and the head
to their first rows and columns (the branches do not see the vocabulary's
size; the whole one takes 21 GB of float32 on a host).
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
from benchmarks.reference import falcon_h1 as ref  # noqa: E402


def rms(x):
    return float(jnp.sqrt(jnp.mean(jnp.square(x))))


def main(layers=6, tokens=512, vocab=4096, seed=1):
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "falcon-h1-34b.json")) as f:
        cfg = dict(json.load(f), vocab_size=vocab)
    shapes = ref.weight_shapes(cfg, layers)
    w = jax.jit(lambda k: weights.make(shapes, k, jnp.bfloat16))(
        weights.seed_key(seed))
    toks = jnp.asarray(np.random.default_rng(seed).integers(0, vocab, tokens))
    eps = cfg["rms_norm_eps"]
    x = w["embed"][toks].astype(jnp.float32) * cfg["embedding_multiplier"]
    for i in range(layers):
        p = f"layers.{i}."
        h = ref.rms_norm(x, w[p + "ln_in.scale"], eps)
        m = cfg["ssm_out_multiplier"] * ref.mixer(w, p, h, cfg, None)
        a = cfg["attention_out_multiplier"] * ref.attention(w, p, h, cfg,
                                                            None)
        # the decays, recomputed as the mixer computes them
        z = ref.sizes(cfg)
        u = ref.project(w, p + "mixer.in_proj",
                        cfg["ssm_in_multiplier"] * h, cfg, None)
        dt = u[:, z["d_ssm"] + z["conv"]:] * cfg["ssm_multipliers"][4]
        a_log, dt_bias, _ = ref.mixer_vectors(cfg, w[p + "mixer.A"],
                                              w[p + "mixer.dt"])
        decay = jnp.exp(-jax.nn.softplus(dt + dt_bias) * jnp.exp(a_log))
        x1 = x + m + a
        f = ref.mlp(w, p, ref.rms_norm(x1, w[p + "ln_ff.scale"], eps), cfg,
                    None)
        print(json.dumps({
            "layer": i, "residual_rms": rms(x),
            "mixer": rms(m) / rms(x), "attention": rms(a) / rms(x),
            "attention_last_quarter":
                rms(a[-tokens // 4:]) / rms(x[-tokens // 4:]),
            "mlp": rms(f) / rms(x1),
            "decay_quartiles": [float(q) for q in jnp.quantile(
                decay, jnp.asarray([0.25, 0.5, 0.75]))],
            "decay_min_max": [float(decay.min()), float(decay.max())]}),
            flush=True)
        x = x1 + f
    logits = ref.head(w, ref.rms_norm(x, w["ln_f.scale"], eps)[-64:], cfg,
                      None)
    print(json.dumps({"logits_std": float(jnp.std(logits))}))


if __name__ == "__main__":
    with jax.default_matmul_precision("highest"):
        main(*(int(a) for a in sys.argv[1:]))
