"""Token sampling for the decode loop — jit-safe, per-row policy.

One function covering both policies the engine offers: temperature 0 is
exact argmax (the reproducibility contract — KV-cached greedy decoding
must match the no-cache forward token-for-token,
tests/test_serving.py), any positive temperature is softmax sampling at
that temperature. The policy is PER ROW (each batch slot carries its
request's own temperature), selected with jnp.where rather than python
branching so a mixed batch stays one compiled program.
"""

import jax
import jax.numpy as jnp


def sample_tokens(rng, logits, temperature):
    """Next token per row.

    logits       [batch, vocab] (any float dtype; upcast to fp32)
    temperature  [batch] fp32; <= 0 selects greedy argmax for that row
    rng          PRNGKey consumed whole (fold per step upstream)

    The argmax is always computed; the categorical draw (threefry bits,
    Gumbel noise and a second argmax over batch x vocab) runs only when
    some row of the batch asks for one: it sits under a ``lax.cond`` on
    ``any(temperature > 0)``, which the program reads in its own input.
    The draw is NOT cheap next to the forward pass at today's
    vocabularies: 24-25 ns a thousand (row, vocabulary) elements on a
    v5e, 0.46 ms of a 20.4 ms decode step at 96 x 200,064 and 0.25 of
    12.8 at 64 x 154,880 (PERF.md §6, PR 51), four to eight times the
    argmax over the same logits; the condition not taken is 0.6 us. A
    batch with one sampling row takes the draw's branch and runs what it
    always ran, on the same ``rng``: the same tokens, bit for bit, as
    when both candidates were always computed.

    The engine writes temperature 0 into every row outside the pass
    (``ServeEngine._place_rows``), so an idle slot's junk logits never
    ask for a draw. Not to be called under ``vmap`` over the
    temperatures: a ``cond`` with a batched predicate becomes a select
    that runs both branches (nothing in the repo does).
    """
    logits = logits.astype(jnp.float32)
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def draw(rng, logits, temperature):
        safe_t = jnp.maximum(temperature, 1e-6)[:, None]
        return jax.random.categorical(rng, logits / safe_t,
                                      axis=-1).astype(jnp.int32)

    drawn = jax.lax.cond(jnp.any(temperature > 0.0), draw,
                         lambda *_: greedy, rng, logits, temperature)
    return jnp.where(temperature <= 0.0, greedy, drawn)
