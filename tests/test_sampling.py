"""The sampler (horovod_tpu/serving/sampling.py): a batch of greedy rows
draws nothing, a batch with a sampling row draws what it always drew. The
old body, both candidates computed and ``where()``-mixed, is kept here as
the oracle: tokens equal bit for bit for every batch, and the draw's
random bits sit under the condition in both serving programs."""

import os

import numpy as np
import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax
import jax.numpy as jnp

from horovod_tpu.models import hybrid
from horovod_tpu.models import transformer as tr
from horovod_tpu.serving import decode as serve_decode
from horovod_tpu.serving import engine as engine_mod
from horovod_tpu.serving.sampling import sample_tokens


def _both_candidates(rng, logits, temperature):
    """``sample_tokens`` as it was until PR 51: the draw on every batch."""
    logits = logits.astype(jnp.float32)
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    safe_t = jnp.maximum(temperature, 1e-6)[:, None]
    drawn = jax.random.categorical(rng, logits / safe_t,
                                   axis=-1).astype(jnp.int32)
    return jnp.where(temperature <= 0.0, greedy, drawn)


ROWS = 6
BATCHES = {
    "greedy": [0.0] * ROWS,
    "sampling": [0.7, 1.0, 1.3, 0.2, 2.0, 1e-7],
    "mixed": [0.0, 0.0, 0.9, 0.0, 0.0, 0.0],
    # the engine's idle slot: temperature 0 (``_place_rows``) over
    # whatever logits its junk token and parked position gave
    "idle_junk": [0.0] * ROWS,
    # ... and the same junk beside a row that does sample
    "idle_junk_mixed": [0.0, 1.1, 0.0, 0.0, 0.0, 0.0],
}


@pytest.mark.parametrize("how", ["jit", "eager"])
@pytest.mark.parametrize("vocab", [257, 4096])
@pytest.mark.parametrize("batch", sorted(BATCHES))
def test_the_tokens_are_those_of_both_candidates_mixed(batch, vocab, how):
    logits = 4.0 * jax.random.normal(jax.random.PRNGKey(vocab),
                                     (ROWS, vocab), jnp.float32)
    if batch.startswith("idle_junk"):
        logits = logits.at[3].set(jnp.nan).at[4, ::2].set(jnp.inf) \
            .at[5].set(-jnp.inf)
    temperature = jnp.asarray(BATCHES[batch], jnp.float32)
    rows = np.asarray(temperature) <= 0.0
    wrap = jax.jit if how == "jit" else (lambda f: f)
    for seed in (0, 3, 2**31 - 1):
        rng = jax.random.PRNGKey(seed)
        for dtype in (jnp.float32, jnp.bfloat16):
            got = wrap(sample_tokens)(rng, logits.astype(dtype), temperature)
            want = wrap(_both_candidates)(rng, logits.astype(dtype),
                                          temperature)
            assert got.dtype == jnp.int32 and got.shape == (ROWS,)
            assert np.array_equal(np.asarray(got), np.asarray(want)), \
                (batch, seed, dtype)
            greedy = np.asarray(jnp.argmax(logits.astype(dtype), axis=-1))
            assert np.array_equal(np.asarray(got)[rows], greedy[rows])


def test_the_signature_is_the_one_the_benchmarks_tests_patch():
    import inspect
    assert list(inspect.signature(sample_tokens).parameters) == [
        "rng", "logits", "temperature"]


def _walk(jaxpr, path=()):
    """(equation, names of the equations it lies under), every equation
    of ``jaxpr`` and of what its equations carry."""
    for eqn in jaxpr.eqns:
        yield eqn, path
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _walk(sub, path + (eqn.primitive.name,))


def _draws(jaxpr):
    return [path for eqn, path in _walk(jaxpr)
            if eqn.primitive.name in DRAWS]


DRAWS = ("random_bits", "threefry2x32")


def _tiny_hybrid():
    cfg = hybrid.HybridConfig.tiny(dtype=jnp.float32, attention_impl="full")
    return cfg, hybrid.init_params(cfg, jax.random.PRNGKey(0))


def _tiny_dense():
    cfg = tr.TransformerConfig.tiny(dtype=jnp.float32,
                                    attention_impl="full")
    return cfg, tr.init_params(cfg, jax.random.PRNGKey(0))[1]


@pytest.mark.parametrize("program", ["decode", "prefill"])
@pytest.mark.parametrize("model", ["dense", "hybrid"])
def test_the_draws_random_bits_lie_under_the_condition(model, program):
    """In both serving programs' traces the draw's bits are generated
    inside ONE ``cond`` and nowhere else, and that ``cond`` has a branch
    with no random bits in it: the one a greedy pass takes. (The fold of
    the step's count into the key, two words, is ``random_fold_in`` and
    stays outside.)"""
    cfg, params = {"dense": _tiny_dense, "hybrid": _tiny_hybrid}[model]()
    slots, max_len = 2, 48

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype)
    if program == "decode":
        state = {k: arr(a.shape, a.dtype) for k, a in
                 serve_decode.state_shapes(cfg, slots, max_len).items()}
        traced = engine_mod._decode_jit.trace(
            cfg, params, arr((slots,), jnp.int32), arr((slots,), jnp.int32),
            state, arr((slots,), jnp.float32), arr((slots,), jnp.bool_),
            arr((2,), jnp.uint32), arr((), jnp.int32))
    else:
        traced = engine_mod._prefill_jit.trace(
            cfg, params, arr((1, 16), jnp.int32), arr((), jnp.int32),
            arr((), jnp.float32), arr((2,), jnp.uint32))
    jaxpr = traced.jaxpr.jaxpr
    drawn = _draws(jaxpr)
    assert drawn and all("cond" in path for path in drawn), drawn
    conds = [eqn for eqn, _ in _walk(jaxpr) if eqn.primitive.name == "cond"
             and any(_draws(b.jaxpr) for b in eqn.params["branches"])]
    assert len(conds) == 1
    assert sorted(bool(_draws(b.jaxpr))
                  for b in conds[0].params["branches"]) == [False, True]
    # the argmax a greedy row is served from is outside it
    assert any(eqn.primitive.name == "argmax" and "cond" not in path
               for eqn, path in _walk(jaxpr))
