"""A dense decoder whose layer stack runs several times over ONE set of
weights (a looped, or universal, transformer; the Ouro family is the
published example; benchmarks/reference/ouro.py is the plain reference,
equation by equation):

    x = E[token]
    for t in 0..T-1:                       # the SAME L layers, T times
        for i in 0..L-1:
            a = x + N2_i(Attn_i(N1_i(x); plane t*L+i))
            x = a + N4_i(MLP_i(N3_i(a)))
        x   = N_f(x)                       # closes EVERY pass, feeds the next
        g_t = sigmoid(w_g . x + b_g)       # the exit gate
    logits = W_head x                      # of the last pass

The block is the dense one of models/transformer.py (RMSNorm, fused qkv,
rotate-half rotary, SwiGLU, no bias, an untied or tied head) with, as
configuration: ``passes`` (T), the rotary base, and the two norms that
close a layer's branches (``sandwich_norm``; N2 and N4 above). With
``passes=1`` and ``sandwich_norm=False`` it IS the dense model, bit for
bit on the same leaves (tests/test_looped_model.py). Every norm's eps is
the program's 1e-6 (``models/transformer._rmsnorm``), which is what the
family publishes.

What the loop costs a server: a token leaves ``passes x layers`` K/V
entries, not ``layers``: pass t of layer i reads and extends its own PLANE
``t * layers + i`` of the cache, because the keys of pass 2 are
projections of another hidden state than those of pass 1. The serving
forwards are in serving/decode.py (``_stack``: the dense model's
projections, norms and kernels, run ``passes`` times; reached through
``state_shapes``, ``prefill``, ``decode``); this module holds the
configuration, a seeded parameter tree, the exit distribution and the
plain whole-sequence forward that returns every pass.

The exit gate gives each pass a probability of being the last:
``p_t = g_t prod_{s<t}(1 - g_s)`` and the last pass takes what is left.
A row leaves at the first pass where the running sum reaches
``exit_threshold``; at 1 (the published value) that is always the last,
so every row of a batch runs every pass. A threshold below 1 would let
rows of ONE batch leave after different passes: serving refuses it by
name (``check_served``) and does not approximate it.

No training path: a looped stack under ``remat`` is ROADMAP work.
"""

import dataclasses

import jax
import jax.numpy as jnp

from .transformer import _logits


@dataclasses.dataclass(frozen=True)
class LoopedConfig:
    vocab_size: int = 32000
    num_layers: int = 4         # WEIGHT layers; the cache holds passes x
    num_heads: int = 8
    d_model: int = 1024
    d_ff: int = 4096
    passes: int = 4             # times the stack runs over its weights
    sandwich_norm: bool = True  # a norm closing each branch (N2, N4)
    rope_theta: float = 10000.0
    exit_threshold: float = 1.0
    max_seq_len: int = 2048
    dtype: jnp.dtype = jnp.bfloat16
    tie_embeddings: bool = False
    logits_fp32: bool = True
    attention_impl: str = "full"

    @property
    def planes(self):
        """K/V planes the cache holds: one per (pass, layer)."""
        return self.passes * self.num_layers

    @classmethod
    def tiny(cls, **kw):
        base = dict(vocab_size=256, num_layers=2, num_heads=4, d_model=64,
                    d_ff=256, passes=3, max_seq_len=128)
        base.update(kw)
        return cls(**base)


def check_served(cfg):
    """Serving runs every pass for every row; a configuration under which
    rows would leave early is refused, never approximated."""
    if cfg.passes < 1:
        raise ValueError(f"passes must be at least 1, got {cfg.passes}")
    if cfg.exit_threshold < 1.0:
        raise NotImplementedError(
            f"exit_threshold={cfg.exit_threshold} < 1 lets rows of one "
            "batch leave the stack after different passes; serving has no "
            "per-pass row mask in decode, no scheduler that regroups the "
            "rows that stay, and no account of the K/V planes a row never "
            "fills. It runs every pass for every row (exit_threshold 1)")


def init_params(cfg, key):
    """A seeded parameter tree in the dense model's layout (embed /
    layer_i.{ln_attn, attn.{qkv, out}, ln_mlp, mlp.{gate, up, down}} /
    ln_f / lm_head), plus ``ln_attn_out`` and ``ln_mlp_out`` per layer
    under ``sandwich_norm`` and ``exit_gate``: matrices
    N(0,1)/sqrt(fan_in), norm gains 1 + 0.1 N(0,1), the gate's bias 0."""
    d = cfg.d_model
    keys = iter(jax.random.split(key, 12 * cfg.num_layers + 8))

    def mat(fan_in, fan_out):
        return {"kernel": jax.random.normal(
            next(keys), (fan_in, fan_out), jnp.float32) / fan_in ** 0.5}

    def gain():
        return {"scale": 1.0 + 0.1 * jax.random.normal(
            next(keys), (d,), jnp.float32)}

    params = {"embed": {"embedding": jax.random.normal(
        next(keys), (cfg.vocab_size, d), jnp.float32)}, "ln_f": gain(),
        "exit_gate": dict(mat(d, 1), bias=jnp.zeros((1,), jnp.float32))}
    if not cfg.tie_embeddings:
        params["lm_head"] = mat(d, cfg.vocab_size)
    for i in range(cfg.num_layers):
        layer = {"ln_attn": gain(), "ln_mlp": gain(),
                 "attn": {"qkv": mat(d, 3 * d), "out": mat(d, d)},
                 "mlp": {"gate": mat(d, cfg.d_ff), "up": mat(d, cfg.d_ff),
                         "down": mat(cfg.d_ff, d)}}
        if cfg.sandwich_norm:
            layer.update(ln_attn_out=gain(), ln_mlp_out=gain())
        params[f"layer_{i}"] = layer
    return params


def exit_gates(cfg, params, hidden):
    """g_t of every pass: ``hidden`` [passes, ..., d] (each pass's
    normalised hidden state) -> [passes, ...] float32."""
    gate = params["exit_gate"]
    score = jnp.dot(hidden.astype(cfg.dtype),
                    gate["kernel"].astype(cfg.dtype),
                    preferred_element_type=jnp.float32)[..., 0]
    return jax.nn.sigmoid(score + gate["bias"].astype(jnp.float32))


def exit_distribution(gates):
    """``gates`` [passes, ...] -> p_t [passes, ...]: the probability that
    pass t is the last. ``p_t = g_t prod_{s<t}(1 - g_s)``, and the last
    pass takes what the others left, so the p_t sum to 1."""
    stay = jnp.cumprod(1.0 - gates, axis=0)
    before = jnp.concatenate([jnp.ones_like(stay[:1]), stay[:-1]], axis=0)
    return jnp.concatenate([(gates * before)[:-1], before[-1:]], axis=0)


def forward(cfg, params, tokens):
    """The plain forward over whole sequences ``tokens`` [b, s], no cache:
    (logits [b, s, vocab] of the LAST pass, hidden [passes, b, s, d] (each
    pass's normalised hidden state), p_t [passes, b, s] float32)."""
    # the one import of serving/ left in models/: the looped forward and
    # ``_stack`` live there until the block is one (ROADMAP D1 (a))
    from ..serving.decode import hidden_states  # which imports this module
    hidden, _, _ = hidden_states(cfg, params, tokens)
    return (_logits(cfg, params, hidden[-1]), hidden,
            exit_distribution(exit_gates(cfg, params, hidden)))
