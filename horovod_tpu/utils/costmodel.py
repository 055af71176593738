"""Analytical roofline cost model: where SHOULD the milliseconds go.

profiling.py measures where step time went; this module computes where
it is *allowed* to go — per-op-class FLOPs and bytes against the chip's
peak matmul throughput and HBM/interconnect bandwidth — so an MFU
number stops being a scalar to stare at and becomes a decomposition:

    measured MFU 0.62, roofline-bound MFU 0.81
      gap: attention +0.09, collective +0.06, other +0.04

Three layers, all plain arithmetic (no jax import at module scope — the
trainer's in-training attribution calls into this from the step loop):

  * ``chip_spec`` / ``CHIP_SPECS`` — nominal per-chip peak dense bf16
    FLOPs, HBM bandwidth, and ICI (interchip) bandwidth by device_kind
    prefix: the one table to update per TPU generation.
  * ``analytic_lm_costs`` — per-class FLOPs/bytes per step per chip for
    the transformer LM, derived from the SAME PaLM appendix-B
    convention as ``models.transformer.matmul_flops_per_token`` (the
    MFU headline and this model must never disagree about what a FLOP
    is). ``program_costs`` pulls the compiled program's own numbers
    from jax's ``cost_analysis()`` when a compiled object is at hand.
  * ``roofline`` / ``mfu_decomposition`` — per-class compute- vs
    memory- vs comm-bound verdicts (arithmetic intensity against the
    ridge point) and the achievable-MFU decomposition.

All "bytes" figures are a traffic *model*, not a measurement: weight
tensors make three HBM passes per step (forward read, dgrad read, wgrad
write), flash attention streams its operand/residual tensors, and a
ring allreduce moves ``2·(n-1)/n`` of the payload over ICI. Good to the
factor-of-two the verdict needs, documented per term below.
"""

import math


class ChipSpec:
    """Nominal per-chip roofline parameters (bf16 dense matmul peak,
    HBM and ICI bandwidth in bytes/s, HBM capacity in bytes — the
    memory plane's budget denominator; None when unknown)."""

    __slots__ = ("kind", "peak_flops", "hbm_bytes_per_s",
                 "ici_bytes_per_s", "hbm_capacity_bytes")

    def __init__(self, kind, peak_flops, hbm_bytes_per_s,
                 ici_bytes_per_s, hbm_capacity_bytes=None):
        self.kind = kind
        self.peak_flops = peak_flops
        self.hbm_bytes_per_s = hbm_bytes_per_s
        self.ici_bytes_per_s = ici_bytes_per_s
        self.hbm_capacity_bytes = hbm_capacity_bytes

    @property
    def ridge_flops_per_byte(self):
        """Arithmetic intensity at which HBM stops being the bound."""
        return self.peak_flops / self.hbm_bytes_per_s

    def as_dict(self):
        return {"kind": self.kind, "peak_flops": self.peak_flops,
                "hbm_bytes_per_s": self.hbm_bytes_per_s,
                "ici_bytes_per_s": self.ici_bytes_per_s,
                "hbm_capacity_bytes": self.hbm_capacity_bytes}


_GiB = 2 ** 30

# Nominal datasheet numbers by device_kind prefix; longest prefix wins
# ("TPU v5 lite" before "TPU v5"). The "cpu" row exists so the whole
# attribution path exercises on the CPU CI — the numbers are a stand-in
# order of magnitude, not a measurement (the 4 GiB "capacity" bounds
# the CI smoke ledger, it is not host RAM).
CHIP_SPECS = (
    ChipSpec("TPU v5 lite", 197e12, 819e9, 200e9, 16 * _GiB),   # v5e
    ChipSpec("TPU v5", 459e12, 2765e9, 600e9, 95 * _GiB),       # v5p
    ChipSpec("TPU v4", 275e12, 1228e9, 268e9, 32 * _GiB),
    ChipSpec("TPU v6", 918e12, 1640e9, 448e9, 32 * _GiB),       # trillium
    ChipSpec("cpu", 200e9, 50e9, 10e9, 4 * _GiB),
)


def chip_spec(device_or_kind):
    """Longest-prefix match against CHIP_SPECS; accepts a jax device
    (``device_kind`` attribute) or a kind string. None when unknown."""
    kind = getattr(device_or_kind, "device_kind", device_or_kind) or ""
    best = None
    for spec in CHIP_SPECS:
        if kind.lower().startswith(spec.kind.lower()):
            if best is None or len(spec.kind) > len(best.kind):
                best = spec
    return best


def peak_flops(device_or_kind):
    """Peak dense bf16 FLOPs/s for a device, or None when unknown."""
    spec = chip_spec(device_or_kind)
    # the CPU row is a placeholder magnitude — an MFU computed against
    # it would be noise, so callers keep getting None off-TPU
    if spec is None or spec.kind == "cpu":
        return None
    return spec.peak_flops


def program_costs(compiled):
    """FLOPs / bytes-accessed straight from a jax compiled program's
    ``cost_analysis()`` (dict on new jax, [dict] on older releases).
    Returns ``{"flops": float, "bytes": float}`` or None when the
    backend doesn't report costs."""
    try:
        ca = compiled.cost_analysis()
    # hvdlint: disable=HVD006(cost_analysis is optional backend metadata; None falls back to the analytic model)
    except Exception:
        return None
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else None
    if not isinstance(ca, dict):
        return None
    flops = ca.get("flops")
    nbytes = ca.get("bytes accessed")
    if flops is None and nbytes is None:
        return None
    return {"flops": float(flops or 0.0), "bytes": float(nbytes or 0.0)}


def lm_matmul_params(cfg):
    """P_matmul of the PaLM convention: qkv+out projections (4·d²), the
    gated SwiGLU MLP (three d×d_ff kernels), and the lm_head. Must stay
    equal to the one inside models.transformer.matmul_flops_per_token —
    pinned against it by tests/test_costmodel.py."""
    return (cfg.num_layers * (4 * cfg.d_model ** 2 +
                              3 * cfg.d_model * cfg.d_ff) +
            cfg.d_model * cfg.vocab_size)


def analytic_lm_costs(cfg, seq, batch_per_chip, n_chips=1,
                      dtype_bytes=2, wire_bytes_per_param=2.0):
    """Per-class FLOPs and bytes PER STEP PER CHIP for the transformer
    LM, from the config alone (the ``cost_analysis`` fallback).

    Classes and the traffic model behind each term:

      matmul     flops = 6·P_matmul·T  (fwd 2 + bwd 4, per token)
                 hbm   = 3·P_matmul·dtype_bytes  (weights: fwd read,
                         dgrad read, wgrad write; activation traffic of
                         the matmuls rides in fusions → "other")
      attention  flops = 12·L·seq·d·T  (the seq-quadratic term of the
                         same convention, fwd+bwd)
                 hbm   = 10·L·T·d·dtype_bytes  (flash streams q,k,v,o
                         forward and q,k,v,o,do + dq|dkv writes
                         backward — ~10 tensor passes, no S matrix)
      collective wire  = 2·P_matmul·wire_bytes_per_param·(n-1)/n
                         (ring allreduce of the gradients; width 2.0 =
                         bf16 wire, 1.0 ≈ the negotiated int8 codec)

    T = batch_per_chip·seq tokens per chip per step. Returns
    ``{cls: {"flops": f, "hbm_bytes": b, "wire_bytes": w}}``.
    """
    tokens = batch_per_chip * seq
    p_matmul = lm_matmul_params(cfg)
    ring = (n_chips - 1) / n_chips if n_chips > 1 else 0.0
    attn_tensors = 10 * cfg.num_layers * tokens * cfg.d_model
    return {
        "matmul": {
            "flops": 6.0 * p_matmul * tokens,
            "hbm_bytes": 3.0 * p_matmul * dtype_bytes,
            "wire_bytes": 0.0,
        },
        "attention": {
            "flops": 12.0 * cfg.num_layers * seq * cfg.d_model * tokens,
            "hbm_bytes": float(attn_tensors * dtype_bytes),
            "wire_bytes": 0.0,
        },
        "collective": {
            "flops": 0.0,
            "hbm_bytes": 2.0 * p_matmul * dtype_bytes * (1 if ring else 0),
            "wire_bytes": 2.0 * p_matmul * wire_bytes_per_param * ring,
        },
    }


def lm_activation_bytes(cfg, seq, batch_per_chip, dtype_bytes=None):
    """Per-chip LIVE activation bytes for one training step of the
    transformer LM — the memory plane's "activations" component
    (docs/memory.md), not a traffic figure.

    The model counts what autodiff keeps resident for backward, per
    token per layer: the two LN outputs + attention input/output
    (≈4·d), the qkv projections (3·d), and the gated MLP's gate/up/down
    intermediates (2·d_ff + d_ff ≈ 3·d_ff) — ≈(8·d + 3·d_ff)·bytes —
    plus the residual stream once and the [B,T,vocab] logits (fp32 when
    ``cfg.logits_fp32``). Flash/remat change the constant, not the
    shape; this is a planning estimate good to the tens of percent the
    ``hvd_mem --plan`` fit verdict needs, and the SAME formula feeds
    both the plan and the measured ledger so the two stay comparable.
    """
    if dtype_bytes is None:
        try:
            import numpy as np
            dtype_bytes = np.dtype(cfg.dtype).itemsize
        # hvdlint: disable=HVD006(exotic dtypes fall back to the bf16 default; the estimate stays an estimate)
        except Exception:
            dtype_bytes = 2
    tokens = batch_per_chip * seq
    per_layer = (8 * cfg.d_model + 3 * cfg.d_ff) * dtype_bytes
    logits_bytes = 4 if getattr(cfg, "logits_fp32", True) else dtype_bytes
    return int(tokens * (cfg.num_layers * per_layer
                         + cfg.d_model * dtype_bytes
                         + cfg.vocab_size * logits_bytes))


def roofline(costs, spec):
    """Per-class roofline verdicts: the time each resource needs and
    which one binds. ``costs`` is ``analytic_lm_costs``-shaped. Returns
    per-class dicts with ``bound_ms`` (the best achievable ms for the
    class), ``verdict`` in compute/memory/comm-bound, and the
    arithmetic intensity vs the chip's ridge point."""
    out = {}
    for cls, c in costs.items():
        t_compute = c.get("flops", 0.0) / spec.peak_flops
        t_memory = c.get("hbm_bytes", 0.0) / spec.hbm_bytes_per_s
        t_comm = c.get("wire_bytes", 0.0) / spec.ici_bytes_per_s
        bound_s, verdict = max(
            (t_compute, "compute-bound"),
            (t_memory, "memory-bound"),
            (t_comm, "comm-bound"))
        ai = (c.get("flops", 0.0) / c["hbm_bytes"]
              if c.get("hbm_bytes") else math.inf)
        out[cls] = {
            "flops": c.get("flops", 0.0),
            "hbm_bytes": c.get("hbm_bytes", 0.0),
            "wire_bytes": c.get("wire_bytes", 0.0),
            "compute_ms": round(t_compute * 1e3, 4),
            "memory_ms": round(t_memory * 1e3, 4),
            "comm_ms": round(t_comm * 1e3, 4),
            "bound_ms": round(bound_s * 1e3, 4),
            "verdict": verdict,
            # hvdlint: disable=HVD009(display formatting of an analytic flops/byte ratio that can be inf at bytes=0; no tensor is touched)
            "arith_intensity": round(ai, 2) if math.isfinite(ai) else None,
            "ridge_flops_per_byte": round(spec.ridge_flops_per_byte, 2),
        }
    return out


# profile_decomposition class → cost-model class (the four flash
# kernel classes are one analytic "attention"; copies/fusions/other are
# modeled as pure HBM traffic under "other")
_PROFILE_TO_MODEL = {
    "flash_fwd": "attention", "flash_dq": "attention",
    "flash_dkv": "attention", "flash_bwd": "attention",
    "matmul": "matmul", "collective": "collective",
}


def measured_class_ms(decomposition):
    """Fold a ``profile_decomposition`` dict's measured per-class ms
    into the cost-model classes (everything unmapped → "other")."""
    out = {}
    for c in (decomposition or {}).get("classes", ()):
        cls = _PROFILE_TO_MODEL.get(c["class"], "other")
        out[cls] = out.get(cls, 0.0) + c["ms_per_step"]
    return out


def mfu_decomposition(measured_ms_per_step, costs, spec,
                      measured_ms_by_class=None):
    """Measured vs roofline-bound MFU, with the gap attributed per
    class. MFU here is the headline convention: total model FLOPs over
    peak·time. ``roofline_ms`` is the sum of per-class bound times —
    the step time a perfectly scheduled, zero-overlap execution of this
    cost model would take (overlap can beat it; dispatch can't).

    When the measured per-class ms (``measured_class_ms`` of a real
    decomposition) is given, each class's ``excess_ms`` over its bound
    — plus the unattributed residual (wall minus accounted classes) —
    splits the MFU gap proportionally."""
    total_flops = sum(c.get("flops", 0.0) for c in costs.values())
    rl = roofline(costs, spec)
    roofline_ms = sum(c["bound_ms"] for c in rl.values())
    measured_mfu = (total_flops /
                    (spec.peak_flops * measured_ms_per_step / 1e3)
                    if measured_ms_per_step else None)
    roofline_mfu = (total_flops /
                    (spec.peak_flops * roofline_ms / 1e3)
                    if roofline_ms else None)
    out = {
        "flops_per_step": total_flops,
        "measured_ms_per_step": round(measured_ms_per_step, 3),
        "roofline_ms_per_step": round(roofline_ms, 3),
        "measured_mfu": round(measured_mfu, 4)
        if measured_mfu is not None else None,
        "roofline_mfu": round(roofline_mfu, 4)
        if roofline_mfu is not None else None,
        "classes": rl,
    }
    if measured_mfu is None or roofline_mfu is None:
        return out
    gap = roofline_mfu - measured_mfu
    out["mfu_gap"] = round(gap, 4)
    if measured_ms_by_class:
        excess = {}
        accounted = 0.0
        for cls, ms in measured_ms_by_class.items():
            bound = rl.get(cls, {}).get("bound_ms", 0.0)
            excess[cls] = max(ms - bound, 0.0)
            accounted += ms
        residual = measured_ms_per_step - accounted
        if residual > 0:
            excess["residual"] = residual
        total_excess = sum(excess.values())
        if total_excess > 0 and gap > 0:
            out["gap_by_class"] = {
                cls: round(gap * e / total_excess, 4)
                for cls, e in sorted(excess.items()) if e > 0}
    return out


def lm_attribution(cfg, seq, batch_per_chip, spec,
                   measured_ms_per_step, decomposition=None,
                   n_chips=1, wire_bytes_per_param=2.0):
    """One-call wrapper: analytic costs → roofline → MFU decomposition,
    folding in a measured ``profile_decomposition`` when one is at
    hand."""
    costs = analytic_lm_costs(cfg, seq, batch_per_chip, n_chips=n_chips,
                              wire_bytes_per_param=wire_bytes_per_param)
    by_class = measured_class_ms(decomposition) if decomposition else None
    out = mfu_decomposition(measured_ms_per_step, costs, spec,
                            measured_ms_by_class=by_class)
    out["chip"] = spec.as_dict()
    out["n_chips"] = n_chips
    return out
