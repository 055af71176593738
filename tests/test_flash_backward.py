"""The flash backward (``ops/flash_attention._flash_bwd``) on both sides
of its rule: ONE kernel that takes dQ, dK and dV from one pass over a
head's tile pairs (``_bwd_kernel``, where the head's operands and a
float32 dQ accumulator fit VMEM: ``bwd_one_pass``, from the shapes
alone) and the two kernels that stand past that budget.

Every shape of this file fits the budget; the ``bwd`` fixture takes the
budget away to reach the two kernels, as ``tests/test_flash_forward.py``'s
``kv`` reaches the streamed forward. Gradients are held to XLA's
(``jax.vjp`` of an independent ``jax.nn.softmax`` reference, NOT
``full_attention``) within the tolerances that file asserts for the
forward's dtypes, and the two sides to each other.

``parallel/ring.py`` calls ``_flash_bwd`` a ring step with the MERGED
lse, causal on the diagonal step only, and rows of pairs in the future
masked by an lse of 1e30; on the CPU the ring takes a pure-jax twin
(``_pair_bwd_ref``), so its own tests never reach the kernel: that
contract is held here, on ``_flash_bwd`` itself.
"""

import numpy as np
import pytest

from tests.test_flash_forward import _TOL

_KERNELS = {"one_pass": ["flash_backward"],
            "two_kernel": ["_dq_kernel", "_dkv_kernel"]}


@pytest.fixture(params=["one_pass", "two_kernel"])
def bwd(request, monkeypatch):
    """Which side of ``bwd_one_pass`` a call of this file lands on."""
    from horovod_tpu.ops import flash_attention as fa
    if request.param == "two_kernel":
        monkeypatch.setattr(fa, "_BWD_ONE_PASS_BYTES", 0)
    return request.param


def _operands(seed, b, sq, sk, h, d, dtype, layout):
    import jax.numpy as jnp
    rng = np.random.default_rng(seed)
    shape = {"bshd": lambda s: (b, s, h, d), "bhsd": lambda s: (b, h, s, d)}[
        layout]
    return [jnp.asarray(rng.normal(size=shape(s)) * 0.5, getattr(jnp, dtype))
            for s in (sq, sk, sk, sq)]          # q, k, v and the cotangent


def _reference(q, k, v, g, causal, layout):
    """(out, lse, dq, dk, dv) from XLA: float32 logits, ``jax.nn.softmax``,
    the mask in LOCAL positions from the top left as the kernels lay it
    (``k_pos <= q_pos``: what a ring step's diagonal pair needs)."""
    import jax
    import jax.numpy as jnp
    eq = {"bshd": ("bqhd,bkhd->bhqk", "bhqk,bkhd->bqhd"),
          "bhsd": ("bhqd,bhkd->bhqk", "bhqk,bhkd->bhqd")}[layout]
    axis = 1 if layout == "bshd" else 2
    sq, sk = q.shape[axis], k.shape[axis]
    mask = jnp.asarray(np.tril(np.ones((sq, sk), bool)))

    def logits(q, k):
        s = jnp.einsum(eq[0], q.astype(jnp.float32),
                       k.astype(jnp.float32)) * (q.shape[-1] ** -0.5)
        return jnp.where(mask, s, -jnp.inf) if causal else s

    def attend(q, k, v):
        return jnp.einsum(eq[1], jax.nn.softmax(logits(q, k), axis=-1),
                          v.astype(jnp.float32))

    out, vjp = jax.vjp(attend, q, k, v)
    lse = jax.nn.logsumexp(logits(q, k), axis=-1)       # [b, h, sq]
    return (out, lse) + vjp(g.astype(jnp.float32))


def _assert_close(got, want, dtype, names=("dq", "dk", "dv")):
    rtol, atol = _TOL[dtype]
    for name, a, b in zip(names, got, want):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert np.isfinite(a).all(), name
        np.testing.assert_allclose(a, b, rtol=rtol, atol=atol, err_msg=name)


def _kernel_calls(fn, *args):
    """(name, operands) of the pallas calls a traced function makes, in
    order."""
    import jax

    def walk(jaxpr, out):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                out.append((eqn.params["jaxpr"].debug_info.func_name,
                            len(eqn.invars)))
            for sub in eqn.params.values():
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    walk(inner, out)
        return out
    return walk(jax.make_jaxpr(fn)(*args).jaxpr, [])


class TestBothSidesOfTheRule:
    # (sq, sk, block): one tile a side, several, and sq != sk both ways
    @pytest.mark.parametrize("sq, sk, block", [
        (64, 64, 64), (128, 128, 32), (64, 128, 32), (128, 64, 32)])
    @pytest.mark.parametrize("layout", ["bshd", "bhsd"])
    @pytest.mark.parametrize("causal", [True, False])
    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    def test_gradients_are_xlas(self, hvd, bwd, dtype, causal, layout, sq,
                                sk, block):
        """dQ, dK, dV from the forward's own (out, lse) against XLA's."""
        from horovod_tpu.ops import flash_attention as fa
        q, k, v, g = _operands(sq + sk, 2, sq, sk, 2, 32, dtype, layout)
        out, lse = fa._flash_fwd(q, k, v, causal, block, block, True,
                                 layout=layout)
        got = fa._flash_bwd(q, k, v, out, lse, g, causal, block, block, True,
                            layout=layout)
        for t, like in zip(got, (q, k, v)):
            assert t.shape == like.shape and t.dtype == like.dtype
        _assert_close(got, _reference(q, k, v, g, causal, layout)[2:], dtype)

    @pytest.mark.parametrize("block_q, block_k", [(32, 64), (64, 32)])
    def test_unequal_blocks(self, hvd, bwd, block_q, block_k):
        """A k block that spans two q blocks and the other way round: the
        first q block a causal k block is seen from."""
        from horovod_tpu.ops import flash_attention as fa
        q, k, v, g = _operands(7, 1, 128, 128, 2, 32, "float32", "bhsd")
        out, lse = fa._flash_fwd(q, k, v, True, block_q, block_k, True,
                                 layout="bhsd")
        got = fa._flash_bwd(q, k, v, out, lse, g, True, block_q, block_k,
                            True, layout="bhsd")
        _assert_close(got, _reference(q, k, v, g, True, "bhsd")[2:],
                      "float32")

    def test_the_scale_is_the_callers(self, hvd, bwd):
        """``scale`` where it is not ``head_dim ** -0.5`` (models/sambay.py)
        goes onto the logits, dQ and dK alike."""
        import jax
        import jax.numpy as jnp
        from horovod_tpu.ops.flash_attention import flash_attention
        q, k, v, g = _operands(8, 1, 64, 64, 2, 32, "float32", "bshd")

        def loss(attend):
            return jax.grad(lambda q, k, v: jnp.sum(attend(q, k, v) * g),
                            argnums=(0, 1, 2))(q, k, v)

        def plain(q, k, v):
            s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * 0.3
            s = jnp.where(jnp.tril(jnp.ones((64, 64), bool)), s, -jnp.inf)
            return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)

        got = loss(lambda q, k, v: flash_attention(
            q, k, v, causal=True, block_q=32, block_k=32, scale=0.3))
        _assert_close(got, loss(plain), "float32")


class TestTheTwoSidesAgree:
    @pytest.mark.parametrize("dtype, tol", [("float32", 2e-6),
                                            ("bfloat16", 2e-2)])
    @pytest.mark.parametrize("causal", [True, False])
    def test_one_pass_is_the_two_kernels(self, hvd, monkeypatch, dtype, tol,
                                         causal):
        """The same (out, lse, dO) through both: in float32 they differ by
        the order of a few sums, in bfloat16 by a rounding of p and dS."""
        from horovod_tpu.ops import flash_attention as fa
        q, k, v, g = _operands(3, 2, 128, 128, 2, 32, dtype, "bhsd")
        out, lse = fa._flash_fwd(q, k, v, causal, 32, 32, True,
                                 layout="bhsd")
        args = (q, k, v, out, lse, g, causal, 32, 32, True)
        one = fa._flash_bwd(*args, layout="bhsd")
        monkeypatch.setattr(fa, "_BWD_ONE_PASS_BYTES", 0)
        two = fa._flash_bwd(*args, layout="bhsd")
        for a, b in zip(one, two):
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b, np.float32),
                                       rtol=tol, atol=tol)


class TestTheRule:
    @pytest.mark.parametrize("sq, sk, d, dtype, one_pass", [
        (4096, 4096, 128, "bfloat16", True),    # the training cell
        (4096, 4096, 128, "float32", True),
        (1024, 1024, 128, "bfloat16", True),    # chip_smoke's lm_train leg
        (8192, 8192, 128, "bfloat16", True),
        (16384, 16384, 128, "bfloat16", False),  # long context
        (8192, 8192, 128, "float32", False),
        (2048, 16384, 128, "bfloat16", True),   # a ring step's shard pair
        (16384, 2048, 256, "bfloat16", False),
    ])
    def test_the_shapes_alone_decide(self, hvd, sq, sk, d, dtype, one_pass):
        from horovod_tpu.ops import flash_attention as fa
        assert fa.bwd_one_pass(sq, sk, d, dtype) is one_pass

    def test_a_call_takes_what_the_rule_says(self, hvd, bwd):
        """One Mosaic call, named, on FIVE operands where the head fits;
        the two kernels past the budget. Nothing else is looked at."""
        import jax
        import jax.numpy as jnp
        from horovod_tpu.ops import flash_attention as fa
        q, k, v, _ = _operands(1, 1, 64, 64, 2, 32, "float32", "bshd")
        assert fa.bwd_one_pass(64, 64, 32, "float32") is (bwd == "one_pass")

        def grads(q, k, v):
            return jax.grad(lambda q, k, v: jnp.sum(fa.flash_attention(
                q, k, v, block_q=32, block_k=32) ** 2), (0, 1, 2))(q, k, v)

        calls = _kernel_calls(grads, q, k, v)
        assert calls[1:] == [(name, 5 if bwd == "one_pass" else 6)
                             for name in _KERNELS[bwd]], calls

    def test_the_counter_reads_once_a_layer(self, hvd, bwd):
        """``hvd_flash_backward_traced_total{kernel}``: one a layer a
        trace of the training step, on the side the shapes chose."""
        import jax
        from horovod_tpu.models import transformer as tr
        from horovod_tpu.utils import metrics as hvd_metrics
        cfg = tr.TransformerConfig.tiny(attention_impl="flash")
        model = tr.TransformerLM(cfg)
        toks = jax.numpy.zeros((2, 32), jax.numpy.int32)
        params = model.init(jax.random.PRNGKey(0), toks)["params"]
        reg = hvd_metrics.reset(enabled=True)
        try:
            jax.make_jaxpr(jax.grad(tr.lm_loss_fn(model)))(params, toks)
            fam = reg.counter("hvd_flash_backward_traced_total",
                              labels=("kernel",))
            other = "two_kernel" if bwd == "one_pass" else "one_pass"
            assert fam.labels(kernel=bwd).value == cfg.num_layers
            assert fam.labels(kernel=other).value == 0
        finally:
            hvd_metrics.reset()


class TestRingAttentionsContract:
    """What ``parallel/ring.py``'s backward hands ``_flash_bwd`` a ring
    step: the merged lse through ``_lse_to_kernel``, ``causal`` only on
    the diagonal step, rows of a future pair masked by an lse of 1e30."""

    @staticmethod
    def _shards(dtype="float32"):
        """Two shards of 64 of one causal sequence of 128: the second
        shard's queries against both shards' keys, with the attention
        over all 128 keys as the reference."""
        q, k, v, g = _operands(21, 1, 128, 128, 2, 32, dtype, "bshd")
        out, lse, dq, dk, dv = _reference(q, k, v, g, True, "bshd")
        return q, k, v, g, out, lse, dq, dk, dv

    def test_the_pairs_sum_to_the_whole_backward(self, hvd, bwd):
        """The diagonal pair (causal) and the past pair (``causal=False``),
        both with the lse of the WHOLE row: dQ is their sum, dK and dV of
        each shard its own pair's."""
        import jax.numpy as jnp
        from horovod_tpu.ops import flash_attention as fa
        from horovod_tpu.parallel import ring
        q, k, v, g, out, lse, dq, dk, dv = self._shards()
        late = slice(64, 128)
        kernel_lse = ring._lse_to_kernel(lse[:, :, late], 1, 2, 64)
        o = out[:, late].astype(q.dtype)
        diag = fa._flash_bwd(q[:, late], k[:, late], v[:, late], o,
                             kernel_lse, g[:, late], True, 32, 32, True)
        past = fa._flash_bwd(q[:, late], k[:, :64], v[:, :64], o,
                             kernel_lse, g[:, late], False, 32, 32, True)
        _assert_close([diag[0] + past[0]], [dq[:, late]], "float32", ["dq"])
        # the early shard's queries see only their own keys
        early = fa._flash_bwd(
            q[:, :64], k[:, :64], v[:, :64], out[:, :64].astype(q.dtype),
            ring._lse_to_kernel(lse[:, :, :64], 1, 2, 64), g[:, :64], True,
            32, 32, True)
        _assert_close([past[1] + early[1], past[2] + early[2]],
                      [dk[:, :64], dv[:, :64]], "float32", ["dk", "dv"])
        _assert_close(diag[1:], [dk[:, late], dv[:, late]], "float32",
                      ["dk", "dv"])
        assert jnp.allclose(early[0], dq[:, :64], atol=2e-5)

    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    def test_a_future_pair_gives_exact_zeros(self, hvd, bwd, dtype):
        """Rows whose lse is 1e30 give p exactly 0 INSIDE the kernel: no
        NaN although the pair's logits, which the merged lse never saw,
        would overflow ``exp``; rows left alone keep their gradients."""
        import jax.numpy as jnp
        from horovod_tpu.ops import flash_attention as fa
        from horovod_tpu.parallel import ring
        q, k, v, g, out, lse, *_ = self._shards(dtype)
        early, late = slice(0, 64), slice(64, 128)
        # the early queries against the LATE keys: a pair in the future,
        # with logits far above anything the early rows' lse holds
        k_future = (k[:, late].astype(jnp.float32) * 40).astype(k.dtype)
        o = out[:, early].astype(q.dtype)
        args = (q[:, early], k_future, v[:, late], o)
        masked = ring._lse_to_kernel(
            jnp.full_like(lse[:, :, early], 1e30), 1, 2, 64)
        got = fa._flash_bwd(*args, masked, g[:, early], False, 32, 32, True)
        for t in got:
            assert not np.asarray(t, np.float32).any()
        # half the rows masked: theirs are zero, the others' finite and
        # what the kernel gives with no row masked
        rows = jnp.arange(64) < 32
        half = jnp.where(rows, 1e30, lse[:, :, late])
        lse_late = ring._lse_to_kernel(lse[:, :, late], 1, 2, 64)
        args = (q[:, late], k[:, early], v[:, early],
                out[:, late].astype(q.dtype))
        got = fa._flash_bwd(*args, ring._lse_to_kernel(half, 1, 2, 64),
                            g[:, late], False, 32, 32, True)
        whole = fa._flash_bwd(*args, lse_late, g[:, late], False, 32, 32,
                              True)
        for t in got:
            assert np.isfinite(np.asarray(t, np.float32)).all()
        assert not np.asarray(got[0][:, :32], np.float32).any()
        np.testing.assert_array_equal(np.asarray(got[0][:, 32:], np.float32),
                                      np.asarray(whole[0][:, 32:],
                                                 np.float32))
