"""chip_smoke.py on the CPU: the same leg functions the chip runs, at
TransformerConfig.tiny with interpreted kernels — plus the things only a
test can pin: main() refuses a CPU, imports create no backend, the
attention call sees its per-device shape under GSPMD, and Mosaic accepts
the kernels at the serving prefill lengths."""

import dataclasses
import functools
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402


@pytest.fixture
def tiny():
    """TransformerConfig.tiny cut to one layer: every leg compiles its
    model several times, and tier 1 pays for each."""
    import dataclasses
    import jax.numpy as jnp
    from horovod_tpu.models import transformer as tr
    return dataclasses.replace(
        tr.TransformerConfig.tiny(attention_impl="flash",
                                  dtype=jnp.float32), num_layers=1)


def _last_json(capsys):
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_main_refuses_a_cpu(capsys):
    assert chip_smoke.main([]) != 0
    captured = capsys.readouterr()
    assert captured.out == ""  # no result line without a chip
    assert "needs a TPU" in captured.err


def test_kernels_leg(hvd, capsys):
    import jax.numpy as jnp
    chip_smoke.leg_kernels([(1, 48, 2, 16)], atol=1e-4, dtype=jnp.float32)
    assert _last_json(capsys)["leg"] == "kernels"


def test_flash_timing_leg(hvd, capsys):
    """Both sides of the backward's rule run and agree; the times are the
    chip's (no TPU plane in a CPU trace)."""
    chip_smoke.leg_flash_timing(bh=2, s=64, d=16, block=32, on_chip=False)
    line = _last_json(capsys)
    assert line["leg"] == "flash_timing" and "kernel_ms" not in line
    assert max(line["max_abs_apart"].values()) < 3e-2


def test_flash_timing_leg_reads_each_sides_events(hvd, capsys, monkeypatch):
    """The timed branch on events shaped as the chip gives them: the named
    call is its own key, the unnamed dQ and dK/dV kernels are ONE key of two
    events under the jitted function's name; a two-kernel side that ran
    the one-pass kernel is refused."""
    def events(*sides):
        it = iter(sides)
        monkeypatch.setattr(chip_smoke, "mosaic_ms",
                            lambda fn, args, calls=4: next(it))

    events({"_lambda_": (2.0, 1)}, {"flash_backward": (4.0, 1)},
           {"_lambda_": (8.0, 2)})
    chip_smoke.leg_flash_timing(bh=2, s=64, d=16, block=32)
    line = _last_json(capsys)
    assert line["kernel_ms"] == {"forward": 2.0, "one_pass": 4.0,
                                 "two_kernel": 8.0}
    assert line["roofline_share"]["one_pass"] == pytest.approx(
        2 * line["roofline_share"]["two_kernel"], rel=1e-3)
    events({"_lambda_": (2.0, 1)}, {"flash_backward": (4.0, 1)},
           {"flash_backward": (4.0, 1)})
    with pytest.raises(AssertionError, match="events a call"):
        chip_smoke.leg_flash_timing(bh=2, s=64, d=16, block=32)


def test_sampler_timing_leg(capsys):
    """A greedy batch and one with a sampling row are both served their
    greedy rows' argmax; the times are the chip's."""
    chip_smoke.leg_sampler_timing(shapes=((4, 300), (3, 129)),
                                  on_chip=False)
    line = _last_json(capsys)
    assert line["leg"] == "sampler_timing"
    assert line["cases"] == [{"shape": [4, 300]}, {"shape": [3, 129]}]


@pytest.mark.parametrize("greedy_draws", [False, True])
def test_sampler_timing_leg_reads_the_draws_events(capsys, monkeypatch,
                                                   greedy_draws):
    """The timed branch on events shaped as the chip gives them (the lone
    sampler on a v5e, PR 51): the argmax is the fusion over the logits
    alone; the logits are copied into VMEM for the conditional's operand;
    ``%conditional`` is an event that SPANS its branch's events, and the
    draw is the fusion inside it that reads the logits AND the threefry
    counters. A greedy batch that runs the draw is refused."""
    from benchmarks.lib import xplane
    rows, vocab = 4, 300
    hbm = f"f32[{rows},{vocab}]{{1,0:T(8,128)}}"
    vmem = f"f32[{rows},{vocab}]{{1,0:T(8,128)S(1)}}"
    counters = f"u32[{rows}]{{0:T(128)S(1)}}"
    result = f"(bf16[{rows}]{{0:T(256)(128)(2,1)}}, s32[{rows}]{{0:T(128)}})"
    argmax = (f"%iota_reduce_fusion = {result} fusion({hbm} %logits.1), "
              "kind=kLoop, calls=%fused_computation.35")
    copy = (f"%copy-done = {vmem} copy-done(({vmem}, {hbm}, u32[]{{:S(2)}}) "
            "%copy-start)")
    cond = (f"%conditional = (s32[{rows}]{{0:T(128)}}) conditional("
            f"s32[]{{:T(128)}} %convert_element_type.1, (s32[{rows}]"
            f"{{0:T(128)}}) %tuple.17, (f32[{rows}]{{0:T(128)S(1)}}, {vmem}, "
            "u32[2]{0:T(128)S(1)}) %tuple.25), "
            "branch_computations={%region_2.4, %region_3.8}")
    bits = f"%fusion.27 = {counters} fusion(), kind=kLoop, calls=%fc.31"
    draw = (f"%fusion.5 = {result} fusion({vmem} %get-tuple-element.8, "
            f"{counters} %broadcast_add_fusion, {counters} %fusion.27, "
            f"f32[{rows}]{{0:T(128)S(1)}} %broadcast_maximum_fusion, "
            "u32[]{:T(128)S(6)} %xor.139), kind=kLoop, calls=%fc.19")

    def trace(drew, calls=4):
        """[(text, start us, us)] a call: 0.6 us of conditional where the
        branch returns the argmax, the draw's 460 inside it where not."""
        call = [(argmax, 1.0, 105.0), (copy, 106.0, 97.0)]
        call += [(cond, 203.0, 460.6), (bits, 203.1, 0.1),
                 (draw, 204.0, 459.5)] if drew else [(cond, 203.0, 0.6)]
        took = 1e-6 * (call[2][1] + call[2][2] + 1.0)
        ops, modules = [], []
        for c in range(calls):
            t0 = c * 1e-3
            ops += [xplane.Event(name, t0 + 1e-6 * at, t0 + 1e-6 * (at + us))
                    for name, at, us in call]
            modules.append(xplane.Event("jit_sample_tokens(7)", t0,
                                        t0 + took))
        return xplane.Trace({0: ops}, {0: modules}, [])
    traces = iter([trace(greedy_draws), trace(True)])
    monkeypatch.setattr(chip_smoke, "profiled",
                        lambda fn, args, calls=4: next(traces))
    if greedy_draws:
        with pytest.raises(AssertionError, match="sampler's events"):
            chip_smoke.leg_sampler_timing(shapes=((rows, vocab),))
        return
    chip_smoke.leg_sampler_timing(shapes=((rows, vocab),))
    (case,) = _last_json(capsys)["cases"]
    assert case["greedy"] == {"draw": 0.0, "argmax": 0.105, "call": 0.2046}
    assert case["one_row"] == {"draw": 0.4595, "argmax": 0.105,
                               "call": 0.6646}


def test_window_kernel_leg(capsys):
    import jax.numpy as jnp
    chip_smoke.leg_window_kernel([(1, 40, 4, 2, 16, 8), (2, 32, 3, 1, 16, 16)],
                                 atol=1e-4, dtype=jnp.float32)
    line = _last_json(capsys)
    assert line["leg"] == "window_kernel" and line["max_abs_err"] < 1e-4
    with pytest.raises(AssertionError, match="against masked attention"):
        chip_smoke.leg_window_kernel([(1, 32, 2, 1, 16, 8)], atol=-1.0,
                                     dtype=jnp.float32)


def test_packed_kernel_and_mamba1_legs(capsys, monkeypatch):
    """The new family's two kernel legs at sizes the CPU runs: the packed
    decode kernel (interpreted: the leg insists that the kernel is what
    runs) against the two-softmax definition over a plane and a ring, rows
    of length 0 and 1 among them; the scan against the literal definition
    over a right-padded prompt and the update in place, a masked row bit
    for bit."""
    from horovod_tpu.ops import flash_attention as fa
    with pytest.raises(AssertionError, match="is not selected"):
        chip_smoke.leg_packed_kernel([(1, 2, 128, 1, [5, 9])])
    monkeypatch.setattr(fa, "_on_one_tpu_chip", lambda: True)
    chip_smoke.leg_packed_kernel([(1, 4, 256, 2, [0, 1, 130, 256]),
                                  (2, 3, 128, 1, [128, 7, 64])])
    line = _last_json(capsys)
    assert line["leg"] == "packed_decode_kernel"
    assert [c["cache"] for c in line["cases"]] == [[1, 4, 256, 1, 256],
                                                   [2, 3, 128, 1, 128]]
    assert all(c["max_abs_err"] < 2e-2 for c in line["cases"])
    with pytest.raises(AssertionError, match="two-softmax"):
        chip_smoke.leg_packed_kernel([(1, 2, 128, 1, [5, 9])], atol=-1.0)
    chip_smoke.leg_mamba1(channels=128, states=4, rows=5, planes=3,
                          lengths=(64,))
    line = _last_json(capsys)
    assert line["leg"] == "mamba1" and line["scans"][0]["positions"] == 64
    assert line["update"]["state"] == [3, 5, 4, 128]
    assert line["update"]["max_abs_err"] < 1e-5


def test_grouped_kernel_leg(capsys):
    import jax.numpy as jnp
    # (tokens, k, experts, d, f, real tokens); the CPU: ragged_dot
    cases = [(24, 2, 8, 32, 16, 24), (40, 4, 16, 16, 32, 29)]
    chip_smoke.leg_grouped_kernel(cases, atol=1e-4, dtype=jnp.float32,
                                  products=["ragged_dot"] * 2)
    line = _last_json(capsys)
    assert line["leg"] == "grouped_kernel" and line["max_abs_err"] < 1e-4
    assert line["products"] == ["ragged_dot", "ragged_dot"]
    with pytest.raises(AssertionError, match="against the loop over"):
        chip_smoke.leg_grouped_kernel(cases[:1], atol=-1.0,
                                      dtype=jnp.float32)
    with pytest.raises(AssertionError, match="wanted"):
        chip_smoke.leg_grouped_kernel(cases[:1], dtype=jnp.float32,
                                      products=["streamed"])


def test_lm_train_and_four_chip_legs(tiny, capsys):
    import horovod_tpu as hvd
    try:
        first = chip_smoke.leg_lm_train(tiny, 4, 32, on_chip=False)
        line = _last_json(capsys)
        assert line["leg"] == "lm_train" and line["compiles"] == 1
        chip_smoke.leg_four_chips(tiny, 4, 32, first, on_chip=False,
                                  steps=2)
        line = _last_json(capsys)
        tp = line["layouts"]["dp2_tp2"]
        assert len(tp["tp_leaf_bytes_per_device"]) == 4
    finally:
        hvd.shutdown()


@pytest.mark.slow  # ~60 s: a bf16 ResNet-18 compiles slowly on the CPU
def test_resnet_leg(capsys):
    import horovod_tpu as hvd
    try:
        chip_smoke.leg_resnet(model="resnet18", batch=2, image_size=32,
                              steps=2)
        assert _last_json(capsys)["leg"] == "resnet"
    finally:
        hvd.shutdown()


@pytest.mark.parametrize("family", ["transformer", "hybrid", "looped",
                                    "latent_moe", "window_moe", "sambay"])
def test_serve_leg(tiny, capsys, family):
    """Every family through the one leg: a dense decoder, a model that
    keeps recurrent and convolution state beside its K/V, a stack that
    runs several times with K/V per (pass, layer) plane, and latent
    attention with dropless experts over ONE latent kind."""
    import jax.numpy as jnp
    if family == "hybrid":
        from horovod_tpu.models import hybrid
        tiny = hybrid.HybridConfig.tiny(chunk=8, dtype=jnp.float32)
    elif family == "looped":
        from horovod_tpu.models import looped
        tiny = looped.LoopedConfig.tiny(dtype=jnp.float32)
    elif family == "latent_moe":
        from horovod_tpu.models import latent_moe
        tiny = latent_moe.LatentMoEConfig.tiny(dtype=jnp.float32)
    elif family == "window_moe":  # window 8: every request wraps a ring
        from horovod_tpu.models import window_moe
        tiny = window_moe.WindowMoEConfig.tiny(dtype=jnp.float32)
    elif family == "sambay":  # ONE plane with two readers, rings, state
        from horovod_tpu.models import sambay
        tiny = sambay.SambaYConfig.tiny(dtype=jnp.float32)
    chip_smoke.leg_serve(tiny, slots=2, max_len=32, kv_block=8,
                         lengths=(3, 8, 12), tie_tol=1e-4, name=family)
    line = _last_json(capsys)
    assert line["model"] == family
    # the CPU backend: the einsum, whatever the kind
    assert line["decode_attention"] == {
        "kinds": {"latent_moe": ["latent"],
                  "window_moe": ["k", "v", "k_ring", "v_ring"],
                  "sambay": ["k", "v", "k_ring", "v_ring"]}.get(
                      family, ["k", "v"]),
        "kernel": False}
    # ...and ragged_dot for the two families with experts, in the decode
    # pass and in the prefill of both padded lengths (8, 8 and 16)
    assert line["experts"] == (
        {"kernel": False, "prefill": {"8": "ragged_dot", "16": "ragged_dot"}}
        if family in ("latent_moe", "window_moe") else None)
    assert line["greedy_exact"] + line["greedy_ties"] == line["tokens"]
    assert line["kv_in_place"] == 1
    assert line["steps_ahead"] > 0   # three requests on two slots
    # two of three in each round: an engine's first pass counts as one that
    # compiles, and what is unread is read before it (a counter of the
    # process, so a later family's line reads more)
    assert line["admissions_ahead"] >= 4


def test_a_failing_leg_fails_the_run(monkeypatch, tmp_path):
    """main() wraps no leg in try/except: what a leg raises reaches the
    interpreter as a non-zero exit."""
    import jax
    from horovod_tpu.utils import compile_cache

    class FakeTpu:
        platform, device_kind = "tpu", "TPU v5 lite"

    monkeypatch.setattr(jax, "devices", lambda *a: [FakeTpu()])
    # main() would switch this process's persistent compile cache on
    monkeypatch.setattr(compile_cache, "configure", lambda: str(tmp_path))

    def boom(*a, **k):
        raise AssertionError("leg failed")
    monkeypatch.setattr(chip_smoke, "leg_kernels", boom)
    with pytest.raises(AssertionError, match="leg failed"):
        chip_smoke.main(["--legs", "kernels"])


def test_imports_create_no_backend():
    """Importing the package, the launcher and the smoke itself must not
    initialise a JAX backend: a parent that did would hold the chip its
    children need (docs/tpus.md, "Chips vs processes")."""
    code = (
        "import horovod_tpu, horovod_tpu.run.cli, horovod_tpu.run.launch\n"
        "import horovod_tpu.utils.compile_cache as cc, chip_smoke\n"
        "cc.configure()\n"
        "from jax._src import xla_bridge\n"
        "assert not xla_bridge.backends_are_initialized()\n")
    subprocess.run([sys.executable, "-c", code], cwd=ROOT, check=True,
                   timeout=120)


def _pallas_operand_shapes(jaxpr, out):
    """Input shapes of every pallas_call in a (nested) jaxpr; inside a
    shard_map body these are per-device shapes."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append(tuple(eqn.invars[0].aval.shape))
        for val in eqn.params.values():
            for sub in (val if isinstance(val, (list, tuple)) else [val]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    _pallas_operand_shapes(inner, out)
    return out


@pytest.mark.parametrize("layout,per_device_bh", [
    (dict(dp=4), 2 * 4),         # batch 8/4, all 4 heads
    (dict(dp=2, tp=2), 4 * 2),   # batch 8/2, heads 4/2
])
def test_attention_runs_on_its_per_device_shape(tiny, layout, per_device_bh):
    """Under make_gspmd_step the flash kernels must be handed each chip's
    (batch/dp, heads/tp) slice: a bare pallas_call has no partitioning
    rule (Mosaic refuses it outright on a TPU)."""
    import jax
    import bench_common
    from horovod_tpu.parallel import mesh as mesh_mod

    mesh = mesh_mod.build_mesh(devices=jax.devices()[:4], **layout)
    step, params, opt_state, toks, _ = bench_common.build_transformer_step(
        mesh, 8, 64, cfg=tiny)
    shapes = _pallas_operand_shapes(
        jax.make_jaxpr(step)(params, opt_state, toks).jaxpr, [])
    head_dim = tiny.d_model // tiny.num_heads
    # a layer's forward and its one-pass backward, each on the slice
    assert len(shapes) == 2 * tiny.num_layers
    assert set(shapes) == {(per_device_bh, 64, head_dim)}


@pytest.fixture(scope="module")
def topo():
    """A described v5e host (libtpu, no device attached). Described here
    and nowhere at import: one process at a time may load libtpu, so only
    the worker that runs this file does. A compile for a described chip
    is written to JAX's persistent cache but cannot be read back without
    the chip, so the cache is off while these tests run."""
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no libtpu in this environment
        pytest.skip(f"no TPU topology available: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def test_mosaic_accepts_the_kernels_without_a_chip(topo):
    """Ahead-of-time compile for a v5e topology: the backward kernels at
    a sequence shorter than the 128-lane tile — the serving prefill
    lengths — were refused by Mosaic before flash_attention aligned its
    blocks."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    from horovod_tpu.ops.flash_attention import flash_attention

    sharding = SingleDeviceSharding(topo.devices[0])

    def grads(q, k, v):
        def loss(q, k, v):
            out = flash_attention(q, k, v, causal=True, interpret=False)
            return jnp.sum(out.astype(jnp.float32) ** 2)
        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    for seq in (16, 200):
        arg = jax.ShapeDtypeStruct((1, seq, 2, 64), jnp.bfloat16,
                                   sharding=sharding)
        jax.jit(grads).trace(arg, arg, arg).lower(
            lowering_platforms=("tpu",)).compile()


@pytest.mark.parametrize("shape, resident", [
    ((1, 4096, 4, 128), True),    # the training cell's rows
    ((1, 16384, 2, 128), False),  # past the VMEM budget: tiles streamed
    ((1, 512, 4, 128), True),     # a serving prefill: one k tile a row
    ((1, 1024, 2, 64), True),     # heads of 64, padded to the lane tile
])
def test_mosaic_accepts_the_forward_on_both_kv_paths(topo, shape, resident):
    """The one forward at the widths the cells run, compiled for a v5e:
    a head's whole K/V in VMEM beside the kernel's own buffers, and past
    that budget the streamed tiles."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    from horovod_tpu.ops import flash_attention as fa

    _, s, _, d = shape
    assert fa.kv_resident(s, -(-d // 128) * 128, jnp.bfloat16) is resident
    arg = jax.ShapeDtypeStruct(shape, jnp.bfloat16,
                               sharding=SingleDeviceSharding(topo.devices[0]))
    compiled = jax.jit(functools.partial(
        fa.flash_attention, causal=True, interpret=False)).trace(
            arg, arg, arg).lower(lowering_platforms=("tpu",)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("shape, one_pass", [
    ((1, 4096, 4, 128), True),    # the training cell's rows
    ((1, 8192, 2, 128), True),    # the longest bfloat16 rows inside it
    ((1, 16384, 2, 128), False),  # past the budget: the two kernels
    ((1, 1024, 2, 64), True),     # heads of 64, padded to the lane tile
])
def test_mosaic_accepts_the_backward_on_both_sides_of_its_rule(
        topo, shape, one_pass):
    """The backward at the widths the cells run, compiled for a v5e: one
    head's operands, gradients and float32 dQ accumulator in VMEM under
    the limit the call asks for, ONE custom call beside the forward's;
    past the budget the dQ and dK/dV kernels."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    from horovod_tpu.ops import flash_attention as fa

    _, s, _, d = shape
    assert fa.bwd_one_pass(s, s, -(-d // 128) * 128, jnp.bfloat16) is one_pass
    arg = jax.ShapeDtypeStruct(shape, jnp.bfloat16,
                               sharding=SingleDeviceSharding(topo.devices[0]))

    def grads(q, k, v):
        return jax.grad(lambda q, k, v: jnp.sum(fa.flash_attention(
            q, k, v, causal=True, interpret=False).astype(jnp.float32)),
            argnums=(0, 1, 2))(q, k, v)

    compiled = jax.jit(grads).trace(arg, arg, arg).lower(
        lowering_platforms=("tpu",)).compile()
    calls = compiled.as_text().count("custom_call_target=\"tpu_custom_call\"")
    assert calls == (2 if one_pass else 3)


@pytest.fixture
def decode_kernel(monkeypatch):
    """The decode program as a TPU backend traces it: attention as the
    Mosaic kernel (ops/flash_attention.py picks it from the backend, and
    this process's is the CPU). ``_decode_jit`` is one jit for the
    process, so what is traced here is dropped again."""
    from horovod_tpu.ops import flash_attention as fa
    from horovod_tpu.serving import engine as engine_mod
    monkeypatch.setattr(fa.jax, "default_backend", lambda: "tpu")
    engine_mod._decode_jit.clear_cache()
    yield
    engine_mod._decode_jit.clear_cache()


def _whole_copies(compiled, *arrays):
    """The compiled program's copies of whole arrays of these (dtype,
    shape)s."""
    import re
    hlo = {"bfloat16": "bf16", "float32": "f32"}
    return [c for dtype, dims in arrays for c in re.findall(
        re.escape(f"{hlo[str(dtype)]}[{','.join(map(str, dims))}]")
        + r"\S*\s+copy\(.*", compiled.as_text())]


@pytest.mark.parametrize("program", ["decode", "decode_kernel",
                                     "write_slot"])
def test_the_serving_programs_update_the_cache_in_place(topo, program,
                                                        request):
    """The TPU compiler's own word, at Baichuan-7B widths (32 heads of
    128, 16 slots x 1536; depth 2 so it compiles in seconds): both
    programs that rewrite the KV cache alias their cache outputs to the
    donated inputs, and neither holds a copy of a whole cache array —
    the 2 x 2 GB a step that the undonated programs moved at depth 10.
    ``decode_kernel``: the same with attention as the Mosaic kernel,
    which Mosaic accepts at these widths, one call a layer, and which
    reads its layer out of the whole cache without a copy of it (in
    either shape: the kernel sees rows of positions x heads)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    from horovod_tpu.models import transformer as tr
    from horovod_tpu.serving import engine as engine_mod

    layers, slots, max_len, heads, head_dim = 2, 16, 1536, 32, 128
    cfg = tr.TransformerConfig(
        vocab_size=64000, num_layers=layers, num_heads=heads,
        d_model=heads * head_dim, d_ff=11008, max_seq_len=4096,
        dtype=jnp.bfloat16, attention_impl="flash")
    one = SingleDeviceSharding(topo.devices[0])

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)
    kv = arr((layers, slots, max_len, heads, head_dim), jnp.bfloat16)
    state = {"k": kv, "v": kv}
    if program == "decode_kernel":
        request.getfixturevalue("decode_kernel")
    if program != "write_slot":
        params = jax.tree_util.tree_map(
            lambda a: arr(a.shape, jnp.bfloat16),
            jax.eval_shape(lambda k: tr.init_params(cfg, k)[1],
                           jax.random.PRNGKey(0)))
        lowered = engine_mod._decode_jit.lower(
            cfg, params, arr((slots,), jnp.int32), arr((slots,), jnp.int32),
            state, arr((slots,), jnp.float32), arr((slots,), jnp.bool_),
            arr((2,), jnp.uint32), arr((), jnp.int32))
    else:
        pk = arr((layers, 1, 1024, heads, head_dim), jnp.bfloat16)
        lowered = engine_mod._write_slot.lower(
            state, {"k": pk, "v": pk}, arr((), jnp.int32),
            arr((slots,), jnp.int32), arr((), jnp.int32))
    compiled = lowered.compile()
    cache_bytes = 2 * layers * slots * max_len * heads * head_dim * 2
    assert compiled.memory_analysis().alias_size_in_bytes == cache_bytes
    copies = _whole_copies(
        compiled, (kv.dtype, kv.shape),
        (kv.dtype, (layers, slots, max_len * heads, head_dim)))
    assert not copies, copies
    assert compiled.as_text().count("tpu_custom_call") == \
        (layers if program == "decode_kernel" else 0)


@pytest.mark.parametrize("program", ["decode_kernel", "prefill"])
def test_a_looped_stack_keeps_its_planes_in_place(topo, program, request):
    """The same word for a stack that runs several times (models/
    looped.py) at Ouro-2.6B widths (16 heads of 128, SwiGLU 5632, 32 slots
    x 1536; two layers x four passes so it compiles in seconds): the
    decode program aliases all EIGHT planes of K and V to its donated
    input, copies no whole array, and holds the decode-attention kernel
    once a WEIGHT layer, inside the one loop over the passes and under
    its scope; the prefill returns K/V for every plane and holds the flash
    kernel once a weight layer too."""
    import re

    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    from horovod_tpu.models import looped
    from horovod_tpu.serving import decode as serve_decode
    from horovod_tpu.serving import engine as engine_mod

    slots, max_len = 32, 1536
    cfg = looped.LoopedConfig(
        vocab_size=49152, num_layers=2, num_heads=16, d_model=2048,
        d_ff=5632, passes=4, rope_theta=1e6, max_seq_len=65536,
        dtype=jnp.bfloat16, attention_impl="flash")
    one = SingleDeviceSharding(topo.devices[0])

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)
    state = {k: arr(a.shape, a.dtype) for k, a in
             serve_decode.state_shapes(cfg, slots, max_len).items()}
    kv = state["k"]
    assert kv.shape == (8, slots, max_len, 16, 128)
    params = jax.tree_util.tree_map(
        lambda a: arr(a.shape, jnp.bfloat16),
        jax.eval_shape(lambda k: looped.init_params(cfg, k),
                       jax.random.PRNGKey(0)))
    # as a TPU backend traces both programs: the kernels compiled
    request.getfixturevalue("decode_kernel")
    if program == "prefill":
        engine_mod._prefill_jit.clear_cache()
        try:
            text = engine_mod._prefill_jit.lower(
                cfg, params, arr((1, 256), jnp.int32), arr((), jnp.int32),
                arr((), jnp.float32), arr((2,), jnp.uint32)
            ).compile().as_text()
        finally:
            engine_mod._prefill_jit.clear_cache()
        assert text.count("tpu_custom_call") == cfg.num_layers
        assert "bf16[8,1,256,16,128]" in text
        return
    compiled = engine_mod._decode_jit.lower(
        cfg, params, arr((slots,), jnp.int32), arr((slots,), jnp.int32),
        state, arr((slots,), jnp.float32), arr((slots,), jnp.bool_),
        arr((2,), jnp.uint32), arr((), jnp.int32)).compile()
    cache_bytes = 2 * 8 * slots * max_len * 16 * 128 * 2
    assert compiled.memory_analysis().alias_size_in_bytes == cache_bytes
    copies = _whole_copies(
        compiled, (kv.dtype, kv.shape),
        (kv.dtype, (8, slots, max_len * 16, 128)))
    assert not copies, copies
    text = compiled.as_text()
    calls = re.findall(r"%decode_attention[.\d]* = .*tpu_custom_call.*", text)
    assert len(calls) == cfg.num_layers == text.count("tpu_custom_call")
    assert all("hvd.loop.passes/while/body" in c for c in calls), calls[0]


def _grouped_calls(text, rows):
    """The compiled program's calls of the grouped SwiGLU kernel over
    ``rows`` assignments: each under ``hvd.moe.experts`` and with the
    three stacks AS THEY LIE among its operands; and no grouped product of
    XLA's own beside them."""
    import re
    from horovod_tpu.ops import grouped_matmul
    held = rows + grouped_matmul.room(rows)   # and the last window's room
    calls = re.findall(r"%grouped_swiglu[.\d]* = .*tpu_custom_call.*", text)
    for call in calls:
        assert re.match(rf"%grouped_swiglu[.\d]* = bf16\[{held},2048\]",
                        call) and "hvd.moe.experts" in call, call[:400]
        assert call.count("bf16[64,2048,1536]") == 2 and \
            call.count("bf16[64,1536,2048]") == 1, call[:400]
    assert "ragged-dot" not in text
    return len(calls)


@pytest.mark.parametrize("program", ["decode_kernel", "prefill"])
def test_a_latent_cache_is_read_in_place_and_experts_are_grouped(
        topo, program, request):
    """The TPU compiler's word for latent attention and dropless experts
    (models/latent_moe.py) at GLM-4.7-Flash widths (20 heads, ranks 768 /
    512, 192 + 64 and 256 wide heads, 64 experts of 1536 of which 4, one
    shared, 64 slots x 1536; one dense and one expert layer so it
    compiles in seconds). Decode: the ONE latent kind is aliased to the
    donated input and no whole copy of it is held; the latent kernel once
    a layer, reading the cache whole; the experts' grouped SwiGLU is ONE
    call of this repo's kernel (ops/grouped_matmul.py) under
    ``hvd.moe.experts`` that takes the three stacks as they lie (no copy,
    no re-laying of a stack) and XLA's own grouped product is gone; one
    int32 vector more comes back. Prefill: the flash kernel at head width
    256 once a layer, the row's latent of every layer, and the same one
    call for the experts over 1,024 assignments."""
    import re

    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    from horovod_tpu.models import latent_moe
    from horovod_tpu.serving import decode as serve_decode
    from horovod_tpu.serving import engine as engine_mod

    slots, max_len = 64, 1536
    cfg = latent_moe.LatentMoEConfig(
        vocab_size=154880, num_layers=2, d_model=2048, num_heads=20,
        q_rank=768, kv_rank=512, nope_dim=192, rope_dim=64, v_dim=256,
        rope_theta=1e6, d_ff=10240, first_dense=1, num_experts=64,
        experts_per_tok=4, shared_experts=1, d_expert=1536,
        route_scale=1.8, max_seq_len=202752, dtype=jnp.bfloat16,
        attention_impl="flash")
    one = SingleDeviceSharding(topo.devices[0])

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)
    state = {k: arr(a.shape, a.dtype) for k, a in
             serve_decode.state_shapes(cfg, slots, max_len).items()}
    latent = state["latent"]
    assert set(state) == {"latent"}
    assert latent.shape == (2, slots, max_len, 1, 640)
    params = jax.tree_util.tree_map(
        lambda a: arr(a.shape, jnp.bfloat16),
        jax.eval_shape(lambda k: latent_moe.init_params(cfg, k),
                       jax.random.PRNGKey(0)))
    request.getfixturevalue("decode_kernel")
    if program == "prefill":
        engine_mod._prefill_jit.clear_cache()
        try:
            text = engine_mod._prefill_jit.lower(
                cfg, params, arr((1, 256), jnp.int32), arr((), jnp.int32),
                arr((), jnp.float32), arr((2,), jnp.uint32)
            ).compile().as_text()
        finally:
            engine_mod._prefill_jit.clear_cache()
        flash = [line for line in text.splitlines()
                 if "tpu_custom_call" in line
                 and line.count("bf16[20,256,256]") >= 4]   # out, q, k, v
        assert len(flash) == cfg.num_layers, len(flash)
        assert "bf16[2,1,256,1,640]" in text
        assert _grouped_calls(text, rows=1024) == 1
        return
    compiled = engine_mod._decode_jit.lower(
        cfg, params, arr((slots,), jnp.int32), arr((slots,), jnp.int32),
        state, arr((slots,), jnp.float32), arr((slots,), jnp.bool_),
        arr((2,), jnp.uint32), arr((), jnp.int32)).compile()
    cache_bytes = 2 * slots * max_len * 640 * 2
    assert compiled.memory_analysis().alias_size_in_bytes == cache_bytes
    stacks = [(latent.dtype, (64, 2048, 1536)),
              (latent.dtype, (64, 1536, 2048))]
    copies = _whole_copies(compiled, (latent.dtype, latent.shape),
                           (latent.dtype, (2, slots, max_len, 640)), *stacks)
    assert not copies, copies
    text = compiled.as_text()
    calls = re.findall(r"%latent_decode_attention[.\d]* = .*tpu_custom_call.*",
                       text)
    assert len(calls) == cfg.num_layers
    assert all("hvd.mla.attend" in c and "bf16[2,64,1536,640]" in c
               for c in calls), calls[0][:400]
    assert _grouped_calls(text, rows=256) == 1
    for scope in ("hvd.moe.route", "hvd.moe.experts", "hvd.moe.shared"):
        assert scope in text, scope
    # what a pass routed comes back beside ids, positions and the cache
    shapes = [str(s.shape) for s in jax.tree_util.tree_leaves(
        compiled.out_info)]
    assert shapes.count("(2,)") == 1


@pytest.mark.parametrize("program", ["decode_kernel", "prefill"])
def test_two_classes_of_cache_are_read_in_place_by_one_kernel(topo, program,
                                                              request):
    """The TPU compiler's word for window and full attention layers with
    different head counts and 256 narrow experts (models/window_moe.py) at
    Laguna-XS.2 widths (48 and 64 query heads over 8 key/value heads of
    128, window 512, 256 experts of 512 of which 8, one shared, 64 slots x
    5120; one dense full layer and one window expert layer so it compiles
    in seconds). Decode: BOTH classes of K/V are aliased to the donated
    input and no whole copy of either, or of a stack, is held; the ONE
    decode kernel runs once a layer, at a group of 6 over the full plane
    and of 8 over the ring (640 entries a row), each read whole and in
    place; the experts' grouped SwiGLU is the Mosaic kernel
    (``grouped_matmul.selected`` at ``[256, 2048, 512]``: two experts are
    12.6 MB of the 48 MB it may fill) over 512 assignments, the stacks as
    they lie. Prefill of 512 tokens: the flash kernel on the full layer,
    the banded kernel on the window layer with K/V at their own 8 heads,
    the ring's row is the window and no more, and 4,096 assignments are
    the last whose rows the kernel keeps in VMEM; a prompt of 1,024 (8,192
    assignments, and every longer one of the cell up to 4,096 tokens) is
    the SAME kernel with the rows streamed through it: no prefill program
    holds a ``ragged-dot``."""
    import re

    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    from horovod_tpu.models import window_moe
    from horovod_tpu.ops import grouped_matmul
    from horovod_tpu.serving import decode as serve_decode
    from horovod_tpu.serving import engine as engine_mod

    slots, max_len = 64, 5120
    cfg = window_moe.WindowMoEConfig(
        vocab_size=100352, d_model=2048, head_dim=128, num_kv_heads=8,
        layer_types=("full", "window"), heads_per_layer=(48, 64),
        window=512,
        rope_full=window_moe.Rotary(
            theta=500000.0, fraction=0.5, factor=64.0, original_len=4096,
            beta_fast=64.0, beta_slow=1.0,
            attention_factor=1.4158883083359672),
        rope_window=window_moe.Rotary(theta=10000.0), d_ff=8192,
        first_dense=1, num_experts=256, experts_per_tok=8, d_expert=512,
        d_shared=512, route_scale=2.5, max_seq_len=262144,
        dtype=jnp.bfloat16, attention_impl="flash")
    one = SingleDeviceSharding(topo.devices[0])

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)
    state = {k: arr(a.shape, a.dtype) for k, a in
             serve_decode.state_shapes(cfg, slots, max_len).items()}
    assert {k: a.shape for k, a in state.items()} == {
        "k": (1, slots, max_len, 8, 128), "v": (1, slots, max_len, 8, 128),
        "k_ring": (1, slots, 640, 8, 128), "v_ring": (1, slots, 640, 8, 128)}
    params = jax.tree_util.tree_map(
        lambda a: arr(a.shape, jnp.bfloat16),
        jax.eval_shape(lambda k: window_moe.init_params(cfg, k),
                       jax.random.PRNGKey(0)))
    request.getfixturevalue("decode_kernel")
    stack = (256, 2048, 512)
    assert grouped_matmul.selected(slots * 8, stack, jnp.bfloat16)
    assert all(grouped_matmul.selected(s_pad * 8, stack, jnp.bfloat16)
               for s_pad in range(512, 4097, 512))   # the cell's prefills
    assert grouped_matmul.resident(512 * 8, 2048, 2)
    assert not grouped_matmul.resident(1024 * 8, 2048, 2)

    def grouped(text, rows):
        calls = re.findall(r"%grouped_swiglu[.\d]* = .*tpu_custom_call.*",
                           text)
        held = rows + grouped_matmul.room(rows)
        for call in calls:
            assert re.match(rf"%grouped_swiglu[.\d]* = bf16\[{held},2048\]",
                            call) and "hvd.moe.experts" in call, call[:400]
            assert call.count("bf16[256,2048,512]") == 2 and \
                call.count("bf16[256,512,2048]") == 1, call[:400]
        return len(calls)
    if program == "prefill":
        for s_pad in (512, 1024):   # rows resident, rows streamed
            engine_mod._prefill_jit.clear_cache()
            try:
                text = engine_mod._prefill_jit.lower(
                    cfg, params, arr((1, s_pad), jnp.int32),
                    arr((), jnp.int32), arr((), jnp.float32),
                    arr((2,), jnp.uint32)).compile().as_text()
            finally:
                engine_mod._prefill_jit.clear_cache()
            band = re.findall(
                r"%window_attention[.\d]* = .*tpu_custom_call.*", text)
            assert len(band) == 1 and "hvd.swa.attend" in band[0]
            assert f"bf16[64,{s_pad},128]" in band[0] and \
                band[0].count(f"bf16[8,{s_pad},128]") == 2   # K/V as they lie
            flash = [line for line in text.splitlines()
                     if "tpu_custom_call" in line and "hvd.full.attend" in line
                     and line.count(f"bf16[48,{s_pad},128]") >= 4]
            assert len(flash) == 1
            # what the row leaves: the padded prefix, and a window at most
            assert f"bf16[1,1,{s_pad},8,128]" in text
            assert "bf16[1,1,512,8,128]" in text
            assert grouped(text, s_pad * 8) == 1 and \
                "ragged-dot" not in text
        return
    compiled = engine_mod._decode_jit.lower(
        cfg, params, arr((slots,), jnp.int32), arr((slots,), jnp.int32),
        state, arr((slots,), jnp.float32), arr((slots,), jnp.bool_),
        arr((2,), jnp.uint32), arr((), jnp.int32)).compile()
    cache_bytes = 2 * slots * (max_len + 640) * 8 * 128 * 2
    assert compiled.memory_analysis().alias_size_in_bytes == cache_bytes
    copies = _whole_copies(
        compiled, (jnp.dtype(jnp.bfloat16), (1, slots, max_len, 8, 128)),
        (jnp.dtype(jnp.bfloat16), (1, slots, max_len * 8, 128)),
        (jnp.dtype(jnp.bfloat16), (1, slots, 640, 8, 128)),
        (jnp.dtype(jnp.bfloat16), (1, slots, 640 * 8, 128)),
        (jnp.dtype(jnp.bfloat16), stack),
        (jnp.dtype(jnp.bfloat16), (256, 512, 2048)))
    assert not copies, copies
    text = compiled.as_text()
    calls = re.findall(r"%decode_attention[.\d]* = .*tpu_custom_call.*",
                       text)
    assert len(calls) == 2
    full, = (c for c in calls if "hvd.full.attend" in c)
    ring, = (c for c in calls if "hvd.swa.attend" in c)
    # 48 query heads over the rows of 5,120, 64 over the rings of 640
    assert "bf16[64,48,128]" in full and \
        full.count(f"bf16[1,64,{max_len * 8},128]") == 2
    assert "bf16[64,64,128]" in ring and \
        ring.count(f"bf16[1,64,{640 * 8},128]") == 2
    assert grouped(text, 512) == 1 and "ragged-dot" not in text
    for scope in ("hvd.moe.route", "hvd.moe.experts", "hvd.moe.shared"):
        assert scope in text, scope
    shapes = [str(s.shape) for s in jax.tree_util.tree_leaves(
        compiled.out_info)]
    assert shapes.count("(2,)") == 1


@pytest.mark.parametrize("program", ["decode_kernel", "prefill"])
def test_one_plane_is_read_in_place_by_every_layer_that_shares_it(
        topo, program, request):
    """The TPU compiler's word for the decoder-hybrid-decoder
    (models/sambay.py) at Phi-4-mini-flash's widths (40 query and 20
    key/value heads of 64 as ten packed pairs of 128 lanes, window 512, a
    state of 16 x 5,120; 96 slots x 3,072; 8 layers, one of each kind's
    period, so that it compiles in seconds). Decode: all six kinds of state
    are aliased to the donated input and no whole copy of any is held; the
    packed decode kernel runs FOUR times (two rings of 640 entries, the
    ONE plane for the full layer and again for the cross layer), each
    reading its cache whole and in place as ``[.., 1280]`` rows; the state
    is held state-major ``[16, 5120]`` and updated by the Mosaic kernel,
    once a Mamba layer, the stacked array in and out as one buffer. Prefill of 256 tokens: the banded
    kernel on the two window layers with the pairs as they lie, the flash
    kernel on the full layer, the packed kernel for the cross layer's ONE
    query over the plane the row leaves, the scan kernel three times and no
    loop; the cross-decoder's products carry one token."""
    import re

    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    from horovod_tpu.models import sambay
    from horovod_tpu.serving import decode as serve_decode
    from horovod_tpu.serving import engine as engine_mod

    slots, max_len = 96, 3072
    cfg = dataclasses.replace(chip_smoke.phi4flash_small_config(),
                              max_seq_len=262144)
    one = SingleDeviceSharding(topo.devices[0])

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)
    state = {k: arr(a.shape, a.dtype) for k, a in
             serve_decode.state_shapes(cfg, slots, max_len).items()}
    assert {k: (a.shape, str(a.dtype)) for k, a in state.items()} == {
        "k": ((1, slots, max_len, 1, 1280), "bfloat16"),
        "v": ((1, slots, max_len, 1, 1280), "bfloat16"),
        "k_ring": ((2, slots, 640, 1, 1280), "bfloat16"),
        "v_ring": ((2, slots, 640, 1, 1280), "bfloat16"),
        "ssm": ((3, slots, 16, 5120), "float32"),
        "conv": ((3, slots, 3, 5120), "bfloat16")}
    params = jax.tree_util.tree_map(
        lambda a: arr(a.shape, jnp.float32 if a.ndim == 1 and
                      a.shape[0] == 5120 or a.shape == (16, 5120)
                      else jnp.bfloat16),
        jax.eval_shape(lambda k: sambay.init_params(cfg, k),
                       jax.random.PRNGKey(0)))
    request.getfixturevalue("decode_kernel")

    def packed(text):
        return re.findall(
            r"%packed_decode_attention[.\d]* = .*tpu_custom_call.*", text)
    if program == "prefill":
        engine_mod._prefill_jit.clear_cache()
        try:
            text = engine_mod._prefill_jit.lower(
                cfg, params, arr((1, 256), jnp.int32), arr((), jnp.int32),
                arr((), jnp.float32), arr((2,), jnp.uint32)) \
                .compile().as_text()
        finally:
            engine_mod._prefill_jit.clear_cache()
        band = re.findall(r"%window_attention[.\d]* = .*tpu_custom_call.*",
                          text)
        assert len(band) == 2 and all("hvd.diff.window" in b for b in band)
        # 40 packed query heads over the 10 pairs as they lie
        assert all("bf16[40,256,128]" in b and
                   b.count("bf16[10,256,128]") == 2 for b in band)
        flash = [line for line in text.splitlines()
                 if "tpu_custom_call" in line and "hvd.diff.full" in line]
        assert len(flash) == 1 and flash[0].count("bf16[40,256,128]") >= 4
        cross = packed(text)
        assert len(cross) == 1 and "hvd.diff.cross" in cross[0] and \
            cross[0].count("bf16[1,1,256,1280]") == 2
        scans = re.findall(r"%selective_scan[.\d]* = .*tpu_custom_call.*",
                           text)
        assert len(scans) == 3 and " while(" not in text
        assert all("hvd.mamba1.mix" in c and "f32[1,16,5120]" in c
                   for c in scans)
        # what the row leaves: the plane's padded prefix, rings of the
        # window at most, the state after the last real token
        for shape in ("bf16[1,1,256,1,1280]", "bf16[2,1,256,1,1280]",
                      "f32[3,1,16,5120]", "bf16[3,1,3,5120]"):
            assert shape in text, shape
        # the cross-decoder over ONE token: its gated unit's product
        assert "bf16[1,1,5120]" in text or "bf16[1,5120]" in text
        return
    compiled = engine_mod._decode_jit.lower(
        cfg, params, arr((slots,), jnp.int32), arr((slots,), jnp.int32),
        state, arr((slots,), jnp.float32), arr((slots,), jnp.bool_),
        arr((2,), jnp.uint32), arr((), jnp.int32)).compile()
    import math
    held = sum(math.prod(a.shape) * a.dtype.itemsize
               for a in state.values())
    assert compiled.memory_analysis().alias_size_in_bytes == held
    copies = _whole_copies(
        compiled, (jnp.dtype(jnp.bfloat16), (1, slots, max_len, 1, 1280)),
        (jnp.dtype(jnp.bfloat16), (1, slots, max_len, 1280)),
        (jnp.dtype(jnp.bfloat16), (2, slots, 640, 1, 1280)),
        (jnp.dtype(jnp.bfloat16), (2, slots, 640, 1280)),
        (jnp.dtype(jnp.float32), (3, slots, 16, 5120)))
    assert not copies, copies
    text = compiled.as_text()
    calls = packed(text)
    assert len(calls) == 4
    ring = [c for c in calls if "hvd.diff.window" in c]
    full = [c for c in calls if "hvd.diff.full" in c]
    cross = [c for c in calls if "hvd.diff.cross" in c]
    assert (len(ring), len(full), len(cross)) == (2, 1, 1)
    for c in ring:
        assert c.count(f"bf16[2,{slots},640,1280]") == 2
    for c in full + cross:   # the SAME plane, each with queries of its own
        assert c.count(f"bf16[1,{slots},{max_len},1280]") == 2
    updates = re.findall(
        r"%mamba1_state_update[.\d]* = .*tpu_custom_call.*", text)
    assert len(updates) == 3 and all(
        "hvd.mamba1.mix" in c and
        c.count(f"f32[3,{slots},16,5120]") >= 2 for c in updates)
    for scope in ("hvd.mamba1.mix", "hvd.gmu"):
        assert scope in text, scope


def test_init_names_the_process_that_holds_the_chip(monkeypatch):
    """What libtpu says when a second process opens a taken chip (seen on
    the v5e) reaches the user as the supported process shapes."""
    import jax
    import horovod_tpu as hvd

    def taken():
        raise RuntimeError(
            "Unable to initialize backend 'tpu': ABORTED: The TPU is "
            "already in use by process with pid 3921. Not attempting to "
            "load libtpu.so in this process.")
    monkeypatch.setattr(jax, "devices", taken)
    with pytest.raises(RuntimeError, match="pid 3921.*hvdrun -np <chips>"):
        hvd.init()
    assert not hvd.is_initialized()


@pytest.mark.parametrize("program", ["decode", "decode_kernel",
                                     "write_slot"])
def test_recurrent_state_is_updated_in_place_beside_the_kv(topo, program,
                                                           request):
    """The same word for a model that keeps recurrent and convolution
    state beside its K/V (models/hybrid.py; mixer 8 heads of 64 with a
    state of 128, 4 query and 2 key/value heads of 128; 8 slots x 512,
    depth 2): every kind of state is aliased to its donated input, and
    the float32 state is never copied whole. ``decode_kernel``: as a TPU
    backend traces it, with grouped-query attention AND the state's
    update (ops/ssm.py ``decode_update``) as Mosaic kernels, one call of
    each a layer, K, V and the state handed to them whole and uncopied."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    from horovod_tpu.models import hybrid
    from horovod_tpu.serving import decode as serve_decode
    from horovod_tpu.serving import engine as engine_mod

    slots, max_len = 8, 512
    cfg = hybrid.HybridConfig(
        vocab_size=4096, num_layers=2, d_model=512, d_ff=1024, num_heads=4,
        num_kv_heads=2, head_dim=128, ssm_heads=8, ssm_head_dim=64,
        ssm_state=128, ssm_groups=2, attention_impl="flash")
    one = SingleDeviceSharding(topo.devices[0])

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)
    state = {k: arr(a.shape, a.dtype) for k, a in
             serve_decode.state_shapes(cfg, slots, max_len).items()}
    if program == "decode_kernel":
        request.getfixturevalue("decode_kernel")
    if program != "write_slot":
        params = jax.tree_util.tree_map(
            lambda a: arr(a.shape, a.dtype),
            jax.eval_shape(lambda k: hybrid.init_params(cfg, k),
                           jax.random.PRNGKey(0)))
        lowered = engine_mod._decode_jit.lower(
            cfg, params, arr((slots,), jnp.int32), arr((slots,), jnp.int32),
            state, arr((slots,), jnp.float32), arr((slots,), jnp.bool_),
            arr((2,), jnp.uint32), arr((), jnp.int32))
    else:
        row = {k: arr((a.shape[0], 1) + ((256,) + a.shape[3:]
                                         if k in "kv" else a.shape[2:]),
                      a.dtype) for k, a in state.items()}
        lowered = engine_mod._write_slot.lower(
            state, row, arr((), jnp.int32), arr((slots,), jnp.int32),
            arr((), jnp.int32))
    compiled = lowered.compile()
    import math
    import re
    state_bytes = sum(math.prod(a.shape) * a.dtype.itemsize
                      for a in state.values())
    assert compiled.memory_analysis().alias_size_in_bytes == state_bytes
    ssm, k = state["ssm"], state["k"]
    copies = _whole_copies(
        compiled, (ssm.dtype, ssm.shape), (k.dtype, k.shape),
        (k.dtype, k.shape[:2] + (k.shape[2] * k.shape[3], k.shape[4])))
    assert not copies, copies
    text = compiled.as_text()
    for kernel in ("decode_attention", "state_update"):
        assert len(re.findall(rf"%{kernel}[.\d]* = .*tpu_custom_call",
                              text)) == \
            (cfg.num_layers if program == "decode_kernel" else 0)
    assert text.count("tpu_custom_call") == \
        (2 * cfg.num_layers if program == "decode_kernel" else 0)
