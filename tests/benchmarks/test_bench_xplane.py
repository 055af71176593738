"""The trace reduction, on the small trace recorded on a v5e
(``benchmarks/tools/record_fixture.py``; three iterations of a tiny step
with a matmul, a convolution, a Mosaic flash kernel and copies, then a
cache-row write, under the harness's dispatch and block spans)."""

import os

import pytest

from benchmarks.lib import xplane
from bench_tiny import REPO

FIXTURE = os.path.join(REPO, "benchmarks", "fixtures", "tiny.xplane.pb")


@pytest.fixture(scope="module")
def trace():
    return xplane.load(FIXTURE)


def test_it_finds_the_chip_its_ops_its_modules_and_the_harness_spans(trace):
    assert sorted(trace.ops) == [0] and len(trace.ops[0]) == 75
    assert len(trace.modules[0]) == 6
    names = {xplane.module_name(e.name) for e in trace.modules[0]}
    assert names == {"jit_tiny_step", "jit_write_row"}
    assert len(trace.span("dispatch")) == 3 and len(trace.span("block")) == 3


@pytest.mark.parametrize("text,op,cls", [
    ("%fusion.1 = bf16[16,2]{1,0:T(8,128)(2,1)S(1)} fusion(bf16[2]{0} %a), "
     "kind=kLoop, calls=%f", "fusion", "fusion"),
    ("%convert_bitcast_fusion = (bf16[1,256,256]{2,1,0:T(8,128)(2,1)S(1)}, "
     "f32[]{:T(128)}) fusion(bf16[256,512]{1,0} %x), kind=kOutput, calls=%f",
     "fusion", "matmul"),
    ("%convolution.3 = f32[8,8]{1,0} convolution(f32[8,8]{1,0} %a, "
     "f32[8,8]{1,0} %b), dim_labels=bf_io->bf", "convolution", "matmul"),
    ("%x.1 = (bf16[2,256,128]{2,1,0}, f32[2,8,256]{2,1,0}) "
     "custom-call(bf16[2,256,128]{2,1,0} %q), "
     "custom_call_target=\"tpu_custom_call\"", "custom-call", "mosaic"),
    ("%copy.2 = bf16[4,256,256]{2,1,0} copy(bf16[4,256,256]{2,1,0} %c)",
     "copy", "copy"),
    ("%all-reduce-start.1 = f32[8]{0} all-reduce-start(f32[8]{0} %g), "
     "replica_groups={}", "all-reduce-start", "collective"),
    ("%all-to-all.4 = (bf16[1,8]{1,0}, bf16[1,8]{1,0}) "
     "all-to-all(bf16[1,8]{1,0} %a, bf16[1,8]{1,0} %b)", "all-to-all",
     "collective"),
    ("%collective-permute-done = f32[2]{0} collective-permute-done("
     "(f32[2]{0}, f32[2]{0}) %s)", "collective-permute-done", "collective"),
])
def test_an_instructions_class_is_read_from_its_opcode(text, op, cls):
    assert xplane.opcode(text) == op
    assert xplane.op_class(text) == cls


def test_busy_time_is_the_union_of_op_intervals_inside_the_window(trace):
    t0, t1 = xplane.window_of(trace)
    busy = xplane.busy_seconds(trace)[0]
    classes = xplane.class_seconds(trace)
    assert 0 < busy < t1 - t0
    # the core runs one op at a time: the classes add up to the union
    assert sum(classes.values()) == pytest.approx(busy, rel=1e-6)
    assert set(classes) == {"copy", "fusion", "matmul", "mosaic"}
    # three runs of a 1.89 us Mosaic kernel, read by hand from the dump
    assert classes["mosaic"] == pytest.approx(3 * 1.89e-6, rel=0.02)
    gaps = xplane.idle_gaps(trace)
    assert sum(gaps.values()) == pytest.approx(t1 - t0 - busy, rel=1e-6)
    assert set(gaps) <= {"dispatch", "block", "untracked"}


def test_overlapping_intervals_are_counted_once():
    assert xplane.total(xplane.merge([(0, 2), (1, 3), (5, 6), (6, 6)])) == 4


def test_the_mosaic_call_gives_its_operand_shapes(trace):
    calls = [e for e in trace.ops[0] if xplane.op_class(e.name) == "mosaic"]
    assert len(calls) == 3
    inner = calls[0].name.partition("custom-call(")[2]
    assert [s for _, s in xplane.shapes(inner)][:3] == [(2, 256, 128)] * 3
    from benchmarks.lib.registry import Registry
    reader = Registry([REPO]).module("readers", "flash_roofline")
    assert reader.operands(calls[0].name) == 3          # q, k, v: a forward


def test_the_breakdown_has_at_most_ten_entries_a_list(trace):
    b = xplane.breakdown(trace)
    assert set(b) == {"device_ops", "idle_gaps"}
    assert 0 < len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    assert b["device_ops"][0][0] == "jit_tiny_step"
    assert all(isinstance(v, float) and v >= 0
               for _, v in b["device_ops"] + b["idle_gaps"])
