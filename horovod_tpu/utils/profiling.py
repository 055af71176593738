"""Device-trace summarization for `jax.profiler` captures.

The timeline (utils/timeline.py) answers "what did the *framework* do";
this module answers "where did the *device* time go" from a profiler
trace directory — the analysis loop used to find the round-2 wins
(tile-misaligned sequence dims, fp32 matmul operands, the flash-kernel
pipeline flush) without leaving Python:

    with jax.profiler.trace("/tmp/prof"):
        for _ in range(3):
            state = step(state, batch)
        jax.block_until_ready(state)
    from horovod_tpu.utils.profiling import summarize_trace
    for row in summarize_trace("/tmp/prof").top(20):
        print(row)

Works on the `*.trace.json.gz` files XLA writes under
``<dir>/plugins/profile/<ts>/``; host-side Python spans (``$``-prefixed)
and jit dispatch wrappers are excluded so the durations are device-op
time, not wall clock.

Beyond the per-op sums, ``summarize_trace`` retains every device op's
begin/end interval with its lane (the trace's pid/tid pair — on TPU one
lane per core stream, collectives often on their own async stream).
``overlap_accounting`` turns those into the comm/compute overlap
numbers (exposed vs hidden collective time, per-lane busy fractions)
that ``profile_decomposition`` embeds and the pod-scale overlap work is
judged against — a collective summed lane-blind is indistinguishable
from one on the critical path; a collective *interval* either is or is
not covered by concurrent compute.
"""

import collections
import glob
import gzip
import json
import os


class OpRow:
    __slots__ = ("name", "group", "total_ms", "count", "long_name")

    def __init__(self, name, group, total_ms, count, long_name):
        self.name = name
        self.group = group
        self.total_ms = total_ms
        self.count = count
        self.long_name = long_name

    def __repr__(self):
        extra = f"  {self.long_name[:80]}" if self.long_name else ""
        return (f"{self.total_ms:9.3f} ms  x{self.count:<4d} "
                f"{self.name[:40]:40s}{extra}")


class OpEvent:
    """One device-op occurrence: name + lane + [start, end) in ms."""

    __slots__ = ("name", "lane", "start_ms", "end_ms")

    def __init__(self, name, lane, start_ms, end_ms):
        self.name = name
        self.lane = lane
        self.start_ms = start_ms
        self.end_ms = end_ms


class TraceSummary:
    def __init__(self, rows, events=None, lane_names=None):
        self.rows = sorted(rows, key=lambda r: -r.total_ms)
        # per-occurrence intervals (OpEvent), lane-keyed by "pid/tid";
        # empty for summaries built from rows alone (pre-overlap callers)
        self.events = events or []
        self.lane_names = lane_names or {}

    @property
    def total_ms(self):
        return sum(r.total_ms for r in self.rows)

    def top(self, n=20):
        return self.rows[:n]

    def by_group(self):
        """Total ms per op family (fusion kinds, custom-call kernels,
        copies, ...) — the first place to look."""
        groups = collections.Counter()
        for r in self.rows:
            groups[r.group] += r.total_ms
        return groups.most_common()


_EXCLUDE_PREFIXES = ("$", "jit_", "Pjit", "np.", "PythonRefManager",
                     "ParseArguments", "PjRt", "Thunk")


def _is_device_op(name):
    if not name or name.startswith(_EXCLUDE_PREFIXES):
        return False
    if " " in name or name.isdigit():
        return False  # python stack frames / step-group lanes
    return True


def find_trace_file(path):
    """``path`` may be the profiler output dir or a trace file itself."""
    if os.path.isfile(path):
        return path
    hits = sorted(glob.glob(
        os.path.join(path, "**", "*.trace.json*"), recursive=True))
    if not hits:
        raise FileNotFoundError(
            f"no *.trace.json(.gz) under {path!r} — pass the directory "
            "given to jax.profiler.trace(...)")
    return hits[-1]  # newest capture


def summarize_trace(path):
    """Aggregate device-op durations from a profiler capture."""
    trace_file = find_trace_file(path)
    opener = gzip.open if trace_file.endswith(".gz") else open
    with opener(trace_file, "rt") as f:
        events = json.load(f).get("traceEvents", [])
    total = collections.Counter()
    count = collections.Counter()
    long_names = {}
    op_events = []
    lane_names = {}
    for e in events:
        if e.get("ph") == "M" and e.get("name") == "thread_name":
            lane = f"{e.get('pid', 0)}/{e.get('tid', 0)}"
            lane_names[lane] = (e.get("args") or {}).get("name", "")
            continue
        if e.get("ph") != "X" or "dur" not in e:
            continue
        name = e.get("name", "")
        if not _is_device_op(name):
            continue
        total[name] += e["dur"]
        count[name] += 1
        if not long_names.get(name):
            args = e.get("args") or {}
            long_names[name] = (args.get("long_name") or
                                args.get("hlo_op") or "")
        ts = e.get("ts", 0)
        op_events.append(OpEvent(
            name, f"{e.get('pid', 0)}/{e.get('tid', 0)}",
            ts / 1e3, (ts + e["dur"]) / 1e3))
    rows = [OpRow(n, n.split(".")[0], total[n] / 1e3, count[n],
                  long_names.get(n, ""))
            for n in total]
    return TraceSummary(rows, events=op_events, lane_names=lane_names)


# Op classes for profile_decomposition, first match wins (checked against
# the lowercased op name AND long_name). The flash kernels are matched by
# their Pallas kernel function names (the custom-call carries them);
# matmul/collective/copy classes follow XLA's HLO naming. Everything that
# matches nothing lands in "other" — the decomposition never drops time.
_OP_CLASSES = (
    ("flash_fwd", ("fwd_kernel",)),
    ("flash_dq", ("dq_kernel",)),
    ("flash_dkv", ("dkv_kernel",)),
    ("flash_bwd", ("flash_backward",)),   # the one-pass backward, by name
    ("collective", ("all-reduce", "allreduce", "all-gather", "allgather",
                    "reduce-scatter", "all-to-all", "collective",
                    "psum", "ppermute")),
    ("matmul", ("dot", "conv", "gemm", "matmul", "einsum")),
    ("copy", ("copy", "transpose", "bitcast", "memset", "dynamic-slice",
              "dynamic-update", "pad", "reshape", "concatenate", "slice")),
    ("fusion", ("fusion", "loop_", "input_", "output_")),
)

# the classes overlap_accounting treats as communication; everything
# else that is a device op counts as compute cover
_COMM_CLASSES = frozenset(("collective",))


def classify_op(row, classes=_OP_CLASSES):
    hay = (row.name + " " + (row.long_name or "")).lower()
    for cls, needles in classes:
        if any(n in hay for n in needles):
            return cls
    return "other"


def _merge_intervals(intervals):
    """Union of [start, end) intervals as a sorted disjoint list."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _span_ms(merged):
    return sum(e - s for s, e in merged)


def _intersect_ms(a, b):
    """Total overlap between two DISJOINT SORTED interval lists."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        s = max(a[i][0], b[j][0])
        e = min(a[i][1], b[j][1])
        if e > s:
            total += e - s
        if a[i][1] <= b[j][1]:
            i += 1
        else:
            j += 1
    return total


def overlap_accounting(summary, classes=_OP_CLASSES, steps=1,
                       comm_classes=_COMM_CLASSES):
    """Comm/compute overlap from a lane-aware capture: how much
    collective time was HIDDEN under concurrent compute (any compute
    lane busy at the same instant) vs EXPOSED on the critical path, and
    how busy each device lane was over the captured span.

    These are the exact numbers a comm-overlap optimization must move:
    bucketed allreduce launched during backward turns exposed_comm_ms
    into hidden_comm_ms; the summed per-class ms in the decomposition
    cannot tell the difference. Returns a plain dict (all ms divided by
    ``steps`` so it reads per step); None when the summary carries no
    intervals (a rows-only summary from an old caller).
    """
    summary = summary if isinstance(summary, TraceSummary) else \
        summarize_trace(summary)
    if not summary.events:
        return None
    class_of = {r.name: classify_op(r, classes) for r in summary.rows}
    comm_iv, compute_iv = [], []
    by_lane = collections.defaultdict(list)
    for ev in summary.events:
        iv = (ev.start_ms, ev.end_ms)
        (comm_iv if class_of.get(ev.name) in comm_classes
         else compute_iv).append(iv)
        by_lane[ev.lane].append(iv)
    comm = _merge_intervals(comm_iv)
    compute = _merge_intervals(compute_iv)
    comm_ms = _span_ms(comm)
    hidden = _intersect_ms(comm, compute)
    exposed = comm_ms - hidden
    span_start = min(s for s, _ in (comm + compute))
    span_end = max(e for _, e in (comm + compute))
    span = span_end - span_start
    lanes = []
    for lane in sorted(by_lane):
        busy = _span_ms(_merge_intervals(by_lane[lane]))
        lanes.append({
            "lane": lane,
            "name": summary.lane_names.get(lane, ""),
            "busy_ms_per_step": round(busy / steps, 3),
            "busy_frac": round(busy / span, 4) if span else None,
        })
    return {
        "comm_ms_per_step": round(comm_ms / steps, 3),
        "compute_ms_per_step": round(_span_ms(compute) / steps, 3),
        "hidden_comm_ms": round(hidden / steps, 3),
        "exposed_comm_ms": round(exposed / steps, 3),
        "overlap_frac": round(hidden / comm_ms, 4) if comm_ms else None,
        "span_ms_per_step": round(span / steps, 3),
        "lanes": lanes,
    }


def profile_decomposition(trace, wall_ms=None, steps=1,
                          classes=_OP_CLASSES, top_per_class=3):
    """Account for every millisecond of a step: group a capture's
    device-op time into op classes (flash kernels, matmuls, collectives,
    copies, fusions, other) and, when the wall time of the traced region
    is known, report the residual — wall minus device-busy, i.e. host
    dispatch + inter-op gaps, the part no per-op row can show. When the
    capture carries per-lane intervals the ``overlap`` block reports
    exposed vs hidden collective time (see ``overlap_accounting``).

    ``trace`` is a profiler dir / trace file / TraceSummary; ``wall_ms``
    the traced region's wall-clock PER STEP; ``steps`` how many steps the
    capture spans (all ms are divided by it, so the output reads in
    ms/step). Composes with merged_timeline.capture(profiler_dir=...):
    the same user-supplied dir feeds merge() (the visual, host + device
    on one clock) and this function (the arithmetic). Returns a plain
    dict.
    """
    summary = trace if isinstance(trace, TraceSummary) else \
        summarize_trace(trace)
    buckets = {}
    for row in summary.rows:
        buckets.setdefault(classify_op(row, classes), []).append(row)
    device_ms = summary.total_ms / steps
    per_class = []
    for cls, rows in sorted(buckets.items(),
                            key=lambda kv: -sum(r.total_ms for r in kv[1])):
        ms = sum(r.total_ms for r in rows) / steps
        per_class.append({
            "class": cls,
            "ms_per_step": round(ms, 3),
            "pct_of_device": round(100 * ms / device_ms, 1)
            if device_ms else 0.0,
            "top_ops": [
                {"name": r.name, "ms_per_step": round(r.total_ms / steps, 3),
                 "count": r.count}
                for r in sorted(rows, key=lambda r: -r.total_ms)
                [:top_per_class]],
        })
    out = {"device_ms_per_step": round(device_ms, 3),
           "classes": per_class, "steps": steps}
    if wall_ms:  # a zero/None wall is unusable: no residual, no frac —
        # a 0 here used to emit a nonsense residual of -device_ms
        out["wall_ms_per_step"] = round(wall_ms, 3)
        out["residual_ms_per_step"] = round(wall_ms - device_ms, 3)
        out["device_busy_frac"] = round(device_ms / wall_ms, 4)
    elif wall_ms is not None:
        out["wall_ms_per_step"] = 0.0
        out["residual_ms_per_step"] = None
        out["device_busy_frac"] = None
    overlap = overlap_accounting(summary, classes=classes, steps=steps)
    if overlap is not None:
        out["overlap"] = overlap
    # Memory plane (docs/memory.md): stamp the allocator peak alongside
    # the time decomposition, so a capture answers "was the slow step
    # also the big step" without a second tool. None off-TPU.
    from . import memory as memory_mod
    peak = memory_mod.step_peak_bytes()
    if peak is not None:
        out["peak_hbm_bytes"] = peak
    return out


def main(argv=None):
    import argparse
    p = argparse.ArgumentParser(
        description="Summarize device-op time from a jax.profiler trace")
    p.add_argument("path", help="profiler output dir or trace file")
    p.add_argument("-n", type=int, default=20, help="rows to print")
    p.add_argument("--decompose", action="store_true",
                   help="print the op-class decomposition instead")
    p.add_argument("--overlap", action="store_true",
                   help="print the comm/compute overlap accounting")
    p.add_argument("--wall-ms", type=float, default=None,
                   help="wall ms/step of the traced region (residual row)")
    p.add_argument("--steps", type=int, default=1,
                   help="steps the capture spans (output is per step)")
    args = p.parse_args(argv)
    summary = summarize_trace(args.path)
    if args.decompose:
        dec = profile_decomposition(summary, wall_ms=args.wall_ms,
                                    steps=args.steps)
        print(json.dumps(dec, indent=2))
        return
    if args.overlap:
        print(json.dumps(overlap_accounting(summary, steps=args.steps),
                         indent=2))
        return
    print(f"device-op total: {summary.total_ms:.1f} ms "
          f"({len(summary.rows)} distinct ops)")
    print("-- by group")
    for group, ms in summary.by_group()[:10]:
        print(f"{ms:9.3f} ms  {group}")
    print("-- top ops")
    for row in summary.top(args.n):
        print(row)


if __name__ == "__main__":
    try:
        main()
    except BrokenPipeError:  # `... | head` closed the pipe: not an error
        import sys
        sys.exit(0)
