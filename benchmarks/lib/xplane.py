"""Reduction of a profiler trace (``.xplane.pb``) to what the per-layer
metrics read.  Nothing but JAX's own reader is needed.

What a TPU trace holds (looked at by hand on a v5e, PR 23): one plane per
chip, ``/device:TPU:<n>``, whose line ``XLA Modules`` has one event per
run of a jitted function (``jit_<name>(<hash>)``) and whose line
``XLA Ops`` has one event per executed HLO instruction, named by the
instruction's text (``%fusion.3 = bf16[..] fusion(...), kind=kOutput``).
The TensorCore runs those one at a time, so an instruction's class can be
read from its opcode: a convolution or dot, alone or at the root of a
``kOutput`` fusion, is a matmul; a ``tpu_custom_call`` is a Mosaic kernel;
``all-reduce``/``all-gather``/``all-to-all``/``collective-permute``/
``reduce-scatter`` (and their ``-start``/``-done`` halves, whose time on
this line is time the core waits) are collectives.  Host threads are lines
of the plane ``/host:CPU``; ``jax.profiler.TraceAnnotation`` spans appear
on the Python thread's line under their own name.  Host and device clocks
share one timeline to within about a millisecond.

Times are seconds from the start of the trace.
"""

import collections
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
SPAN_PREFIX = "bench."
COLLECTIVES = ("all-reduce", "all-gather", "all-to-all", "reduce-scatter",
               "collective-permute", "collective-broadcast")
COPIES = ("copy", "copy-start", "copy-done", "transpose", "bitcast", "pad",
          "slice", "dynamic-slice", "dynamic-update-slice", "concatenate",
          "reshape", "broadcast")

Event = collections.namedtuple("Event", "name start end")


class Trace:
    """ops / modules: {device index: [Event]}, spans: [Event] (host)."""

    def __init__(self, ops, modules, spans):
        self.ops, self.modules, self.spans = ops, modules, spans

    def span(self, name):
        return [s for s in self.spans if s.name == SPAN_PREFIX + name]


def find(tracedir):
    hits = sorted(glob.glob(os.path.join(tracedir, "**", "*.xplane.pb"),
                            recursive=True))
    if not hits:
        raise FileNotFoundError(f"no .xplane.pb under {tracedir}")
    return hits[-1]


def load(path):
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    ops, modules, spans = {}, {}, []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = int(m.group(1))
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops[dev] = _events(line)
                elif line.name == "XLA Modules":
                    modules[dev] = _events(line)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                spans.extend(e for e in _events(line, SPAN_PREFIX))
    spans.sort(key=lambda e: e.start)
    return Trace(ops, modules, spans)


def _events(line, prefix=None):
    out = []
    for ev in line.events:
        if prefix is not None and not ev.name.startswith(prefix):
            continue
        start = ev.start_ns * 1e-9
        out.append(Event(ev.name, start, start + ev.duration_ns * 1e-9))
    return out


# -- instruction text ---------------------------------------------------------

def opcode(text):
    """The opcode of an HLO instruction's text, ``""`` if it has none."""
    _, _, rest = text.partition(" = ")
    rest = rest or text
    if rest.startswith("("):  # tuple type: skip to its closing parenthesis
        depth = 0
        for i, ch in enumerate(rest):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                rest = rest[i + 1:].lstrip()
                break
    else:
        rest = rest.partition(" ")[2]
    m = re.match(r"([A-Za-z][A-Za-z0-9_\-]*)\(", rest)
    return m.group(1) if m else ""


def op_class(text):
    op = opcode(text)
    if op == "custom-call":
        return "mosaic" if "tpu_custom_call" in text else "custom-call"
    for c in COLLECTIVES:
        if op in (c, c + "-start", c + "-done"):
            return "collective"
    if op in ("convolution", "dot"):
        return "matmul"
    if op == "fusion":
        return "matmul" if "kind=kOutput" in text else "fusion"
    if op in COPIES:
        return "copy"
    return op or "other"


def module_name(text):
    """``jit__decode_jit(123)`` -> ``jit__decode_jit``."""
    return text.partition("(")[0]


def shapes(text):
    """Result and operand array shapes in an instruction's text, in
    order: [(dtype, (dims...)), ...]."""
    out = []
    for dtype, dims in re.findall(r"\b([a-z]+[0-9]+|pred)\[([0-9,]*)\]",
                                  text):
        out.append((dtype, tuple(int(d) for d in dims.split(",") if d)))
    return out


# -- interval arithmetic --------------------------------------------------------

def merge(intervals):
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def clip(events, t0, t1):
    return [(max(e.start, t0), min(e.end, t1)) for e in events
            if e.end > t0 and e.start < t1]


def total(intervals):
    return sum(e - s for s, e in intervals)


def window_of(trace):
    """[t0, t1] of the traced window: the harness's ``bench.window`` span
    when there is one, else the extent of the device events."""
    w = trace.span("window")
    if w:
        return w[0].start, w[-1].end
    ev = [e for evs in trace.ops.values() for e in evs]
    if not ev:
        raise ValueError("the trace holds no device operation")
    return min(e.start for e in ev), max(e.end for e in ev)


def busy_seconds(trace):
    """{device: seconds in which an operation ran}, inside the window:
    the union of the device's op intervals."""
    t0, t1 = window_of(trace)
    return {d: total(merge(clip(evs, t0, t1)))
            for d, evs in trace.ops.items()}


def class_seconds(trace, device=None):
    """{class: seconds} of device operations inside the window, summed
    over ``device`` (or averaged over all devices)."""
    t0, t1 = window_of(trace)
    devs = [device] if device is not None else sorted(trace.ops)
    out = collections.Counter()
    for d in devs:
        for e in trace.ops[d]:
            if e.end > t0 and e.start < t1 and opcode(e.name) != "while":
                out[op_class(e.name)] += (min(e.end, t1) - max(e.start, t0)) \
                    / len(devs)
    return dict(out)


def module_seconds(trace):
    """{jitted function: [durations]} inside the window, device 0."""
    t0, t1 = window_of(trace)
    out = collections.defaultdict(list)
    for d in sorted(trace.modules)[:1]:
        for e in trace.modules[d]:
            if e.start >= t0 and e.end <= t1:
                out[module_name(e.name)].append(e.end - e.start)
    return dict(out)


def idle_gaps(trace, device=None):
    """{harness span: idle seconds}: every gap in the device's busy time,
    charged to the innermost harness span open at the gap's middle
    (``untracked`` if none)."""
    t0, t1 = window_of(trace)
    device = sorted(trace.ops)[0] if device is None else device
    busy = merge(clip(trace.ops[device], t0, t1))
    edges = [t0] + [x for iv in busy for x in iv] + [t1]
    spans = [s for s in trace.spans if s.name != SPAN_PREFIX + "window"]
    out = collections.Counter()
    for a, b in zip(edges[0::2], edges[1::2]):
        if b <= a:
            continue
        mid, owner = (a + b) / 2, "untracked"
        width = None
        for s in spans:
            if s.start <= mid <= s.end and \
                    (width is None or s.end - s.start < width):
                owner, width = s.name[len(SPAN_PREFIX):], s.end - s.start
        out[owner] += b - a
    return dict(out)


def breakdown(trace, limit=10):
    """The result line's ``breakdown``: device time by jitted function and
    by op class, and idle gaps by harness span, largest first."""
    mods = {k: sum(v) for k, v in module_seconds(trace).items()}
    classes = {"op:" + k: v for k, v in class_seconds(trace).items()}
    ops = sorted(list(mods.items()) + list(classes.items()),
                 key=lambda kv: -kv[1])[:limit]
    gaps = sorted(idle_gaps(trace).items(), key=lambda kv: -kv[1])[:limit]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in gaps]}
