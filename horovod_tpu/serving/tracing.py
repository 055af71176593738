"""Request-path tracing for the serving plane (docs/serving.md).

Every ``Request`` the admission queue accepts becomes ONE trace on the
tracing plane's shared clock (utils/tracing.py): a ``request`` root
span from arrival to terminal outcome, with child spans for each phase
the request actually spent time in —

    arrive -> queue_wait -> admit -> prefill -> decode_tick* ->
        retire | reject | evict

``queue_wait`` reopens on every KV-pressure requeue (the reopened span
carries ``requeue=True`` and its time is accounted separately), so a
request bounced off a full cache shows exactly where its budget went.
The fused per-step decode cost is recorded once per engine step as a
``decode_tick`` span — NOT once per slot per step, which would churn
the flight ring at batch_size x token_rate — and its duration is
attributed to every request active during the tick.

On retire the trace closes with a per-request latency decomposition in
milliseconds::

    queue_wait  submit -> first admission pop
    requeue     every later KV-pressure wait in the queue
    prefill     prompt pass + first-token sample
    decode      sum of the decode ticks the request was active for
    scheduler_stall  the residual: total minus everything above —
                admit-scan time, gauge refreshes, heartbeats, host gaps

The decomposition lands in three places: the root span's ``phase_ms``
attrs (what tools/hvd_slo.py digests out of a flight dump), the
``hvd_serve_phase_seconds{phase}`` histogram (what hvd_top renders
live), and the engine's serve_retire event (what the postmortem event
log shows). Because these are ordinary spans in the ordinary flight
ring, a ``serve_failover`` dump automatically contains every in-flight
request's open spans — hvd_postmortem names them — and the Perfetto
export lanes the closed ones per batch slot (hvd_slo --trace).

A request's trace says where the REQUEST waited; the inside of one
``ServeEngine.step()`` is the step record's (``StepTrace``): the step's
phases on the same clock, each also a ``jax.profiler.TraceAnnotation``
(``hvd.serve.<phase>`` under ``hvd.serve.step``) so that a profile shows
them on the device's timeline, kept as ONE record a step in the
tracer's step ring. A ``decode_tick`` span carries ``step=<seq>``, so a
request's trace names the steps it rode. Beside the phases the record
holds the step's ``launches``, one entry a dispatch of a device program
where it is made (a number that runs on across steps, the program's name
as a profile shows it, the host's call), and its ``reads``, one entry a
read-back naming the launch whose result it waited for: which program a
phase launched and which pass a ``decode_readback`` read are said, not
guessed, and the time the host KNEW the chip had nothing queued (every
launch read back, the next not yet made) can be added up from a flight
dump's ``steps`` alone (docs/tracing.md).

Default ON; ``HVD_SERVE_TRACE=0`` (or ``HVD_TRACE=0``) reduces every
call here to a shared null object; the engine reads the switch once a
step (``begin_step``). Measured cost on the v5e, tracing on against
``HVD_SERVE_TRACE=0`` on the same seeds: PERF.md §6, PR 24.

This module is the ONE sanctioned place for request timing in
``serving/`` — hvdlint HVD014 flags ad-hoc ``time.*`` deltas on
request objects anywhere else in the package.
"""

import contextlib

from jax.profiler import TraceAnnotation

from ..common import config
from ..utils import metrics as hvd_metrics
from ..utils import tracing as hvd_tracing

# phase keys of the per-request decomposition, reporting order
PHASES = ("queue_wait", "requeue", "prefill", "decode",
          "scheduler_stall")


def enabled():
    """Request tracing rides the tracing plane: both HVD_TRACE and
    HVD_SERVE_TRACE (default on) must be up."""
    return bool(hvd_tracing.get_tracer().enabled and
                config.env_bool("SERVE_TRACE", True))


def phase_histogram(reg=None):
    """The shared per-phase latency histogram (idempotent — the
    registry dedupes by name)."""
    reg = reg if reg is not None else hvd_metrics.get_registry()
    return reg.histogram(
        "hvd_serve_phase_seconds",
        "Per-request latency decomposition: seconds spent in each "
        "request-path phase (queue_wait/requeue/prefill/decode/"
        "scheduler_stall).", labels=("phase",),
        buckets=hvd_metrics.SERVE_PHASE_BUCKETS)


class RequestTrace:
    """Span lifecycle + phase accounting for one request.

    Created by ``begin()`` at submit; the queue drives the wait spans
    (pop/requeue/reject), the engine drives prefill/decode/retire.
    Spans are stored on the object and closed by the next lifecycle
    call — the sanctioned span-outlives-the-method pattern (hvdlint
    HVD008); a crash mid-request leaves them open on purpose, which is
    exactly how the failover dump shows in-flight work.
    """

    __slots__ = ("_tracer", "request_id", "trace_id", "root", "slot",
                 "requeues", "closed", "_wait", "_prefill", "_decode",
                 "_phase_us")

    def __init__(self, tracer, request_id):
        self._tracer = tracer
        self.request_id = request_id
        self.trace_id = tracer.new_trace_id(request_id)
        self.root = None
        self.slot = None
        self.requeues = 0
        self.closed = False
        self._wait = None
        self._prefill = None
        self._decode = None
        self._phase_us = {"queue_wait": 0.0, "requeue": 0.0,
                          "prefill": 0.0, "decode": 0.0,
                          "scheduler_stall": 0.0}

    # -- queue side --

    def on_submit(self):
        self.root = self._tracer.span(
            hvd_tracing.REQUEST, tensor=self.request_id,
            trace_id=self.trace_id)
        self._wait = self._tracer.span(
            hvd_tracing.QUEUE_WAIT, tensor=self.request_id,
            trace_id=self.trace_id, parent=self.root)
        return self

    def on_pop(self):
        """Admission pop: close the active wait span, crediting its
        duration to queue_wait (first wait) or requeue (later ones)."""
        w, self._wait = self._wait, None
        if w is not None:
            w.close()
            phase = "requeue" if w.attrs.get("requeue") else "queue_wait"
            self._phase_us[phase] += (w.end_us or 0) - w.start_us

    def on_requeue(self, reason="kv_pressure"):
        """KV pressure bounced the request back: reopen the wait lane,
        marked so its time is accounted as requeue, not queue_wait."""
        self.requeues += 1
        self._wait = self._tracer.span(
            hvd_tracing.QUEUE_WAIT, tensor=self.request_id,
            trace_id=self.trace_id, parent=self.root, requeue=True,
            reason=reason)

    def on_reject(self, reason):
        """Terminal rejection (queue_full / deadline / too_long):
        close out whatever is open and stamp the decomposition."""
        self.on_pop()
        return self._close("rejected", reason, status="error")

    # -- engine side --

    def on_prefill_start(self, slot, prompt_len):
        self.slot = slot
        self._prefill = self._tracer.span(
            hvd_tracing.PREFILL, tensor=self.request_id,
            trace_id=self.trace_id, parent=self.root, slot=slot,
            prompt_len=prompt_len)

    def on_prefill_end(self, ttft_s=None):
        """Prefill readback done: close the prefill span and open the
        slot-residency decode span (the Perfetto slot lane)."""
        p, self._prefill = self._prefill, None
        if p is not None:
            if ttft_s is not None:
                p.annotate(ttft_s=round(ttft_s, 6))
            p.close()
            self._phase_us["prefill"] += (p.end_us or 0) - p.start_us
        self._decode = self._tracer.span(
            hvd_tracing.DECODE, tensor=self.request_id,
            trace_id=self.trace_id, parent=self.root, slot=self.slot)

    def on_decode_tick(self, dur_us):
        """One fused engine step covered this request: attribute the
        tick's duration to its decode phase."""
        self._phase_us["decode"] += dur_us

    def annotate(self, **attrs):
        """Stamp attrs on the request's root span — e.g. the weight
        generation that admitted it (fleet plane, docs/fleet.md), so
        every flight dump attributes tokens to the weights that
        produced them."""
        if self.root is not None:
            self.root.annotate(**attrs)

    def on_retire(self, outcome, reason="", tokens=0):
        if self._decode is not None:
            self._decode.annotate(tokens=tokens)
        return self._close(outcome, reason,
                           status="ok" if outcome == "completed"
                           else "error")

    # -- close + decomposition --

    def _close(self, outcome, reason, status):
        if self.closed:
            return self.phase_ms()
        self.closed = True
        for s in (self._wait, self._prefill, self._decode):
            if s is not None and s.open:
                s.close()
        self._wait = self._prefill = self._decode = None
        if self.root is not None:
            total_us = max(
                (self._tracer.clock.ts_us() - self.root.start_us), 0.0)
            self._phase_us["scheduler_stall"] = max(
                total_us - sum(self._phase_us.values()), 0.0)
        phases = self.phase_ms()
        if self.root is not None:
            self.root.close(
                status=status, outcome=outcome, reason=reason,
                slot=self.slot, requeues=self.requeues,
                phase_ms=phases)
        hist = phase_histogram()
        for phase, ms in phases.items():
            hist.labels(phase=phase).observe(ms / 1e3)
        return phases

    def phase_ms(self):
        return {k: round(v / 1e3, 3) for k, v in self._phase_us.items()}


class _NullRequestTrace:
    """Absorbs the whole lifecycle when request tracing is off."""

    request_id = trace_id = slot = None
    requeues = 0
    closed = False

    def on_submit(self):
        return self

    def on_pop(self):
        pass

    def on_requeue(self, reason="kv_pressure"):
        pass

    def on_reject(self, reason):
        return {}

    def on_prefill_start(self, slot, prompt_len):
        pass

    def on_prefill_end(self, ttft_s=None):
        pass

    def on_decode_tick(self, dur_us):
        pass

    def annotate(self, **attrs):
        pass

    def on_retire(self, outcome, reason="", tokens=0):
        return {}

    def phase_ms(self):
        return {}


_NULL_TRACE = _NullRequestTrace()


def begin(request):
    """Mint the trace for a freshly submitted request. Idempotent for a
    live trace (a requeued request keeps its spans), but a CLOSED trace
    — the same Request object resubmitted, as A/B harnesses do — gets
    a fresh one: each submission is its own lifecycle. Called by
    AdmissionQueue.submit, so direct engine users get traced too."""
    trace = getattr(request, "trace", None)
    if trace is not None and trace is not _NULL_TRACE and \
            not trace.closed:
        return trace
    if not enabled():
        request.trace = _NULL_TRACE
        return _NULL_TRACE
    trace = RequestTrace(hvd_tracing.get_tracer(),
                         request.request_id).on_submit()
    request.trace = trace
    return trace


def trace_of(request):
    """The request's trace, or the shared null object — callers never
    branch on enablement."""
    trace = getattr(request, "trace", None)
    return trace if trace is not None else _NULL_TRACE


# -- engine-step spans ------------------------------------------------------

def heartbeat_span(**attrs):
    """One span per replica-liveness RPC (serving/replica.py): the
    heartbeat is a real per-step stall source — a slow control plane
    shows up here, not as mystery scheduler_stall."""
    if not enabled():
        return hvd_tracing._NULL_SPAN
    return hvd_tracing.get_tracer().span(hvd_tracing.HEARTBEAT, **attrs)


def route_span(**attrs):
    """One span per router dispatch decision (horovod_tpu/router/):
    which replica won, under which policy/affinity path, and whether
    this was a reroute after a replica loss — closed immediately, so
    the request's trace tree records where it was sent and why."""
    if not enabled():
        return hvd_tracing._NULL_SPAN
    return hvd_tracing.get_tracer().span(hvd_tracing.ROUTE, **attrs)


def slow_tick_us():
    """``HVD_SERVE_TRACE_SLOW_TICK_MS`` in µs; the engine reads it once,
    when it is built, and hands it to every ``finish_tick``."""
    return config.env_float("SERVE_TRACE_SLOW_TICK_MS", 250.0) * 1e3


def finish_tick(span, active_slots, slow_us, step):
    """Close a decode-tick span; returns its duration in µs (0 when
    tracing is off) and emits a ``slow_decode_tick`` event past
    ``slow_us`` (HVD_SERVE_TRACE_SLOW_TICK_MS) — the per-step analogue
    of the tracer's slow_span escalation. ``step``, the record the
    span came from (``tick_span``), says where the time went: the event
    carries ``step=<seq>``, ``where=<phase>`` and the program read or
    launched there (``StepTrace.slowest_since``)."""
    span.close(active=active_slots)
    if span.end_us is None:
        return 0.0
    dur_us = span.end_us - span.start_us
    if dur_us >= slow_us:
        reg = hvd_metrics.get_registry()
        if reg.enabled:
            reg.event("slow_decode_tick", active=active_slots,
                      dur_ms=round(dur_us / 1e3, 3),
                      **step.slowest_since(span.start_us))
    return dur_us


# -- the inside of one engine step --------------------------------------------

# Phase names of a step record; the benchmark's readers use them letter
# for letter (benchmarks/lib/step_phases.py). A step that admits and
# decodes launches everything before it reads anything: ``prefill`` (one a
# request), the two ``decode_`` launch phases, and only then one
# ``prefill_readback`` and ``bookkeeping`` a request. What each covers:
# docs/tracing.md.
STEP_PHASES = ("control", "admit", "prefill", "prefill_readback",
               "decode_prepare", "decode_dispatch", "decode_readback",
               "bookkeeping", "telemetry")
# counts of a step record, taken where the work happens
# state_rows / state_bytes: a model with a recurrent state only (0 else) —
# rows whose state the step's decode passes advanced (== active outside
# a hot swap), and bytes of that state the step's programs had to move
# (read + write per advanced row, one write per admitted row)
# ahead: 1 when the step returned with its decode pass in flight, its ids
# unread (serving/engine.py ``_due``); the NEXT step's ``decode_readback``
# then starts with that pass's ids. 0 on every other step that decoded.
# admitted_ahead: the step's admissions whose first token was read with a
# program of the step queued behind their prefill (``_read_first_tokens``):
# the step's decode pass, or a later admission's prefill where a step
# admits more than two. The chip ran on meanwhile: == admitted on every
# step that admits into a batch that decodes; less where nothing was
# launched behind the last one (a request that asks for one token into an
# idle batch, a row the ledger refused before the launch, a dispatch that
# compiles: what is unread is read before it)
# drew: 1 when a row of the step's decode pass(es) had a temperature above
# 0, so the program's sampler ran its categorical draw (threefry bits and
# Gumbel noise over rows x vocabulary, serving/sampling.py); 0 when every
# row was greedy and the pass drew nothing. The host's own knowledge of
# the requests' temperatures (``_decode``), no device read
# kv_bytes (not in STEP_COUNTS: only a step that decoded has it): bytes of
# K and V the step's decode passes had to stream, each decoding row's in
# whole blocks of ops/flash_attention.decode_block positions up to its
# length, over all planes (layers x passes); of a ring (K and V of a window
# layer, models/window_moe.py) up to min(length, window)
# window_kv_bytes (likewise, and only a model with rings): the rings' part
# of kv_bytes
# shared_kv_bytes (likewise, and only a model whose layers SHARE a plane,
# models/sambay.py): the part of kv_bytes that is the one full plane's,
# counted once a reading layer (it is held once)
# self_tokens, cross_tokens (only a step that admitted, and only that
# model): positions its prefills ran the self-decoder over (the padded
# prompts) and the cross-decoder over (one an admission)
# passes (not in STEP_COUNTS, likewise): stack passes the step's decode
# program runs each row, from the configuration: the passes of a looped
# stack (models/looped.py), 1 for every other model
# experts_touched, expert_tokens_max (not in STEP_COUNTS: only a model with
# experts, models/latent_moe.py, and only a step that READ a decode pass
# back): the (layer, expert) pairs that at least one decoding row of that
# pass was routed to, summed over the expert layers (what the pass had to
# read of the experts' weights), and the most assignments any one expert
# got. Counted on the device and read back with the pass's ids, so with a
# pass in flight they land in the record of the step that reads it, as its
# tokens do (``ahead``)
STEP_COUNTS = ("admitted", "active", "retired", "cohorts", "prompt_tokens",
               "state_rows", "state_bytes", "ahead", "admitted_ahead",
               "drew")
# Beside the phases a record holds two lists, in the order things happened:
# launches: [n, program, call_start_us, call_end_us], one entry a dispatch
# of a device program from the step (``StepTrace.launch``). ``n`` numbers
# the launches of a tracer's life, across steps, as ``seq`` numbers its
# steps; ``program`` is the name under which a profile shows the run, less
# ``jit_`` and the hash (``_decode_jit``, ``_prefill_jit``, ``_write_slot``:
# an admission is two entries, its prefill and its slot write; the prefill's
# key is folded on the host, serving/host_key.py, and launches nothing); the
# two times are the host's call: the chip runs launches in the order of
# their numbers.
# reads: [n, start_us, end_us], one entry a read-back (``StepTrace.read``):
# ``n`` is the launch whose result the ``device_get`` returned, possibly a
# launch of the step before (``ahead``); the times are those of the
# ``prefill_readback`` / ``decode_readback`` phase entry the read lies in.
_STEP_ANNOTATION = "hvd.serve.step"
_PHASE_ANNOTATIONS = {p: "hvd.serve." + p for p in STEP_PHASES}


class _Launch:
    """One dispatch being made (``StepTrace.launch``): entered it gives the
    launch's number, left it stamps the end of the host's call."""

    __slots__ = ("_clock", "_entry")

    def __init__(self, clock, entry):
        self._clock, self._entry = clock, entry

    def __enter__(self):
        return self._entry[0]

    def __exit__(self, exc_type, exc, tb):
        self._entry[3] = self._clock.ts_us()
        return False


class StepTrace:
    """The record of one ``ServeEngine.step()``, written from inside it.

    ``with step.phase(name):`` marks a phase. Phases do not nest; each
    starts where the one before it ended (ONE clock read a phase, at its
    end), so together they tile ``[start_us, end_us]`` with no hole: the
    few instructions between two ``with`` blocks go to the later one. A
    name may come several times in a step (one ``prefill`` per admitted
    request); two of one name in a row are one entry. Each phase is also
    a ``hvd.serve.<phase>`` TraceAnnotation and the step a
    ``hvd.serve.step`` one: free with no profile being taken, and under
    one the same phases on the profiler's clock. ``with step.launch(
    program) as n:`` goes around ONE dispatch of a device program, inside
    whatever phase it is made in (two clock reads), and ``step.read(n)``
    follows the readback phase that returned launch ``n``'s result (no
    clock read: the phase entry's times). ``finish()`` makes the step one
    record in the tracer's step ring (``Tracer.steps()``, the flight
    dump's ``steps``).
    """

    __slots__ = ("_tracer", "_clock", "_step_annotation",
                 "_phase_annotation", "_name", "seq", "start_us",
                 "end_us", "phases", "launches", "reads", "counts")

    def __init__(self, tracer):
        self._tracer = tracer
        self._clock = tracer.clock
        self.seq = tracer.next_step_seq()
        # a TraceAnnotation starts when it is made, not when entered
        self._step_annotation = TraceAnnotation(_STEP_ANNOTATION)
        self._phase_annotation = self._name = None
        self.start_us = self.end_us = self._clock.ts_us()
        self.phases = []
        self.launches = []
        self.reads = []
        self.counts = dict.fromkeys(STEP_COUNTS, 0)

    def phase(self, name):
        self._phase_annotation = TraceAnnotation(_PHASE_ANNOTATIONS[name])
        self._name = name
        return self

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        end = self._clock.ts_us()
        self._phase_annotation.__exit__(exc_type, exc, tb)
        if self.phases and self.phases[-1][0] == self._name:
            self.phases[-1][2] = end
        else:
            self.phases.append([self._name, self.end_us, end])
        self.end_us = end
        return False

    def count(self, name, n=1):
        self.counts[name] = self.counts.get(name, 0) + n

    def peak(self, name, n):
        """A count that several passes of one step do not add up to: the
        largest any of them read (a hot swap's cohorts; else one pass)."""
        self.counts[name] = max(self.counts.get(name, 0), n)

    def launch(self, program):
        """``with step.launch(program) as n:`` around the dispatch of one
        device program, where it is made; ``n`` is the launch's number,
        for the read-back that will wait for it."""
        start = self._clock.ts_us()
        entry = [self._tracer.next_launch_seq(), program, start, start]
        self.launches.append(entry)
        return _Launch(self._clock, entry)

    def read(self, n):
        """The readback phase that has just closed returned the result of
        launch ``n`` (``None``: launched with tracing off, not booked)."""
        if n is not None:
            _, start, end = self.phases[-1]
            self.reads.append([n, start, end])

    def slowest_since(self, ts_us):
        """Where the step's time went since ``ts_us``: ``step`` (its
        ``seq``), ``where`` (the longest phase entry that ended after
        ``ts_us``) and, when a read-back or a launch call lies in that
        entry, ``read`` or ``launch`` (its program)."""
        out = {"step": self.seq}
        covered = [p for p in self.phases if p[2] > ts_us]
        if not covered:
            return out
        out["where"], start, end = max(covered, key=lambda p: p[2] - p[1])
        reads = [r for r in self.reads if start <= r[1] and r[2] <= end]
        calls = [c for c in self.launches if start <= c[2] and c[3] <= end]
        if reads:
            out["read"] = self._program_of(reads[-1][0])
        elif calls:
            out["launch"] = max(calls, key=lambda c: c[3] - c[2])[1]
        return out

    def _program_of(self, n):
        """The program of launch ``n``: one of this step's, or (a pass
        read a step later) of the record before it."""
        before = self._tracer.steps()[-1:]
        for launches in [self.launches] + [r.get("launches", ())
                                           for r in before]:
            for entry in launches:
                if entry[0] == n:
                    return entry[1]
        return None

    def tick_span(self, **attrs):
        """The step's one ``decode_tick`` span (the engine-wide lane),
        naming the step record it belongs to."""
        return self._tracer.span(hvd_tracing.DECODE_TICK, step=self.seq,
                                 **attrs)

    def finish(self):
        self._step_annotation.__exit__(None, None, None)
        rec = {"seq": self.seq, "start_us": self.start_us,
               "end_us": self.end_us, "phases": self.phases,
               "launches": self.launches, "reads": self.reads}
        rec.update(self.counts)
        self._tracer.record_step(rec)


class _NullStepTrace:
    """Absorbs a whole step when request tracing is off: no clock read,
    no annotation, no record."""

    seq = None

    def phase(self, name):
        return self

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        return False

    def count(self, name, n=1):
        pass

    def peak(self, name, n):
        pass

    def launch(self, program):
        return _NULL_LAUNCH

    def read(self, n):
        pass

    def tick_span(self, **attrs):
        return hvd_tracing._NULL_SPAN

    def finish(self):
        pass


NULL_STEP = _NullStepTrace()
_NULL_LAUNCH = contextlib.nullcontext()  # entered it gives None: no number


def begin_step():
    """Open the record of the engine step that is starting; the ONE read
    of the switch in a step (its tick span comes from the record)."""
    if not enabled():
        return NULL_STEP
    return StepTrace(hvd_tracing.get_tracer())
