"""Closed-loop serving traffic: as many callers as slots, each sending its
next request when its last one ends, so completed tokens/s is the
engine's capacity and both tails are set by the engine and the length mix
and not by an arrival process.

Lengths are a fixed multiset: ``requests_per_cycle`` requests take that
many mid-quantiles of each clipped log-normal law (prompts, outputs);
``--seed`` permutes both lists independently, cycle after cycle, and draws
the token ids, so every seed offers the same work in another order.  All
requests are greedy.  Set-up warms every padded prefill shape of the
multiset through the engine itself, then lets the callers run for
``preroll_s`` so that the slots are out of step when the window opens.

The harness drives ``engine.step()`` from one thread on the engine's own
clock (``time.monotonic``): it stamps ``arrival_ts`` at submit and reads
``RequestResult.ttft_s`` and ``finish_ts``.  Percentiles are over the
requests that FINISHED inside the window (first token and last token both
stamped by the engine); tokens are counted per step as they are emitted.

After the window the engine's weights and cache are freed and the plain
reference scores a seeded sample of the finished requests, the longest
among them: the widest gap by which a served token's reference logit
lies below the reference's best.
"""

import gc
import math
import statistics
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.lib import stats
from benchmarks.lib import train_reference as tref
from benchmarks.lib import weights


def stratified_lengths(law, n):
    """``n`` mid-quantiles of a log-normal law clipped to [lo, hi]."""
    nd = statistics.NormalDist()
    out = []
    for i in range(n):
        z = nd.inv_cdf((i + 0.5) / n)
        v = law["median"] * math.exp(law["sigma"] * z)
        out.append(int(round(min(max(v, law["min"]), law["max"]))))
    return out


def request_stream(traffic, vocab, seed):
    """Endless (prompt tokens, max_new_tokens) from the fixed multiset."""
    n = traffic["requests_per_cycle"]
    prompts = stratified_lengths(traffic["prompt_tokens"], n)
    outputs = stratified_lengths(traffic["output_tokens"], n)
    rng = np.random.default_rng(int(seed))
    while True:
        for p, o in zip(rng.permutation(prompts), rng.permutation(outputs)):
            yield tuple(int(t) for t in rng.integers(0, vocab, int(p))), int(o)


def padded(n, block, max_len):
    return min(-(-n // block) * block, max_len)


class Generator:
    def __init__(self, run):
        self.run = run
        self.prog = None
        self.sent = {}       # request id -> (Request, submit ts)
        self.finished = []   # dicts, in finish order
        self.failed = 0
        self.attempted = 0
        self.serial = 0

    # -- callers -----------------------------------------------------------

    def _submit(self, caller):
        from horovod_tpu.serving.queue import Request
        prompt, new = next(self.stream)
        rid = f"c{caller}-{self.serial}"
        self.serial += 1
        req = Request(rid, prompt, max_new_tokens=new,
                      temperature=self.run.traffic["temperature"])
        req.arrival_ts = self.clock()
        self.attempted += 1
        if not self.prog.engine.submit(req):
            self.failed += 1
            return False
        self.sent[rid] = (req, caller)
        return True

    def _collect(self, results):
        """Book finished requests; each frees its caller for the next."""
        freed = []
        for res in results:
            req, caller = self.sent.pop(res.request_id)
            freed.append(caller)
            ok = res.outcome == "completed" and \
                len(res.tokens) == req.max_new_tokens
            if not ok:
                self.failed += 1
                self.run.log("request_failed", id=res.request_id,
                             outcome=res.outcome, reason=res.reason)
                continue
            first_ts = req.arrival_ts + res.ttft_s
            self.finished.append({
                "id": res.request_id, "prompt": req.prompt,
                "tokens": res.tokens, "submit_ts": req.arrival_ts,
                "ttft_s": res.ttft_s, "finish_ts": res.finish_ts,
                "tpot_s": stats.tpot_seconds(first_ts, res.finish_ts,
                                             len(res.tokens)),
                "queue_wait_ms": (res.phase_ms or {}).get("queue_wait")})
        return freed

    def _loop(self, seconds, record):
        """Drive the engine for ``seconds``; the callers' loop."""
        eng, run = self.prog.engine, self.run
        steps = []
        t_open = self.clock()
        while True:
            queued = len(eng.queue)
            t0 = self.clock()
            with run.span("engine.step"):
                done = eng.step()
            t1 = self.clock()
            admitted = queued - len(eng.queue)
            active = eng.active_count
            if record:
                # one token per admitted prefill, one per row decoded
                steps.append((t0, t1, admitted,
                              admitted + active + len(done), active,
                              self.live_tokens()))
            with run.span("submit"):
                for caller in self._collect(done):
                    self._submit(caller)
            if t1 - t_open >= seconds:
                return steps, t_open, self.clock()

    def live_tokens(self):
        """Tokens held in the cache by the requests in flight now."""
        eng = self.prog.engine
        return sum(eng.kv.ledger.length(s) for s in eng.scheduler.active)

    # -- the generator's four phases ----------------------------------------

    def setup(self):
        run, traffic = self.run, self.run.traffic
        self.clock = time.monotonic
        adapter = run.registry.module("programs", traffic["family"])
        self.prog = adapter.build_serve(run, clock=self.clock)
        run.log("program", **self.prog.describe)
        eng, e = self.prog.engine, traffic["engine"]
        vocab = run.config["vocab_size"]
        n = traffic["requests_per_cycle"]
        prompts = stratified_lengths(traffic["prompt_tokens"], n)
        outputs = stratified_lengths(traffic["output_tokens"], n)
        shapes = sorted({padded(p, e["kv_block"], e["max_len"])
                         for p in prompts})
        run.log("lengths", requests_per_cycle=n,
                prompt_tokens=sum(prompts), output_tokens=sum(outputs),
                prompt_histogram=dict(zip(
                    map(str, shapes),
                    stats.histogram(prompts, [0] + [s + 0.5 for s in shapes]))),
                output_quartiles=statistics.quantiles(outputs, n=4),
                padded_prefill_shapes=shapes)
        with run.setup_item("compile_or_cache_load_and_warm_shapes"):
            from horovod_tpu.serving.queue import Request
            rng = np.random.default_rng(int(run.seed) + 1)
            for i, s in enumerate(shapes):
                prompt = tuple(int(t) for t in rng.integers(0, vocab, s))
                if not eng.submit(Request(f"warm-{i}", prompt,
                                          max_new_tokens=2)):
                    raise RuntimeError(f"warm-up request {i} refused")
            warm = eng.run_to_completion()
            if len(warm) != len(shapes) or \
                    any(r.outcome != "completed" for r in warm):
                raise RuntimeError(f"warm-up did not complete: {warm}")
        self.stream = request_stream(traffic, vocab, run.seed)
        with run.setup_item("preroll"):
            for caller in range(traffic["callers"]):
                self._submit(caller)
            self._loop(traffic["preroll_s"], record=False)
        self.compiles_before = self.prog.compiles()

    def window(self, seconds):
        run = self.run
        first = len(self.finished)
        gc_before = gc.get_stats()[2]["collections"]
        steps, t_open, t_close = self._loop(seconds, record=True)
        window_s = t_close - t_open
        done = [r for r in self.finished[first:]
                if t_open <= r["finish_ts"] <= t_close]
        tokens = sum(s[3] for s in steps)
        ttft_ms = [r["ttft_s"] * 1e3 for r in done]
        tpot_ms = [r["tpot_s"] * 1e3 for r in done
                   if r["tpot_s"] is not None]
        step_s = [s[1] - s[0] for s in steps]
        loop_s = window_s - sum(step_s)
        compiles = self.prog.compiles()
        qs = (50, 75, 90, 95, 100)
        run.log("window", window_s=window_s, steps=len(steps),
                tokens=tokens, finished=len(done),
                ttft_ms={f"p{q}": round(stats.percentile(ttft_ms, q), 3)
                         for q in qs} if ttft_ms else None,
                tpot_ms={f"p{q}": round(stats.percentile(tpot_ms, q), 3)
                         for q in qs} if tpot_ms else None,
                ttft_samples=len(ttft_ms), tpot_samples=len(tpot_ms),
                in_flight=len(self.sent),
                callers_loop_ms_per_step=loop_s * 1e3 / max(len(steps), 1),
                step_ms_p50=stats.median(step_s) * 1e3,
                step_ms_max=max(step_s) * 1e3,
                # an outlier step told from one that admitted prefills
                slowest_steps=[
                    {"ms": round((s[1] - s[0]) * 1e3, 2), "admitted": s[2]}
                    for s in sorted(steps, key=lambda s: s[0] - s[1])[:5]],
                decode_only_step_ms={
                    f"p{q}": round(stats.percentile(
                        [(s[1] - s[0]) * 1e3 for s in steps
                         if s[2] == 0] or [0.0], q), 3)
                    for q in (50, 99, 100)},
                full_gc_in_window=gc.get_stats()[2]["collections"]
                - gc_before)
        obs = {"kind": "serve", "steps": steps, "window_s": window_s,
               "requests": done, "tokens": tokens,
               "slots": run.traffic["engine"]["num_slots"],
               "compiles": compiles["prefill"] + compiles["decode"],
               "compiled_in_window":
                   sum(compiles.values()) -
                   sum(self.compiles_before.values()),
               "model": {"layers": self.prog.describe["layers"]}}
        self.window_requests = done
        obs["end_to_end"] = {"serve_tokens_per_s": tokens / window_s}
        if ttft_ms:
            obs["end_to_end"]["ttft_p90"] = stats.percentile(ttft_ms, 90)
        if tpot_ms:
            obs["end_to_end"]["tpot_p90"] = stats.percentile(tpot_ms, 90)
        return obs

    def memory_analysis(self):
        return None

    def sample(self):
        """The finished requests the reference scores: the longest, and
        others drawn from the seed, ``check_requests`` in all."""
        done = self.window_requests
        k = min(self.run.traffic["check_requests"], len(done))
        order = sorted(range(len(done)), key=lambda i: -(
            len(done[i]["prompt"]) + len(done[i]["tokens"])))
        rng = np.random.default_rng(int(self.run.seed) + 2)
        rest = [int(i) for i in rng.permutation(order[1:])[:max(k - 1, 0)]]
        return [done[i] for i in order[:1] + rest] if done else []

    def check(self):
        run = self.run
        sample = self.sample()
        self.prog.free()
        t0 = time.perf_counter()
        worst, where, served = widest_gap(
            sample, reference_logits(run, sample))
        run.log("reference", seconds=time.perf_counter() - t0,
                requests=len(sample), served_tokens=served)
        checks = [(f"served_logit_gap[{where}]", worst,
                   run.limits["served_logit_gap"])]
        if not sample:
            checks = [("finished_requests", math.inf, 0)]
        return checks, self.attempted, self.failed


def make_leaves(shapes, key):
    """The served model's leaves as the program is given them: bfloat16,
    made on the device in one jitted call and handed out in that type.
    Widening them inside the same call is not the same values on the TPU:
    XLA drops the float32 -> bfloat16 -> float32 round trip there and hands
    out the unrounded noise (chip run, PR 26: every leaf differed by up to
    half a bfloat16 step, the reference's logits by up to 0.035)."""
    return jax.jit(lambda k: weights.make(shapes, k, jnp.bfloat16))(key)


def reference_logits(run, sample, quant=None):
    """For each request of ``sample``, float32 reference logits [served
    tokens, vocab] at the positions that produced each served token
    (teacher-forced: one forward over prompt + served tokens, padded to
    ``max_len`` so that one program serves every request).

    The served model IS its bf16 leaves, and the reference is handed them
    as they are: it widens what it uses where it uses it (exact), and may
    work in blocks to fit.  No float32 copy of the model is held, so the
    check's device bytes (the ``reference_memory`` line) are the leaves
    plus the reference program's own temporaries."""
    traffic = run.traffic
    ref = run.registry.module("reference", traffic["family"])
    adapter = run.registry.module("programs", traffic["family"])
    layers = adapter.depth(run.config, traffic)
    shapes = ref.weight_shapes(run.config, layers)
    max_len = traffic["engine"]["max_len"]
    rows = traffic["output_tokens"]["max"]
    out = []
    with jax.default_matmul_precision("highest"):
        w = make_leaves(shapes, tref.weights_key(run.seed))
        fwd = jax.jit(lambda w, toks, at: ref.logits_at(
            w, toks, at, run.config, layers, quant)).lower(
                w, jax.ShapeDtypeStruct((max_len,), jnp.int32),
                jax.ShapeDtypeStruct((rows,), jnp.int32)).compile()
        # memory_stats()'s peak is the whole process's, and the serving
        # window has set it: the check's own is what its program declares
        ma = fwd.memory_analysis()
        run.log("reference_memory", quant=quant,
                leaf_bytes=sum(a.nbytes for a in w.values()),
                temp_bytes=ma.temp_size_in_bytes,
                peak_bytes=ma.argument_size_in_bytes +
                ma.output_size_in_bytes + ma.temp_size_in_bytes)
        for req in sample:
            seq = list(req["prompt"]) + list(req["tokens"])
            toks = np.zeros(max_len, np.int32)
            toks[:len(seq) - 1] = seq[:-1]
            at = np.zeros(rows, np.int32)
            n = len(req["tokens"])
            at[:n] = len(req["prompt"]) - 1 + np.arange(n)
            out.append(np.asarray(
                fwd(w, jnp.asarray(toks), jnp.asarray(at)))[:n])
    for leaf in w.values():
        leaf.delete()
    return out


def widest_gap(sample, logits, tokens=None):
    """(widest gap, request id, tokens scored): how far below the
    reference's best logit the reference's logit of each token lies.
    ``tokens`` defaults to the served tokens."""
    worst, where, served = 0.0, None, 0
    for i, (req, lg) in enumerate(zip(sample, logits)):
        toks = np.asarray(req["tokens"] if tokens is None else tokens[i])
        gaps = lg.max(axis=-1) - lg[np.arange(len(toks)), toks]
        served += len(toks)
        if not np.isfinite(gaps).all():
            return math.inf, req["id"], served
        if float(gaps.max()) >= worst:
            worst, where = float(gaps.max()), req["id"]
    return worst, where, served

