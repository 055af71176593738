"""The analyzer analyzed: per-rule trigger / non-trigger / suppression
fixtures for tools/hvdlint, plus the end-to-end gate asserting the repo
itself lints clean (zero unbaselined findings — the same invocation CI
runs first).

Fixture snippets are written to tmp_path and scanned with
``analyze_paths``; role-scoped rules (HVD001/HVD003) opt in via the
``# hvdlint: role=`` marker instead of the built-in path lists, which is
exactly how any new module would.
"""

import json
import os
import subprocess
import sys
import textwrap

import pytest

from tools.hvdlint import analyze_paths
from tools.hvdlint.engine import iter_python_files
from tools.hvdlint.rules import RULES

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# a minimal config.py stand-in for HVD005 tests: exactly one aliased and
# one exact-name variable registered
FAKE_REGISTRY = textwrap.dedent("""\
    ENV_REGISTRY = (
        ("HOROVOD_CYCLE_TIME", True, "5.0", "common/config.py",
         "Cycle time."),
        ("HVD_COORDINATOR_ADDR", False, None, "mpi_ops.py",
         "Coordinator address."),
    )
""")


def lint_source(tmp_path, source, name="snippet.py", registry=None,
                baseline=None):
    """Write one fixture file and return its live + suppressed findings."""
    f = tmp_path / name
    f.write_text(textwrap.dedent(source))
    reg = tmp_path / "fake_config.py"
    reg.write_text(registry if registry is not None else FAKE_REGISTRY)
    findings, _ = analyze_paths(
        [str(f)], baseline_path=baseline, env_registry_path=str(reg))
    return findings


def live(findings, rule=None):
    return [f for f in findings if not f.suppressed and
            (rule is None or f.rule == rule)]


# ---------------------------------------------------------------------------
# HVD001 — rank-divergent iteration
# ---------------------------------------------------------------------------

def test_hvd001_triggers_on_set_iteration_in_wire_module(tmp_path):
    found = lint_source(tmp_path, """\
        # hvdlint: role=wire
        pending = set()

        def plan():
            return [name for name in pending]
        """)
    assert [f.rule for f in live(found)] == ["HVD001"]


def test_hvd001_triggers_on_list_of_set_attribute(tmp_path):
    found = lint_source(tmp_path, """\
        # hvdlint: role=wire
        class Coord:
            def __init__(self):
                self._lost = set()

            def response(self):
                return list(self._lost)
        """)
    assert [f.rule for f in live(found)] == ["HVD001"]


def test_hvd001_sorted_and_dict_iteration_are_clean(tmp_path):
    found = lint_source(tmp_path, """\
        # hvdlint: role=wire
        pending = set()
        table = {}

        def plan():
            for name in sorted(pending):
                yield name
            for key in table:  # dicts are insertion-ordered: identical
                yield key      # across ranks by construction
        """)
    assert live(found) == []


def test_hvd001_ignores_non_wire_modules(tmp_path):
    found = lint_source(tmp_path, """\
        pending = set()

        def local_only():
            return [n for n in pending]
        """)
    assert live(found) == []


def test_hvd001_suppression_honored(tmp_path):
    found = lint_source(tmp_path, """\
        # hvdlint: role=wire
        pending = set()

        def plan():
            # hvdlint: disable=HVD001(order feeds a local cache, never the wire)
            return [name for name in pending]
        """)
    assert live(found) == []
    assert [f.rule for f in found if f.suppressed == "inline"] == \
        ["HVD001"]


# ---------------------------------------------------------------------------
# HVD002 — lock order / self-deadlock
# ---------------------------------------------------------------------------

def test_hvd002_triggers_on_direct_reacquire(tmp_path):
    found = lint_source(tmp_path, """\
        import threading
        _lock = threading.Lock()

        def leaf():
            with _lock:
                with _lock:
                    return 1
        """)
    assert [f.rule for f in live(found)] == ["HVD002"]


def test_hvd002_triggers_on_call_graph_reacquire(tmp_path):
    # the metrics-registry reset() bug shape: hold the lock, call a
    # function whose body takes it again
    found = lint_source(tmp_path, """\
        import threading
        _lock = threading.Lock()

        def get_thing():
            with _lock:
                return 1

        def reset():
            with _lock:
                return get_thing()
        """)
    assert [f.rule for f in live(found)] == ["HVD002"]


def test_hvd002_triggers_on_inconsistent_order(tmp_path):
    found = lint_source(tmp_path, """\
        import threading
        a = threading.Lock()
        b = threading.Lock()

        def one():
            with a:
                with b:
                    pass

        def two():
            with b:
                with a:
                    pass
        """)
    assert any(f.rule == "HVD002" and "inconsistent" in f.message
               for f in live(found))


def test_hvd002_rlock_reentry_is_clean(tmp_path):
    found = lint_source(tmp_path, """\
        import threading
        _lock = threading.RLock()

        def outer():
            with _lock:
                return inner()

        def inner():
            with _lock:
                return 1
        """)
    assert live(found) == []


def test_hvd002_release_before_call_is_clean(tmp_path):
    # the fixed shape of reset(): the call happens after the with-region
    found = lint_source(tmp_path, """\
        import threading
        _lock = threading.Lock()

        def get_thing():
            with _lock:
                return 1

        def reset():
            with _lock:
                pass
            return get_thing()
        """)
    assert live(found) == []


# ---------------------------------------------------------------------------
# HVD003 — blocking call in the coordinator loop
# ---------------------------------------------------------------------------

def test_hvd003_triggers_on_unbounded_blocking(tmp_path):
    found = lint_source(tmp_path, """\
        # hvdlint: role=loop
        import socket
        import time

        def cycle(sock, thread):
            time.sleep(5)
            socket.create_connection(("peer", 1))
            thread.join()
        """)
    assert [f.rule for f in live(found)] == ["HVD003"] * 3


def test_hvd003_bounded_calls_are_clean(tmp_path):
    found = lint_source(tmp_path, """\
        # hvdlint: role=loop
        import socket
        import time

        def cycle(sock, thread, cycle_time_s):
            time.sleep(0.005)
            time.sleep(cycle_time_s)
            socket.create_connection(("peer", 1), timeout=2.0)
            thread.join(timeout=1.0)
        """)
    assert live(found) == []


def test_hvd003_ignores_modules_without_loop_role(tmp_path):
    found = lint_source(tmp_path, """\
        import time

        def launcher_wait():
            time.sleep(30)
        """)
    assert live(found) == []


# ---------------------------------------------------------------------------
# HVD004 — raw wall clock
# ---------------------------------------------------------------------------

def test_hvd004_triggers_on_time_time_and_from_import(tmp_path):
    found = lint_source(tmp_path, """\
        import time
        from time import time as now

        def stamp():
            return time.time(), time.time_ns(), now()
        """)
    assert [f.rule for f in live(found)] == ["HVD004"] * 3


def test_hvd004_monotonic_and_shared_clock_are_clean(tmp_path):
    found = lint_source(tmp_path, """\
        import time

        def stamp(clock):
            return time.monotonic(), time.perf_counter(), clock.ts_us()
        """)
    assert live(found) == []


def test_hvd004_suppression_honored(tmp_path):
    found = lint_source(tmp_path, """\
        import time

        def wall_stamp():
            return time.time()  # hvdlint: disable=HVD004(cross-process stamp)
        """)
    assert live(found) == []


# ---------------------------------------------------------------------------
# HVD005 — env-registry drift
# ---------------------------------------------------------------------------

def test_hvd005_triggers_on_unregistered_reads(tmp_path):
    found = lint_source(tmp_path, """\
        import os
        from horovod_tpu.common.config import env_int

        a = os.environ.get("HVD_NOT_REGISTERED")
        b = os.environ["HOROVOD_ALSO_MISSING"]
        c = "HVD_THIRD_ONE" in os.environ
        d = env_int("BRAND_NEW_KNOB", 3)
        """)
    hits = live(found, "HVD005")
    assert len(hits) == 4
    assert "HVD_NOT_REGISTERED" in hits[0].message


def test_hvd005_registered_reads_are_clean(tmp_path):
    found = lint_source(tmp_path, """\
        import os
        from horovod_tpu.common.config import env_float

        a = os.environ.get("HVD_COORDINATOR_ADDR")
        b = env_float("CYCLE_TIME", 5.0)   # aliased HOROVOD_/HVD_
        c = os.environ.get("HVD_CYCLE_TIME")  # the alias spelling
        d = os.environ.get("PATH")  # non-HVD names are out of scope
        """)
    assert live(found) == []


def test_hvd005_real_registry_parses_without_import(tmp_path):
    from tools.hvdlint import envdoc
    entries = envdoc.load_env_registry()
    names = {e["name"] for e in entries}
    assert "HOROVOD_FUSION_THRESHOLD" in names
    assert "HVD_COORDINATOR_ADDR" in names
    assert len(entries) >= 49
    lookup = envdoc.registry_lookup(entries)
    assert "HVD_FUSION_THRESHOLD" in lookup  # alias spelling


# ---------------------------------------------------------------------------
# HVD006 — swallowed exception
# ---------------------------------------------------------------------------

def test_hvd006_triggers_on_silent_broad_except(tmp_path):
    found = lint_source(tmp_path, """\
        def fetch(client):
            try:
                return client.cycle()
            except Exception:
                pass
        """)
    assert [f.rule for f in live(found)] == ["HVD006"]


def test_hvd006_narrow_logged_or_reraised_are_clean(tmp_path):
    found = lint_source(tmp_path, """\
        import logging
        log = logging.getLogger(__name__)

        def fetch(client):
            try:
                return client.cycle()
            except ConnectionError:
                return None

        def fetch2(client):
            try:
                return client.cycle()
            except Exception as exc:
                log.warning("cycle failed: %s", exc)
                return None

        def fetch3(client):
            try:
                return client.cycle()
            except Exception:
                raise
        """)
    assert live(found) == []


def test_hvd006_suppression_with_reason_honored(tmp_path):
    found = lint_source(tmp_path, """\
        def close(sock):
            try:
                sock.close()
            # hvdlint: disable=HVD006(teardown is best-effort)
            except Exception:
                pass
        """)
    assert live(found) == []


def test_reasonless_suppression_is_integrity_finding(tmp_path):
    found = lint_source(tmp_path, """\
        def close(sock):
            try:
                sock.close()
            except Exception:  # hvdlint: disable=HVD006
                pass
        """)
    rules = sorted(f.rule for f in live(found))
    # the disable does NOT suppress, and is itself reported
    assert rules == ["HVD000", "HVD006"]


# ---------------------------------------------------------------------------
# HVD007 — jit purity
# ---------------------------------------------------------------------------

def test_hvd007_triggers_on_side_effects_in_traced_fn(tmp_path):
    found = lint_source(tmp_path, """\
        import functools
        import os
        import time
        import jax

        @jax.jit
        def step(x):
            print("tracing")
            return x * time.time()

        @functools.partial(jax.jit, static_argnums=1)
        def step2(x, n):
            return x * float(os.environ.get("HVD_COORDINATOR_ADDR", 1))
        """)
    # (the raw time.time() also trips HVD004 — that rule is file-wide)
    assert [f.rule for f in live(found, "HVD007")] == ["HVD007"] * 3


def test_hvd007_pure_traced_and_impure_untraced_are_clean(tmp_path):
    found = lint_source(tmp_path, """\
        import jax
        import jax.numpy as jnp

        @jax.jit
        def step(x):
            return jnp.sum(x * 2.0)

        def host_side():
            print("not traced, print away")
        """)
    assert live(found) == []


def test_hvd007_catches_lambda_passed_to_jit(tmp_path):
    found = lint_source(tmp_path, """\
        import jax

        _replicate = jax.jit(lambda x: print(x) or x)
        """)
    assert [f.rule for f in live(found)] == ["HVD007"]


# ---------------------------------------------------------------------------
# HVD008 — span leak
# ---------------------------------------------------------------------------

def test_hvd008_triggers_on_discarded_span(tmp_path):
    found = lint_source(tmp_path, """\
        from horovod_tpu.utils import tracing as hvd_tracing

        def enqueue(tracer, name):
            tracer.span("negotiate", tensor=name)
        """)
    assert [f.rule for f in live(found)] == ["HVD008"]


def test_hvd008_triggers_on_discarded_annotate_chain(tmp_path):
    # annotate() returns the span, so chaining doesn't close it
    found = lint_source(tmp_path, """\
        def enqueue(name):
            from horovod_tpu.utils.tracing import get_tracer
            get_tracer().span("enqueue", tensor=name).annotate(op="sum")
        """)
    assert [f.rule for f in live(found)] == ["HVD008"]


def test_hvd008_triggers_on_assigned_never_closed(tmp_path):
    found = lint_source(tmp_path, """\
        def run(tracer):
            s = tracer.span("execute")
            do_work()
        """)
    hits = live(found, "HVD008")
    assert len(hits) == 1 and "'s'" in hits[0].message


def test_hvd008_clean_forms(tmp_path):
    found = lint_source(tmp_path, """\
        def lexical(tracer):
            with tracer.span("fusion") as fspan:
                fspan.annotate(n_buckets=3)

        def explicit(tracer):
            s = tracer.span("execute")
            try:
                do_work()
                s.close(bytes=128)
            except Exception as exc:
                s.abort(exc)
                raise

        def stored(tracer, entry):
            # ownership handed to the entry: closed elsewhere by design
            entry.span = tracer.span("negotiate")

        def escapes(tracer):
            a = tracer.span("step")
            register(a)           # passed on: callee owns the close
            b = tracer.span("cycle")
            return b              # returned: caller owns the close
        """)
    assert live(found) == []


def test_hvd008_suppression_honored(tmp_path):
    found = lint_source(tmp_path, """\
        def fire_and_forget(tracer):
            tracer.span("enqueue")  # hvdlint: disable=HVD008(leak drill)
        """)
    assert live(found) == []
    assert [f.rule for f in found if f.suppressed == "inline"] == \
        ["HVD008"]


# ---------------------------------------------------------------------------
# HVD009 — ad-hoc numerics probe
# ---------------------------------------------------------------------------

def test_hvd009_triggers_on_adhoc_isnan(tmp_path):
    found = lint_source(tmp_path, """\
        import jax.numpy as jnp

        def flush(grad):
            if jnp.isnan(grad).any():
                raise ValueError("nan gradient")
            return grad
        """)
    assert [f.rule for f in live(found)] == ["HVD009"]
    assert "isnan" in live(found)[0].message


def test_hvd009_triggers_on_bare_imported_name(tmp_path):
    found = lint_source(tmp_path, """\
        from numpy import isfinite

        def guard(x):
            return isfinite(x).all()
        """)
    assert [f.rule for f in live(found)] == ["HVD009"]


def test_hvd009_sanctioned_numerics_module_is_clean(tmp_path):
    mod = tmp_path / "horovod_tpu" / "utils"
    mod.mkdir(parents=True)
    f = mod / "numerics.py"
    f.write_text(textwrap.dedent("""\
        import jax.numpy as jnp

        def tensor_stats(x):
            return jnp.isfinite(x)
        """))
    reg = tmp_path / "fake_config.py"
    reg.write_text(FAKE_REGISTRY)
    findings, _ = analyze_paths([str(f)], env_registry_path=str(reg))
    assert live(findings) == []


def test_hvd009_routed_stats_call_is_clean(tmp_path):
    found = lint_source(tmp_path, """\
        from horovod_tpu.utils import numerics

        def flush(flat, sizes):
            return numerics.segment_stats(flat, sizes)
        """)
    assert live(found) == []


def test_hvd009_suppression_honored(tmp_path):
    found = lint_source(tmp_path, """\
        import math

        def host_guard(x):
            return math.isnan(x)  # hvdlint: disable=HVD009(host scalar)
        """)
    assert live(found) == []
    assert [f.rule for f in found if f.suppressed == "inline"] == \
        ["HVD009"]


# ---------------------------------------------------------------------------
# HVD010 — wire-dtype cast bypasses the codec registry
# ---------------------------------------------------------------------------

def test_hvd010_triggers_on_direct_int8_cast(tmp_path):
    found = lint_source(tmp_path, """\
        import jax.numpy as jnp

        def narrow(grad):
            return grad.astype(jnp.int8)
        """)
    assert [f.rule for f in live(found)] == ["HVD010"]
    assert "int8" in live(found)[0].message


def test_hvd010_triggers_on_string_and_npdtype_forms(tmp_path):
    found = lint_source(tmp_path, """\
        import numpy as np

        def narrow(grad, other):
            a = grad.astype("float8_e4m3fn")
            b = other.astype(np.dtype("uint8"))
            return a, b
        """)
    assert sorted(f.rule for f in live(found)) == ["HVD010", "HVD010"]


def test_hvd010_wide_casts_are_clean(tmp_path):
    found = lint_source(tmp_path, """\
        import jax.numpy as jnp

        def widen(grad):
            # bf16/f32 casts are numerics policy, not wire format
            return grad.astype(jnp.bfloat16).astype(jnp.float32)
        """)
    assert live(found) == []


def test_hvd010_sanctioned_quantization_module_is_clean(tmp_path):
    mod = tmp_path / "horovod_tpu" / "ops"
    mod.mkdir(parents=True)
    f = mod / "quantization.py"
    f.write_text(textwrap.dedent("""\
        import jax.numpy as jnp

        def encode(x):
            return x.astype(jnp.int8)
        """))
    reg = tmp_path / "fake_config.py"
    reg.write_text(FAKE_REGISTRY)
    findings, _ = analyze_paths([str(f)], env_registry_path=str(reg))
    assert live(findings) == []


def test_hvd010_suppression_honored(tmp_path):
    found = lint_source(tmp_path, """\
        import jax.numpy as jnp

        def tokens(ids):
            return ids.astype(jnp.uint8)  # hvdlint: disable=HVD010(token bytes, not a wire codec)
        """)
    assert live(found) == []
    assert [f.rule for f in found if f.suppressed == "inline"] == \
        ["HVD010"]


# ---------------------------------------------------------------------------
# HVD011 — blocking host sync in the serving decode loop
# ---------------------------------------------------------------------------

def test_hvd011_triggers_on_host_syncs_in_serve_loop(tmp_path):
    found = lint_source(tmp_path, """\
        # hvdlint: role=serve_loop
        import jax
        import numpy as np

        def decode_step_host(x):
            tok = jax.device_get(x)
            x.block_until_ready()
            return np.asarray(tok)
        """)
    assert [f.rule for f in live(found)] == ["HVD011"] * 3


def test_hvd011_triggers_in_real_serving_path(tmp_path):
    mod = tmp_path / "horovod_tpu" / "serving"
    mod.mkdir(parents=True)
    f = mod / "engine.py"
    f.write_text(textwrap.dedent("""\
        import jax

        def peek(x):
            return jax.device_get(x)
        """))
    reg = tmp_path / "fake_config.py"
    reg.write_text(FAKE_REGISTRY)
    findings, _ = analyze_paths([str(f)], env_registry_path=str(reg))
    assert [f.rule for f in live(findings)] == ["HVD011"]


def test_hvd011_jnp_asarray_and_outside_scope_are_clean(tmp_path):
    # jnp.asarray is host->device: legal inside the loop
    found = lint_source(tmp_path, """\
        # hvdlint: role=serve_loop
        import jax.numpy as jnp

        def feed(tokens):
            return jnp.asarray(tokens)
        """)
    assert live(found) == []
    # and without the role/path scope, host syncs are someone else's
    # business (training scripts readback all the time)
    found = lint_source(tmp_path, """\
        import jax

        def fetch(x):
            return jax.device_get(x)
        """)
    assert live(found) == []


def test_hvd011_suppression_honored(tmp_path):
    found = lint_source(tmp_path, """\
        # hvdlint: role=serve_loop
        import jax
        import numpy as np

        def sample(nxt):
            # hvdlint: disable=HVD011(the per-step batched token readback)
            return np.asarray(jax.device_get(nxt))
        """)
    assert live(found) == []
    assert sorted(f.rule for f in found if f.suppressed == "inline") == \
        ["HVD011", "HVD011"]


# ---------------------------------------------------------------------------
# HVD012 — ad-hoc training-state serialization
# ---------------------------------------------------------------------------

def test_hvd012_triggers_on_numpy_and_torch_dumps(tmp_path):
    found = lint_source(tmp_path, """\
        import numpy as np
        import torch

        def dump(path, params, model):
            np.savez(path, **params)
            np.savez_compressed(path + ".z", **params)
            np.save(path + ".npy", params["w"])
            torch.save(model.state_dict(), path + ".pt")
        """)
    assert [f.rule for f in live(found)] == ["HVD012"] * 4


def test_hvd012_sanctioned_checkpoint_module_is_clean(tmp_path):
    mod = tmp_path / "horovod_tpu" / "utils"
    mod.mkdir(parents=True)
    f = mod / "checkpoint.py"
    f.write_text(textwrap.dedent("""\
        import numpy as np

        def write_shard(path, arrays):
            np.savez(path, **arrays)
        """))
    reg = tmp_path / "fake_config.py"
    reg.write_text(FAKE_REGISTRY)
    findings, _ = analyze_paths([str(f)], env_registry_path=str(reg))
    assert live(findings) == []


def test_hvd012_non_dump_writes_are_clean(tmp_path):
    # json/pickle scratch and this repo's own checkpoint entry points
    # are not array dumps; np.save needs the np receiver to count
    found = lint_source(tmp_path, """\
        import json
        import pickle
        from horovod_tpu.utils import checkpoint

        def scratch(path, obj, tree):
            json.dump(obj, open(path, "w"))
            pickle.dumps(obj)
            checkpoint.save(path, tree)

        def save(path, obj):
            return path, obj
        """)
    assert live(found) == []


def test_hvd012_suppression_honored(tmp_path):
    found = lint_source(tmp_path, """\
        import numpy as np

        def export_onnx_weights(path, arrays):
            # hvdlint: disable=HVD012(interchange export, not durable training state)
            np.savez(path, **arrays)
        """)
    assert live(found) == []
    assert [f.rule for f in found if f.suppressed == "inline"] == \
        ["HVD012"]


# ---------------------------------------------------------------------------
# HVD013 — ad-hoc step timers in hot-path modules
# ---------------------------------------------------------------------------

def test_hvd013_triggers_on_perf_counter_in_hot_path(tmp_path):
    found = lint_source(tmp_path, """\
        # hvdlint: role=hot_path
        import time

        def step(fn, x):
            t0 = time.perf_counter()
            y = fn(x)
            dt = time.perf_counter_ns() - t0
            return y, dt
        """)
    assert [f.rule for f in live(found)] == ["HVD013"] * 2


def test_hvd013_triggers_on_from_import_alias(tmp_path):
    found = lint_source(tmp_path, """\
        # hvdlint: role=hot_path
        from time import perf_counter as pc

        def step(fn, x):
            t0 = pc()
            return fn(x), pc() - t0
        """)
    assert [f.rule for f in live(found)] == ["HVD013"] * 2


def test_hvd013_triggers_in_real_ops_path(tmp_path):
    mod = tmp_path / "horovod_tpu" / "ops"
    mod.mkdir(parents=True)
    f = mod / "fusion.py"
    f.write_text(textwrap.dedent("""\
        import time

        def flush(buckets):
            t0 = time.perf_counter()
            return buckets, t0
        """))
    reg = tmp_path / "fake_config.py"
    reg.write_text(FAKE_REGISTRY)
    findings, _ = analyze_paths([str(f)], env_registry_path=str(reg))
    assert [f.rule for f in live(findings)] == ["HVD013"]


def test_hvd013_monotonic_refs_and_cold_paths_are_clean(tmp_path):
    # time.monotonic is the shared clock's base and the wire-timeout
    # primitive; a bare attribute reference (clock=time.monotonic) is
    # not a timing read; and outside the hot-path scope raw timers are
    # someone else's business
    found = lint_source(tmp_path, """\
        # hvdlint: role=hot_path
        import time

        def deadline(timeout_s):
            return time.monotonic() + timeout_s

        def make_engine():
            return dict(clock=time.monotonic, now=time.perf_counter)
        """)
    assert live(found) == []
    found = lint_source(tmp_path, """\
        import time

        def bench_once(fn):
            t0 = time.perf_counter()
            fn()
            return time.perf_counter() - t0
        """)
    assert live(found) == []


def test_hvd013_instrument_step_is_sanctioned(tmp_path):
    mod = tmp_path / "horovod_tpu"
    mod.mkdir(parents=True)
    f = mod / "trainer.py"
    f.write_text(textwrap.dedent("""\
        import time

        def instrument_step(step_fn):
            def wrapped(*a):
                t0 = time.perf_counter()
                out = step_fn(*a)
                return out, time.perf_counter() - t0
            return wrapped
        """))
    reg = tmp_path / "fake_config.py"
    reg.write_text(FAKE_REGISTRY)
    findings, _ = analyze_paths([str(f)], env_registry_path=str(reg))
    assert live(findings) == []


def test_hvd013_suppression_honored(tmp_path):
    found = lint_source(tmp_path, """\
        # hvdlint: role=hot_path
        import time

        def flush(buckets):
            # hvdlint: disable=HVD013(flush duration feeding the hvd_fusion_flush_seconds histogram)
            t0 = time.perf_counter()
            return buckets, t0
        """)
    assert live(found) == []
    assert [f.rule for f in found if f.suppressed == "inline"] == \
        ["HVD013"]


def test_hvd014_triggers_on_request_ts_delta(tmp_path):
    found = lint_source(tmp_path, """\
        # hvdlint: role=serve_path

        def retire(now, req, st):
            ttft = now - req.arrival_ts
            gap = now - st.last_token_ts
            return ttft, gap
        """)
    assert [f.rule for f in live(found)] == ["HVD014"] * 2


def test_hvd014_triggers_in_real_serving_path(tmp_path):
    mod = tmp_path / "horovod_tpu" / "serving"
    mod.mkdir(parents=True)
    f = mod / "engine.py"
    f.write_text(textwrap.dedent("""\
        def deadline_left(now, req):
            return req.deadline_s - (now - req.arrival_ts)
        """))
    reg = tmp_path / "fake_config.py"
    reg.write_text(FAKE_REGISTRY)
    findings, _ = analyze_paths([str(f)], env_registry_path=str(reg))
    assert [f.rule for f in live(findings)] == ["HVD014"]


def test_hvd014_trace_layer_is_sanctioned(tmp_path):
    # serving/tracing.py IS the request-timing layer: the same delta
    # there is the instrument, not a rival
    mod = tmp_path / "horovod_tpu" / "serving"
    mod.mkdir(parents=True)
    f = mod / "tracing.py"
    f.write_text(textwrap.dedent("""\
        def waited(now, req):
            return now - req.arrival_ts
        """))
    reg = tmp_path / "fake_config.py"
    reg.write_text(FAKE_REGISTRY)
    findings, _ = analyze_paths([str(f)], env_registry_path=str(reg))
    assert live(findings) == []


def test_hvd014_non_ts_deltas_and_outside_scope_clean(tmp_path):
    # subtraction per se is fine — only request-lifecycle timestamp
    # attributes mark a latency measurement
    found = lint_source(tmp_path, """\
        # hvdlint: role=serve_path

        def trim(req, budget):
            return len(req.prompt) - budget

        def room(ledger):
            return ledger.capacity - ledger.used
        """)
    assert live(found) == []
    # outside the serving plane the same delta is someone else's
    # business (bench harnesses, tests)
    found = lint_source(tmp_path, """\
        def waited(now, req):
            return now - req.arrival_ts
        """)
    assert live(found) == []


def test_hvd014_suppression_honored(tmp_path):
    found = lint_source(tmp_path, """\
        # hvdlint: role=serve_path

        def observe_ttft(hist, now, req):
            # hvdlint: disable=HVD014(TTFT histogram on the shared registry consumes this delta)
            hist.observe(now - req.arrival_ts)
        """)
    assert live(found) == []
    assert [f.rule for f in found if f.suppressed == "inline"] == \
        ["HVD014"]


# ---------------------------------------------------------------------------
# HVD015 — ad-hoc weight load in the serving plane
# ---------------------------------------------------------------------------

def test_hvd015_triggers_on_manager_restore_in_serve_path(tmp_path):
    found = lint_source(tmp_path, """\
        # hvdlint: role=serve_path

        def refresh(self, step):
            params = self.manager.restore(step)
            extra = self.checkpoint.restore_with_extra(like=params)
            return params, extra
        """)
    assert [f.rule for f in live(found)] == ["HVD015"] * 2


def test_hvd015_triggers_in_real_serving_module(tmp_path):
    mod = tmp_path / "horovod_tpu" / "serving"
    mod.mkdir(parents=True)
    f = mod / "engine.py"
    f.write_text(textwrap.dedent("""\
        import numpy as np

        def reload_weights(path):
            return np.load(path)
        """))
    reg = tmp_path / "fake_config.py"
    reg.write_text(FAKE_REGISTRY)
    findings, _ = analyze_paths([str(f)], env_registry_path=str(reg))
    assert [f.rule for f in live(findings)] == ["HVD015"]


def test_hvd015_triggers_on_bare_import_alias(tmp_path):
    found = lint_source(tmp_path, """\
        # hvdlint: role=serve_path
        from horovod_tpu.utils.checkpoint import restore

        def refresh(path, like):
            return restore(path, like=like)
        """)
    assert [f.rule for f in live(found)] == ["HVD015"]


def test_hvd015_subscriber_layer_is_sanctioned(tmp_path):
    # fleet/subscriber.py IS the weight-load path: restore there is the
    # mechanism, not a rival
    mod = tmp_path / "horovod_tpu" / "fleet"
    mod.mkdir(parents=True)
    f = mod / "subscriber.py"
    f.write_text(textwrap.dedent("""\
        from horovod_tpu.utils import checkpoint

        def _restore(d, like):
            return checkpoint.restore_with_extra(d, like=like)
        """))
    reg = tmp_path / "fake_config.py"
    reg.write_text(FAKE_REGISTRY)
    findings, _ = analyze_paths([str(f)], env_registry_path=str(reg))
    assert live(findings) == []


def test_hvd015_outside_serving_plane_is_clean(tmp_path):
    # the trainer restoring its own checkpoint is the normal resume
    # path, not an ad-hoc serving-side load
    found = lint_source(tmp_path, """\
        def resume(self):
            return self.manager.restore(like=self.params)
        """)
    assert live(found) == []


def test_hvd015_suppression_honored(tmp_path):
    found = lint_source(tmp_path, """\
        # hvdlint: role=serve_path

        def warm_start(self):
            # hvdlint: disable=HVD015(one-time boot load before the subscriber exists)
            return self.manager.restore(like=self.params)
        """)
    assert live(found) == []
    assert [f.rule for f in found if f.suppressed == "inline"] == \
        ["HVD015"]


# ---------------------------------------------------------------------------
# HVD016 — full-tree barrier in the backward→apply window
# ---------------------------------------------------------------------------

def test_hvd016_triggers_on_synchronize_comprehension(tmp_path):
    found = lint_source(tmp_path, """\
        # hvdlint: role=hot_path

        def reduce_all(mpi_ops, handles):
            return [mpi_ops.synchronize(h) for h in handles]
        """)
    assert [f.rule for f in live(found)] == ["HVD016"]


def test_hvd016_triggers_on_block_until_ready(tmp_path):
    found = lint_source(tmp_path, """\
        # hvdlint: role=hot_path
        import jax

        def step(backward, apply, x):
            grads = backward(x)
            jax.block_until_ready(grads)
            return apply(grads)
        """)
    assert [f.rule for f in live(found)] == ["HVD016"]


def test_hvd016_triggers_in_real_optim_path(tmp_path):
    mod = tmp_path / "horovod_tpu"
    mod.mkdir(parents=True)
    f = mod / "optim.py"
    f.write_text(textwrap.dedent("""\
        def drain(mpi_ops, handles):
            return [mpi_ops.synchronize(h) for h in handles]
        """))
    reg = tmp_path / "fake_config.py"
    reg.write_text(FAKE_REGISTRY)
    findings, _ = analyze_paths([str(f)], env_registry_path=str(reg))
    assert [f.rule for f in live(findings)] == ["HVD016"]


def test_hvd016_instrument_step_sync_is_sanctioned(tmp_path):
    # the measurement boundary: instrument_step's own block_until_ready
    # IS the step wall's definition, not a rival barrier
    mod = tmp_path / "horovod_tpu"
    mod.mkdir(parents=True)
    f = mod / "trainer.py"
    f.write_text(textwrap.dedent("""\
        import jax

        def instrument_step(step_fn):
            def wrapped(*a):
                out = step_fn(*a)
                jax.block_until_ready(out)
                return out
            return wrapped
        """))
    reg = tmp_path / "fake_config.py"
    reg.write_text(FAKE_REGISTRY)
    findings, _ = analyze_paths([str(f)], env_registry_path=str(reg))
    assert live(findings) == []


def test_hvd016_per_bucket_sync_and_cold_paths_are_clean(tmp_path):
    # a single synchronize as results are consumed is the overlap
    # plane's OWN idiom; and outside the hot-path scope the barrier is
    # someone else's call
    found = lint_source(tmp_path, """\
        # hvdlint: role=hot_path

        def consume(mpi_ops, handle, apply):
            return apply(mpi_ops.synchronize(handle))
        """)
    assert live(found) == []
    found = lint_source(tmp_path, """\
        import jax

        def eval_once(model, x):
            out = model(x)
            jax.block_until_ready(out)
            return [sync(h) for h in out]
        """)
    assert live(found) == []


def test_hvd016_suppression_honored(tmp_path):
    found = lint_source(tmp_path, """\
        # hvdlint: role=hot_path

        def drain(mpi_ops, handles):
            # hvdlint: disable=HVD016(checkpoint boundary: every shard must be on host before save)
            return [mpi_ops.synchronize(h) for h in handles]
        """)
    assert live(found) == []
    assert [f.rule for f in found if f.suppressed == "inline"] == \
        ["HVD016"]


# ---------------------------------------------------------------------------
# HVD017 — direct engine admission outside the router front door
# ---------------------------------------------------------------------------

def test_hvd017_triggers_on_engine_submit_and_admission_queue(tmp_path):
    found = lint_source(tmp_path, """\
        # hvdlint: role=client_path
        from horovod_tpu.serving import AdmissionQueue

        def drive(engine, requests):
            queue = AdmissionQueue(max_depth=8)
            for req in requests:
                engine.submit(req)
        """)
    assert [f.rule for f in live(found)] == ["HVD017"] * 2


def test_hvd017_scopes_to_client_dirs(tmp_path):
    # same code under examples/ fires without any role marker...
    mod = tmp_path / "examples"
    mod.mkdir(parents=True)
    f = mod / "demo.py"
    f.write_text("def go(engine, req):\n    engine.submit(req)\n")
    reg = tmp_path / "fake_config.py"
    reg.write_text(FAKE_REGISTRY)
    findings, _ = analyze_paths([str(f)], env_registry_path=str(reg))
    assert [f.rule for f in live(findings)] == ["HVD017"]
    # ...and the identical snippet with no role and no client dir is
    # out of scope (the engine's own internals are the implementation)
    found = lint_source(tmp_path, """\
        def go(engine, req):
            engine.submit(req)
        """)
    assert live(found) == []


def test_hvd017_router_submit_is_sanctioned(tmp_path):
    # Router.submit IS the front door; queue.submit inside the serving
    # plane is somebody else's receiver
    found = lint_source(tmp_path, """\
        # hvdlint: role=client_path

        def drive(router, queue, requests):
            for req in requests:
                router.submit(req)
            queue.submit(requests[0])
        """)
    assert live(found) == []


def test_hvd017_suppression_honored(tmp_path):
    found = lint_source(tmp_path, """\
        # hvdlint: role=client_path

        def bench_arm(engine, req):
            # hvdlint: disable=HVD017(single-replica bench arm: the bare engine is the thing measured)
            engine.submit(req)
        """)
    assert live(found) == []
    assert [f.rule for f in found if f.suppressed == "inline"] == \
        ["HVD017"]


# ---------------------------------------------------------------------------
# HVD018 — unbounded retry loop
# ---------------------------------------------------------------------------

def test_hvd018_triggers_on_deadline_free_sleep_loop(tmp_path):
    found = lint_source(tmp_path, """\
        # hvdlint: role=retry_path
        import time

        def wait_for_pointer(path):
            while True:
                if path.exists():
                    return path.read_text()
                time.sleep(0.1)
        """)
    assert [f.rule for f in live(found)] == ["HVD018"]


def test_hvd018_deadline_check_bounds_the_loop(tmp_path):
    # the run/mpi.py rendezvous shape: monotonic-vs-deadline compare
    # anywhere in the body is the bound this rule wants
    found = lint_source(tmp_path, """\
        # hvdlint: role=retry_path
        import time

        def wait_for_pointer(path, timeout_s):
            deadline = time.monotonic() + timeout_s
            while True:
                if path.exists():
                    return path.read_text()
                if time.monotonic() > deadline:
                    raise TimeoutError(path)
                time.sleep(0.1)
        """)
    assert live(found) == []


def test_hvd018_bound_named_operand_counts(tmp_path):
    # a compare against a timeout/deadline-named value also reads as a
    # bound even when the clock call is hoisted out of the compare
    found = lint_source(tmp_path, """\
        # hvdlint: role=retry_path
        import time

        def poll(conn, timeout_s):
            waited = 0.0
            while True:
                if conn.ready():
                    return conn.take()
                if waited >= timeout_s:
                    raise TimeoutError
                time.sleep(0.05)
                waited += 0.05
        """)
    assert live(found) == []


def test_hvd018_sleepless_drain_loop_not_flagged(tmp_path):
    # a blocking-recv drain loop is bounded by its peer's EOF — no
    # sleep, no finding (the serving queue's pop loop is this shape)
    found = lint_source(tmp_path, """\
        # hvdlint: role=retry_path

        def drain(sock):
            while True:
                msg = sock.recv()
                if not msg:
                    break
        """)
    assert live(found) == []


def test_hvd018_scopes_to_control_planes(tmp_path):
    # identical snippet with no role marker and no scoped dir is out
    # of scope
    found = lint_source(tmp_path, """\
        import time

        def wait(path):
            while True:
                time.sleep(0.1)
        """)
    assert live(found) == []
    # ...and under horovod_tpu/router/ it fires without a marker
    mod = tmp_path / "horovod_tpu" / "router"
    mod.mkdir(parents=True)
    f = mod / "spin.py"
    f.write_text("import time\n\ndef wait(path):\n"
                 "    while True:\n        time.sleep(0.1)\n")
    reg = tmp_path / "fake_config.py"
    reg.write_text(FAKE_REGISTRY)
    findings, _ = analyze_paths([str(f)], env_registry_path=str(reg))
    assert [f.rule for f in live(findings)] == ["HVD018"]


def test_hvd018_suppression_honored(tmp_path):
    found = lint_source(tmp_path, """\
        # hvdlint: role=retry_path
        import time

        def serve(sock):
            # hvdlint: disable=HVD018(bounded by peer EOF; the sleep is an injected chaos fault)
            while True:
                req = sock.recv()
                time.sleep(req.delay_s)
        """)
    assert live(found) == []
    assert [f.rule for f in found if f.suppressed == "inline"] == \
        ["HVD018"]


# ---------------------------------------------------------------------------
# HVD019 — ad-hoc sharding outside the mesh plane
# ---------------------------------------------------------------------------

def test_hvd019_triggers_on_bare_namedsharding(tmp_path):
    found = lint_source(tmp_path, """\
        # hvdlint: role=mesh_path
        from jax.sharding import NamedSharding, PartitionSpec as P

        def place(x, mesh):
            return jax.device_put(x, NamedSharding(mesh, P("dp")))
        """)
    assert [f.rule for f in live(found)] == ["HVD019"]


def test_hvd019_triggers_on_device_put_with_inline_mesh(tmp_path):
    found = lint_source(tmp_path, """\
        # hvdlint: role=mesh_path
        import jax
        from jax.sharding import Mesh

        def place(x, devices):
            return jax.device_put(x, Mesh(devices, ("dp",)))
        """)
    assert [f.rule for f in live(found)] == ["HVD019"]


def test_hvd019_sees_through_import_aliases(tmp_path):
    found = lint_source(tmp_path, """\
        # hvdlint: role=mesh_path
        from jax.sharding import NamedSharding as NS

        def place(x, mesh, spec):
            return NS(mesh, spec)
        """)
    assert [f.rule for f in live(found)] == ["HVD019"]


def test_hvd019_mesh_lib_helpers_are_sanctioned(tmp_path):
    # the fix the rule points at: specs routed through parallel/mesh.py
    found = lint_source(tmp_path, """\
        # hvdlint: role=mesh_path
        from horovod_tpu.parallel import mesh as mesh_lib
        from jax.sharding import PartitionSpec as P

        def place(tree, spec_tree, mesh):
            s = mesh_lib.named_sharding(P("dp"), mesh)
            return mesh_lib.device_put_tree(tree, spec_tree, mesh)
        """)
    assert live(found, "HVD019") == []


def test_hvd019_scoped_to_data_plane_modules(tmp_path):
    # no role marker, not under trainer/serving/ops: out of scope
    found = lint_source(tmp_path, """\
        from jax.sharding import NamedSharding

        def place(x, mesh, spec):
            return NamedSharding(mesh, spec)
        """)
    assert live(found, "HVD019") == []


def test_hvd019_fires_under_serving_without_marker_but_not_in_mesh_py(
        tmp_path):
    reg = tmp_path / "fake_config.py"
    reg.write_text(FAKE_REGISTRY)
    src = ("from jax.sharding import NamedSharding\n\n"
           "def place(x, mesh, spec):\n"
           "    return NamedSharding(mesh, spec)\n")
    serve = tmp_path / "horovod_tpu" / "serving"
    serve.mkdir(parents=True)
    (serve / "warm.py").write_text(src)
    plane = tmp_path / "horovod_tpu" / "parallel"
    plane.mkdir(parents=True)
    (plane / "mesh.py").write_text(src)
    findings, _ = analyze_paths(
        [str(serve / "warm.py"), str(plane / "mesh.py")],
        env_registry_path=str(reg))
    assert [(f.rule, "serving" in f.file) for f in live(findings)] == \
        [("HVD019", True)]


def test_hvd019_suppression_honored(tmp_path):
    found = lint_source(tmp_path, """\
        # hvdlint: role=mesh_path
        from jax.sharding import NamedSharding, PartitionSpec as P

        def rendezvous_sharding(mesh):
            # hvdlint: disable=HVD019(per-process rendezvous mesh, not the data plane)
            return NamedSharding(mesh, P("proc"))
        """)
    assert live(found) == []
    assert [f.rule for f in found if f.suppressed == "inline"] == \
        ["HVD019"]


# ---------------------------------------------------------------------------
# baseline machinery
# ---------------------------------------------------------------------------

def test_baseline_consumes_match_and_requires_reason(tmp_path):
    src = """\
        import time

        def stamp():
            return time.time()
        """
    bl = tmp_path / "baseline.json"
    bl.write_text(json.dumps({"version": 1, "entries": [{
        "file": str(tmp_path / "snippet.py"), "rule": "HVD004",
        "match": "return time.time()", "count": 1,
        "reason": "wall stamp compared across processes"}]}))
    found = lint_source(tmp_path, src, baseline=str(bl))
    assert live(found) == []
    assert [f.rule for f in found if f.suppressed == "baseline"] == \
        ["HVD004"]

    # an empty reason turns the entry itself into a finding
    bl.write_text(json.dumps({"version": 1, "entries": [{
        "file": str(tmp_path / "snippet.py"), "rule": "HVD004",
        "match": "return time.time()", "count": 1, "reason": ""}]}))
    found = lint_source(tmp_path, src, baseline=str(bl))
    assert sorted(f.rule for f in live(found)) == ["HVD000"]


def test_stale_baseline_entry_is_reported(tmp_path):
    bl = tmp_path / "baseline.json"
    bl.write_text(json.dumps({"version": 1, "entries": [{
        "file": str(tmp_path / "snippet.py"), "rule": "HVD004",
        "match": "return time.time()", "count": 1,
        "reason": "was a wall stamp"}]}))
    found = lint_source(tmp_path, "x = 1\n", baseline=str(bl))
    hits = live(found, "HVD000")
    assert len(hits) == 1 and "stale" in hits[0].message


def test_syntax_error_is_integrity_finding(tmp_path):
    found = lint_source(tmp_path, "def broken(:\n")
    assert [f.rule for f in live(found)] == ["HVD000"]


def test_walk_excludes_pycache_and_native(tmp_path):
    (tmp_path / "__pycache__").mkdir()
    (tmp_path / "__pycache__" / "junk.py").write_text("x = 1\n")
    (tmp_path / "_native").mkdir()
    (tmp_path / "_native" / "gen.py").write_text("x = 1\n")
    (tmp_path / "real.py").write_text("x = 1\n")
    files = iter_python_files([str(tmp_path)])
    assert [os.path.basename(f) for f in files] == ["real.py"]


# ---------------------------------------------------------------------------
# HVD020 — ad-hoc memory probe outside the memory plane
# ---------------------------------------------------------------------------

def test_hvd020_triggers_on_device_memory_stats(tmp_path):
    found = lint_source(tmp_path, """\
        # hvdlint: role=mem_path
        import jax

        def headroom():
            return jax.devices()[0].memory_stats()
        """)
    assert [f.rule for f in live(found)] == ["HVD020"]


def test_hvd020_triggers_on_live_arrays_and_memory_analysis(tmp_path):
    found = lint_source(tmp_path, """\
        # hvdlint: role=mem_path
        import jax

        def audit(compiled):
            n = sum(a.nbytes for a in jax.live_arrays())
            return n, compiled.memory_analysis()
        """)
    assert [f.rule for f in live(found)] == ["HVD020", "HVD020"]


def test_hvd020_memory_plane_wrappers_are_sanctioned(tmp_path):
    # the fix the rule points at: probes routed through utils/memory.py
    found = lint_source(tmp_path, """\
        # hvdlint: role=mem_path
        from horovod_tpu.utils import memory as hvd_memory

        def headroom():
            hvd_memory.get_ledger().account_tree("params", {})
            return hvd_memory.step_peak_bytes()
        """)
    assert live(found, "HVD020") == []


def test_hvd020_scoped_to_trainer_serving_ops(tmp_path):
    # no role marker, not under trainer/serving/ops: out of scope
    found = lint_source(tmp_path, """\
        import jax

        def headroom():
            return jax.devices()[0].memory_stats()
        """)
    assert live(found, "HVD020") == []


def test_hvd020_fires_under_serving_but_not_in_memory_py(tmp_path):
    reg = tmp_path / "fake_config.py"
    reg.write_text(FAKE_REGISTRY)
    src = ("import jax\n\n"
           "def headroom():\n"
           "    return jax.devices()[0].memory_stats()\n")
    serve = tmp_path / "horovod_tpu" / "serving"
    serve.mkdir(parents=True)
    (serve / "probe.py").write_text(src)
    plane = tmp_path / "horovod_tpu" / "utils"
    plane.mkdir(parents=True)
    (plane / "memory.py").write_text(src)
    findings, _ = analyze_paths(
        [str(serve / "probe.py"), str(plane / "memory.py")],
        env_registry_path=str(reg))
    assert [(f.rule, "serving" in f.file) for f in live(findings)] == \
        [("HVD020", True)]


def test_hvd020_suppression_honored(tmp_path):
    found = lint_source(tmp_path, """\
        # hvdlint: role=mem_path
        import jax

        def debug_dump():
            # hvdlint: disable=HVD020(one-shot debug CLI, not a run path)
            return jax.devices()[0].memory_stats()
        """)
    assert live(found) == []
    assert [f.rule for f in found if f.suppressed == "inline"] == \
        ["HVD020"]


# ---------------------------------------------------------------------------
# HVD023 — ad-hoc alert outside the alerting plane
# ---------------------------------------------------------------------------

def test_hvd023_triggers_on_quantile_threshold_with_warning(tmp_path):
    found = lint_source(tmp_path, """\
        # hvdlint: role=alert_path
        import logging
        from horovod_tpu.utils import metrics as hvd_metrics

        log = logging.getLogger(__name__)

        def watch(bounds, counts, slo):
            p99 = hvd_metrics.histogram_quantile(bounds, counts, 0.99)
            if p99 > slo:
                log.warning("ttft p99 %s over slo %s", p99, slo)
        """)
    assert [f.rule for f in live(found)] == ["HVD023"]


def test_hvd023_triggers_on_burn_rate_with_event_and_dump(tmp_path):
    # the full private ladder: burn-rate compare -> event + flight dump
    found = lint_source(tmp_path, """\
        # hvdlint: role=alert_path
        from horovod_tpu.utils import metrics, tracing

        def police(good, bad, target):
            burn_rate = (bad / max(good + bad, 1)) / (1 - target)
            if burn_rate > 4.0:
                metrics.get_registry().event("goodput_burn", burn=burn_rate)
                tracing.dump_on_failure("goodput_burn")
        """)
    assert [f.rule for f in live(found)] == ["HVD023"]


def test_hvd023_compare_without_escalation_is_control_not_alert(tmp_path):
    # thresholding a p99 to *actuate* (no warn/event/dump) is a control
    # decision — the elastic/canary controllers' shape — not an alert
    found = lint_source(tmp_path, """\
        # hvdlint: role=alert_path
        def decide(win, slo):
            ttft_p99 = win.ttft_p99()
            if ttft_p99 > slo:
                return "scale_up"
            return "hold"
        """)
    assert live(found, "HVD023") == []


def test_hvd023_escalation_without_slo_signal_not_flagged(tmp_path):
    # warning on a plain state flag is the storm-ladder shape: no
    # SLO-shaped read in the test, so no finding
    found = lint_source(tmp_path, """\
        # hvdlint: role=alert_path
        import logging

        log = logging.getLogger(__name__)

        def escalate(storming, misses):
            if storming and misses > 4:
                log.warning("recompile storm: %d misses", misses)
        """)
    assert live(found, "HVD023") == []


def test_hvd023_fires_under_router_but_not_in_alerts_py(tmp_path):
    reg = tmp_path / "fake_config.py"
    reg.write_text(FAKE_REGISTRY)
    src = ("import logging\n"
           "log = logging.getLogger(__name__)\n\n"
           "def watch(win, slo):\n"
           "    ttft_p99 = win.p99()\n"
           "    if ttft_p99 > slo:\n"
           "        log.warning('over slo')\n")
    router = tmp_path / "horovod_tpu" / "router"
    router.mkdir(parents=True)
    (router / "watchdog.py").write_text(src)
    plane = tmp_path / "horovod_tpu" / "utils"
    plane.mkdir(parents=True)
    (plane / "alerts.py").write_text(src)
    findings, _ = analyze_paths(
        [str(router / "watchdog.py"), str(plane / "alerts.py")],
        env_registry_path=str(reg))
    assert [(f.rule, "router" in f.file) for f in live(findings)] == \
        [("HVD023", True)]


def test_hvd023_out_of_scope_without_role(tmp_path):
    found = lint_source(tmp_path, """\
        import logging

        log = logging.getLogger(__name__)

        def watch(p99, slo):
            if p99 > slo:
                log.warning("over slo")
        """)
    assert live(found, "HVD023") == []


def test_hvd023_suppression_honored(tmp_path):
    found = lint_source(tmp_path, """\
        # hvdlint: role=alert_path
        import logging

        log = logging.getLogger(__name__)

        def grade(after_p99, baseline_p99, x):
            # hvdlint: disable=HVD023(in-plane grading actuates a rollback; the alerting plane watches hvd_route_breaker_trips_total)
            if after_p99 > x * baseline_p99:
                log.warning("graded change breached; rolling back")
        """)
    assert live(found) == []
    assert [f.rule for f in found if f.suppressed == "inline"] == \
        ["HVD023"]


# ---------------------------------------------------------------------------
# rule catalog + CLI + end-to-end gate
# ---------------------------------------------------------------------------

def test_every_rule_has_catalog_entry():
    assert sorted(RULES) == \
        [f"HVD{i:03d}" for i in range(1, 21)] + ["HVD023"]
    for rule in RULES.values():
        assert rule.summary
        assert len(rule.explain) > 200  # the full story, not a stub


def test_cli_explain_and_json(tmp_path):
    env = dict(os.environ, PYTHONPATH=REPO_ROOT)
    out = subprocess.run(
        [sys.executable, "-m", "tools.hvdlint", "--explain", "HVD002"],
        capture_output=True, text=True, cwd=REPO_ROOT, env=env)
    assert out.returncode == 0
    assert "reset()" in out.stdout

    snippet = tmp_path / "s.py"
    snippet.write_text("import time\nt = time.time()\n")
    out = subprocess.run(
        [sys.executable, "-m", "tools.hvdlint", str(snippet),
         "--format", "json", "--baseline", "none"],
        capture_output=True, text=True, cwd=REPO_ROOT, env=env)
    assert out.returncode == 1
    payload = json.loads(out.stdout)
    assert payload["live"] == 1
    assert payload["findings"][0]["rule"] == "HVD004"


@pytest.mark.slow
def test_repo_lints_clean_end_to_end():
    """The CI gate itself: zero unbaselined findings over the repo."""
    env = dict(os.environ, PYTHONPATH=REPO_ROOT)
    out = subprocess.run(
        [sys.executable, "-m", "tools.hvdlint",
         "horovod_tpu", "tools", "examples"],
        capture_output=True, text=True, cwd=REPO_ROOT, env=env)
    assert out.returncode == 0, out.stdout + out.stderr


@pytest.mark.slow
def test_envdoc_matches_registry_end_to_end():
    env = dict(os.environ, PYTHONPATH=REPO_ROOT)
    out = subprocess.run(
        [sys.executable, "-m", "tools.hvdlint", "--check-envdoc"],
        capture_output=True, text=True, cwd=REPO_ROOT, env=env)
    assert out.returncode == 0, out.stdout + out.stderr
