"""The hvdlint rule set. Every rule encodes a bug class this repo has
actually hit (or a sibling of one); ``--explain HVDnnn`` prints the
``explain`` text below, history included.

Module roles
------------
Two rules are scoped to modules with a declared *role* instead of the
whole tree, because their invariants only hold on specific planes:

  wire  — code that builds or orders cross-rank messages
          (CycleRequest/CycleResponse, fusion plans). HVD001 applies.
  loop  — code that runs inside the paced coordinator/background cycle.
          HVD003 applies.

Roles come from the path lists below, or from a
``# hvdlint: role=wire,loop`` comment in the file (how test fixtures —
and any future module — opt in without editing this file).
"""

import ast
import dataclasses
import re

from .engine import Finding

WIRE_MODULE_SUFFIXES = (
    "horovod_tpu/ops/negotiation.py",
    "horovod_tpu/ops/eager.py",
    "horovod_tpu/ops/fusion.py",
)
LOOP_MODULE_SUFFIXES = (
    "horovod_tpu/ops/negotiation.py",
    "horovod_tpu/ops/eager.py",
)

_ENV_NAME_RE = re.compile(r"^(HVD|HOROVOD)_[A-Z0-9_]+$")
# common/config.py-style helpers: the literal gets a HOROVOD_/HVD_ prefix
_ENV_HELPERS = {"_env", "env_bool", "env_int", "env_float", "env_str"}
# mpi_ops-style helper: literal args are FULL env var names
_ENV_FULLNAME_HELPERS = {"_env_first"}

_LOG_CALL_NAMES = {"debug", "info", "warning", "warn", "error",
                   "exception", "critical", "event", "print_exc",
                   "print"}

_BROAD_EXC_NAMES = {"Exception", "BaseException"}


def _roles_for(ctx):
    roles = set(ctx.roles)
    for suffix in WIRE_MODULE_SUFFIXES:
        if ctx.relpath.endswith(suffix):
            roles.add("wire")
    for suffix in LOOP_MODULE_SUFFIXES:
        if ctx.relpath.endswith(suffix):
            roles.add("loop")
    return roles


def _attr_chain(node):
    """foo.bar.baz -> ["foo", "bar", "baz"]; None if not a pure chain."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return list(reversed(parts))
    return None


def _iter_function_defs(tree):
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            yield node


def _enclosing_class(node):
    cur = getattr(node, "hvdlint_parent", None)
    while cur is not None:
        if isinstance(cur, ast.ClassDef):
            return cur
        cur = getattr(cur, "hvdlint_parent", None)
    return None


class SharedState:
    """Cross-file inputs the rules need: the env registry parsed (not
    imported) from common/config.py. Loaded once per run."""

    def __init__(self, env_registry_path=None):
        from . import envdoc
        self.env_registry_path = (env_registry_path or
                                  envdoc.DEFAULT_REGISTRY_PATH)
        self.env_registry = None
        self.env_registry_error = None
        self.env_lookup = frozenset()
        try:
            self.env_registry = envdoc.load_env_registry(
                self.env_registry_path)
            self.env_lookup = envdoc.registry_lookup(self.env_registry)
        # hvdlint: disable=HVD006(re-surfaced as an HVD005 finding per file)
        except Exception as exc:
            self.env_registry_error = str(exc)


@dataclasses.dataclass
class Rule:
    code: str
    name: str
    summary: str
    explain: str
    checker: object

    def check(self, ctx, shared):
        return list(self.checker(ctx, shared))


# ---------------------------------------------------------------------------
# HVD001 — rank-divergent iteration
# ---------------------------------------------------------------------------

_SET_METHODS = {"union", "difference", "intersection",
                "symmetric_difference", "copy"}
_ORDER_SAFE_WRAPPERS = {"sorted", "len", "sum", "min", "max", "any",
                        "all", "set", "frozenset"}


def _collect_setty_symbols(tree):
    """Names / self-attributes the module ever assigns a set to."""
    names, attrs = set(), set()

    def is_setty(expr):
        if isinstance(expr, (ast.Set, ast.SetComp)):
            return True
        if isinstance(expr, ast.Call):
            if isinstance(expr.func, ast.Name) and \
                    expr.func.id in ("set", "frozenset"):
                return True
            if isinstance(expr.func, ast.Attribute) and \
                    expr.func.attr in _SET_METHODS and \
                    is_setty(expr.func.value):
                return True
        if isinstance(expr, ast.BinOp) and isinstance(
                expr.op, (ast.Sub, ast.BitOr, ast.BitAnd, ast.BitXor)):
            return is_setty(expr.left) or is_setty(expr.right)
        if isinstance(expr, ast.Name):
            return expr.id in names
        if isinstance(expr, ast.Attribute):
            chain = _attr_chain(expr)
            return (chain is not None and len(chain) == 2 and
                    chain[0] == "self" and chain[1] in attrs)
        return False

    # two passes so `a = set(); b = a` converges for the common shapes
    for _ in range(2):
        for node in ast.walk(tree):
            if isinstance(node, ast.Assign):
                value, targets = node.value, node.targets
            elif isinstance(node, ast.AnnAssign) and node.value is not None:
                value, targets = node.value, [node.target]
            else:
                continue
            if not is_setty(value):
                continue
            for t in targets:
                if isinstance(t, ast.Name):
                    names.add(t.id)
                elif isinstance(t, ast.Attribute):
                    chain = _attr_chain(t)
                    if chain and len(chain) == 2 and chain[0] == "self":
                        attrs.add(chain[1])
    return names, attrs, is_setty


def check_rank_divergence(ctx, shared):
    if "wire" not in _roles_for(ctx):
        return
    names, attrs, is_setty = _collect_setty_symbols(ctx.tree)

    def describe(expr):
        if isinstance(expr, ast.Name):
            return f"set '{expr.id}'"
        if isinstance(expr, ast.Attribute):
            return f"set 'self.{expr.attr}'"
        return "a set expression"

    iters = []
    for node in ast.walk(ctx.tree):
        if isinstance(node, (ast.For, ast.AsyncFor)):
            iters.append(node.iter)
        elif isinstance(node, (ast.ListComp, ast.SetComp, ast.DictComp,
                               ast.GeneratorExp)):
            iters.extend(gen.iter for gen in node.generators)
        elif isinstance(node, ast.Call) and \
                isinstance(node.func, ast.Name) and \
                node.func.id in ("list", "tuple") and node.args:
            # list(a_set) / tuple(a_set) materializes the randomized
            # order just as surely as a for-loop does
            iters.append(node.args[0])
        elif isinstance(node, ast.Starred):
            iters.append(node.value)
    for it in iters:
        if is_setty(it):
            yield Finding(
                "HVD001", ctx.relpath, it.lineno, it.col_offset,
                f"iterating {describe(it)} without sorted() in a wire "
                "module: set order is hash-randomized and diverges "
                "across ranks, so anything built from this order "
                "(CycleRequest/CycleResponse contents, fusion plans) "
                "desynchronizes the collective schedule. Wrap the "
                "iterable in sorted().")


# ---------------------------------------------------------------------------
# HVD002 — lock order / self-deadlock
# ---------------------------------------------------------------------------

def _lock_kind_of(value):
    """'lock'/'rlock' for a threading.Lock()/RLock() or
    lockdep.lock(name)/lockdep.rlock(name) construction, else None —
    the sanitizer wrapper (utils/lockdep.py) is a drop-in, so every
    lock-aware rule must see through it."""
    if not (isinstance(value, ast.Call) and
            isinstance(value.func, ast.Attribute) and
            isinstance(value.func.value, ast.Name)):
        return None
    owner, ctor = value.func.value.id, value.func.attr
    if owner == "threading" and ctor in ("Lock", "RLock"):
        return "rlock" if ctor == "RLock" else "lock"
    if owner == "lockdep" and ctor in ("lock", "rlock"):
        if ctor == "rlock":
            return "rlock"
        for kw in value.keywords:
            if kw.arg == "reentrant" and \
                    isinstance(kw.value, ast.Constant) and kw.value.value:
                return "rlock"
        return "lock"
    return None


def _lock_defs(tree):
    """Map lock symbols to kind. Keys: ("mod", name) for module-level
    locks, ("cls", ClassName, attr) for self.<attr> locks."""
    locks = {}
    for node in ast.walk(tree):
        if not isinstance(node, ast.Assign):
            continue
        value = node.value
        kind = _lock_kind_of(value)
        if kind is None:
            continue
        for t in node.targets:
            if isinstance(t, ast.Name):
                cls = _enclosing_class(node)
                if cls is None:
                    locks[("mod", t.id)] = kind
                else:
                    locks[("cls", cls.name, t.id)] = kind
            elif isinstance(t, ast.Attribute):
                chain = _attr_chain(t)
                cls = _enclosing_class(node)
                if chain and len(chain) == 2 and chain[0] == "self" and \
                        cls is not None:
                    locks[("cls", cls.name, chain[1])] = kind
    return locks


def _resolve_lock(expr, cls_name, locks):
    """Lock key for an expression like `self._lock` / `_registry_lock`
    (also unwraps `X.acquire`-style attribute tails upstream)."""
    if isinstance(expr, ast.Name):
        key = ("mod", expr.id)
        return key if key in locks else None
    chain = _attr_chain(expr)
    if chain and len(chain) == 2 and chain[0] == "self" and cls_name:
        key = ("cls", cls_name, chain[1])
        return key if key in locks else None
    return None


def _direct_acquisitions(func, cls_name, locks):
    """Lock keys a function acquires directly (with-blocks + .acquire)."""
    acquired = set()
    for node in ast.walk(func):
        if isinstance(node, ast.With):
            for item in node.items:
                key = _resolve_lock(item.context_expr, cls_name, locks)
                if key:
                    acquired.add(key)
        elif isinstance(node, ast.Call) and \
                isinstance(node.func, ast.Attribute) and \
                node.func.attr == "acquire":
            key = _resolve_lock(node.func.value, cls_name, locks)
            if key:
                acquired.add(key)
    return acquired


def check_lock_order(ctx, shared):
    locks = _lock_defs(ctx.tree)
    if not locks:
        return []

    # function tables for the one-module call graph
    mod_funcs = {}    # name -> FunctionDef (module top level)
    methods = {}      # (cls, name) -> FunctionDef
    for node in ctx.tree.body:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            mod_funcs[node.name] = node
        elif isinstance(node, ast.ClassDef):
            for sub in node.body:
                if isinstance(sub, (ast.FunctionDef,
                                    ast.AsyncFunctionDef)):
                    methods[(node.name, sub.name)] = sub

    def fkey_of_call(call, cls_name):
        func = call.func
        if isinstance(func, ast.Name) and func.id in mod_funcs:
            return ("f", func.id)
        if isinstance(func, ast.Attribute) and \
                isinstance(func.value, ast.Name) and \
                func.value.id == "self" and cls_name and \
                (cls_name, func.attr) in methods:
            return ("m", cls_name, func.attr)
        return None

    def fnode(fkey):
        return mod_funcs[fkey[1]] if fkey[0] == "f" else methods[
            (fkey[1], fkey[2])]

    def fcls(fkey):
        return None if fkey[0] == "f" else fkey[1]

    closure_memo = {}

    def closure(fkey, stack=()):
        """Locks acquired by fkey or (transitively) its same-module
        callees."""
        if fkey in closure_memo:
            return closure_memo[fkey]
        if fkey in stack:
            return set()
        func = fnode(fkey)
        acq = set(_direct_acquisitions(func, fcls(fkey), locks))
        for node in ast.walk(func):
            if isinstance(node, ast.Call):
                callee = fkey_of_call(node, fcls(fkey))
                if callee is not None:
                    acq |= closure(callee, stack + (fkey,))
        closure_memo[fkey] = acq
        return acq

    findings = []
    # (lock_a, lock_b) -> first (line, col) where b was taken under a
    nesting_pairs = {}

    def visit(node, held, cls_name):
        if isinstance(node, ast.With):
            new = []
            for item in node.items:
                key = _resolve_lock(item.context_expr, cls_name, locks)
                if key is None:
                    continue
                if key in held and locks[key] == "lock":
                    findings.append(Finding(
                        "HVD002", ctx.relpath, node.lineno,
                        node.col_offset,
                        f"re-acquiring non-reentrant lock "
                        f"'{_lock_name(key)}' already held in this "
                        "function: guaranteed self-deadlock (the "
                        "metrics-registry reset() bug class)."))
                for h in held:
                    if h != key:
                        nesting_pairs.setdefault(
                            (h, key), (node.lineno, node.col_offset))
                new.append(key)
            for child in ast.iter_child_nodes(node):
                visit(child, held + new, cls_name)
            return
        if isinstance(node, ast.Call):
            # direct re-acquire via .acquire()
            if isinstance(node.func, ast.Attribute) and \
                    node.func.attr == "acquire":
                key = _resolve_lock(node.func.value, cls_name, locks)
                if key is not None and key in held and \
                        locks[key] == "lock":
                    findings.append(Finding(
                        "HVD002", ctx.relpath, node.lineno,
                        node.col_offset,
                        f"acquire() on non-reentrant lock "
                        f"'{_lock_name(key)}' while it is already held "
                        "in this function: guaranteed self-deadlock."))
            # call into a same-module function that takes a held lock
            callee = fkey_of_call(node, cls_name)
            if callee is not None and held:
                callee_locks = closure(callee)
                for h in held:
                    if h in callee_locks and locks[h] == "lock":
                        findings.append(Finding(
                            "HVD002", ctx.relpath, node.lineno,
                            node.col_offset,
                            f"call to '{_callee_name(callee)}' while "
                            f"holding non-reentrant lock "
                            f"'{_lock_name(h)}', which it (or a callee) "
                            "acquires again: self-deadlock — the exact "
                            "shape of the metrics-registry reset() bug "
                            "fixed in the telemetry PR."))
                    for k in callee_locks:
                        if k != h:
                            nesting_pairs.setdefault(
                                (h, k), (node.lineno, node.col_offset))
        for child in ast.iter_child_nodes(node):
            visit(child, held, cls_name)

    for name, func in mod_funcs.items():
        visit(func, [], None)
    for (cls, name), func in methods.items():
        visit(func, [], cls)

    # inconsistent ordering: A->B somewhere and B->A somewhere else
    reported = set()
    for (a, b), (line, col) in sorted(nesting_pairs.items(),
                                      key=lambda kv: kv[1]):
        if (b, a) in nesting_pairs and frozenset((a, b)) not in reported:
            reported.add(frozenset((a, b)))
            other_line = nesting_pairs[(b, a)][0]
            findings.append(Finding(
                "HVD002", ctx.relpath, line, col,
                f"inconsistent lock order: '{_lock_name(a)}' -> "
                f"'{_lock_name(b)}' here but '{_lock_name(b)}' -> "
                f"'{_lock_name(a)}' at line {other_line}; two threads "
                "taking these paths concurrently deadlock. Pick one "
                "global order."))
    return findings


def _lock_name(key):
    return key[1] if key[0] == "mod" else f"{key[1]}.{key[2]}"


def _callee_name(fkey):
    return fkey[1] if fkey[0] == "f" else f"{fkey[1]}.{fkey[2]}"


# ---------------------------------------------------------------------------
# HVD003 — blocking call in the coordinator loop
# ---------------------------------------------------------------------------

_SUBPROC_BLOCKING = {"run", "check_output", "check_call", "call",
                     "communicate"}


def check_blocking_in_loop(ctx, shared):
    if "loop" not in _roles_for(ctx):
        return
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call):
            continue
        chain = _attr_chain(node.func)
        kwargs = {k.arg for k in node.keywords}
        if chain == ["time", "sleep"] and node.args and \
                isinstance(node.args[0], ast.Constant) and \
                isinstance(node.args[0].value, (int, float)) and \
                node.args[0].value >= 1.0:
            yield Finding(
                "HVD003", ctx.relpath, node.lineno, node.col_offset,
                f"time.sleep({node.args[0].value}) in a coordinator-loop "
                "module: a sleep at or above 1 s stalls the negotiation "
                "cycle (5 ms cadence) for every rank. Sleep the cycle "
                "time, or move the wait off the loop thread.")
        elif chain == ["socket", "create_connection"] and \
                "timeout" not in kwargs and len(node.args) < 2:
            yield Finding(
                "HVD003", ctx.relpath, node.lineno, node.col_offset,
                "socket.create_connection without a timeout in a "
                "coordinator-loop module: a silent peer blocks the "
                "cycle forever. Pass timeout=.")
        elif chain and chain[-1] == "settimeout" and node.args and \
                isinstance(node.args[0], ast.Constant) and \
                node.args[0].value is None:
            yield Finding(
                "HVD003", ctx.relpath, node.lineno, node.col_offset,
                "settimeout(None) in a coordinator-loop module makes the "
                "socket blocking with no bound; the cycle hangs with a "
                "silent peer.")
        elif chain and len(chain) >= 2 and chain[-1] in ("wait", "join") \
                and not node.args and not node.keywords:
            yield Finding(
                "HVD003", ctx.relpath, node.lineno, node.col_offset,
                f"unbounded .{chain[-1]}() in a coordinator-loop module: "
                "pass a timeout so a dead peer/thread cannot hang the "
                "cycle (liveness escalation needs the loop to keep "
                "turning).")
        elif isinstance(node.func, ast.Name) and node.func.id == "open":
            yield Finding(
                "HVD003", ctx.relpath, node.lineno, node.col_offset,
                "file I/O in a coordinator-loop module: disk latency "
                "(NFS, page cache miss) stalls every rank's cycle. "
                "Queue to a writer thread (utils/timeline.py pattern).")
        elif chain and chain[0] == "subprocess" and \
                chain[-1] in _SUBPROC_BLOCKING and "timeout" not in kwargs:
            yield Finding(
                "HVD003", ctx.relpath, node.lineno, node.col_offset,
                f"subprocess.{chain[-1]} without timeout= in a "
                "coordinator-loop module blocks the cycle on an external "
                "process.")


# ---------------------------------------------------------------------------
# HVD004 — raw wall clock
# ---------------------------------------------------------------------------

def check_raw_clock(ctx, shared):
    # `from time import time` aliases
    aliases = set()
    for node in ast.walk(ctx.tree):
        if isinstance(node, ast.ImportFrom) and node.module == "time":
            for a in node.names:
                if a.name in ("time", "time_ns"):
                    aliases.add(a.asname or a.name)
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call):
            continue
        chain = _attr_chain(node.func)
        hit = (chain in (["time", "time"], ["time", "time_ns"]) or
               (isinstance(node.func, ast.Name) and
                node.func.id in aliases))
        if hit:
            yield Finding(
                "HVD004", ctx.relpath, node.lineno, node.col_offset,
                "raw wall-clock read: timeline and metrics correlate "
                "through utils.metrics.shared_clock() (monotonic base + "
                "one epoch anchor). Use shared_clock().ts_us() / "
                ".epoch_us(); only genuinely cross-process wall-clock "
                "stamps may stay, with a disable reason.")


# ---------------------------------------------------------------------------
# HVD005 — env-registry drift
# ---------------------------------------------------------------------------

def _call_name(node):
    """Last path segment of the callee: f() -> "f", mod.f() -> "f"."""
    if isinstance(node.func, ast.Name):
        return node.func.id
    if isinstance(node.func, ast.Attribute):
        return node.func.attr
    return None


def _env_reads(tree):
    """Yield (node, env_name) for every literal HVD_*/HOROVOD_* env
    access: os.environ get/[]/in/pop/setdefault, os.getenv, and the
    repo's config-helper calls (env_bool("X") reads HOROVOD_X/HVD_X)."""
    def literal(arg):
        return arg.value if isinstance(arg, ast.Constant) and \
            isinstance(arg.value, str) else None

    for node in ast.walk(tree):
        if isinstance(node, ast.Call):
            chain = _attr_chain(node.func)
            if chain and len(chain) >= 3 and chain[-2] == "environ" and \
                    chain[-1] in ("get", "pop", "setdefault") and \
                    node.args:
                name = literal(node.args[0])
                if name and _ENV_NAME_RE.match(name):
                    yield node, name
            elif chain and chain[-1] == "getenv" and node.args:
                name = literal(node.args[0])
                if name and _ENV_NAME_RE.match(name):
                    yield node, name
            elif _call_name(node) in _ENV_HELPERS and node.args:
                name = literal(node.args[0])
                if name and not _ENV_NAME_RE.match(name) and \
                        _ENV_NAME_RE.match("HOROVOD_" + name):
                    yield node, "HOROVOD_" + name
            elif _call_name(node) in _ENV_FULLNAME_HELPERS:
                for arg in node.args:
                    name = literal(arg)
                    if name and _ENV_NAME_RE.match(name):
                        yield node, name
        elif isinstance(node, ast.Subscript):
            chain = _attr_chain(node.value)
            if chain and chain[-1] == "environ":
                name = literal(node.slice)
                if name and _ENV_NAME_RE.match(name):
                    yield node, name
        elif isinstance(node, ast.Compare) and len(node.ops) == 1 and \
                isinstance(node.ops[0], (ast.In, ast.NotIn)):
            chain = _attr_chain(node.comparators[0])
            if chain and chain[-1] == "environ":
                name = literal(node.left)
                if name and _ENV_NAME_RE.match(name):
                    yield node, name


def check_env_registry(ctx, shared):
    reads = list(_env_reads(ctx.tree))
    if not reads:
        return
    if shared.env_registry_error is not None:
        yield Finding(
            "HVD005", ctx.relpath, reads[0][0].lineno,
            reads[0][0].col_offset,
            f"cannot load ENV_REGISTRY from "
            f"{shared.env_registry_path}: {shared.env_registry_error}")
        return
    for node, name in reads:
        if name not in shared.env_lookup:
            yield Finding(
                "HVD005", ctx.relpath, node.lineno, node.col_offset,
                f"env var '{name}' is read here but not registered: add "
                "it to ENV_REGISTRY in horovod_tpu/common/config.py "
                "(name, default, owner, description) and regenerate "
                "docs/envvars.md with `python -m tools.hvdlint "
                "--emit-envdoc docs/envvars.md`.")


# ---------------------------------------------------------------------------
# HVD006 — swallowed exception
# ---------------------------------------------------------------------------

def _is_broad(handler_type):
    if handler_type is None:  # bare except:
        return True
    if isinstance(handler_type, ast.Name):
        return handler_type.id in _BROAD_EXC_NAMES
    if isinstance(handler_type, ast.Tuple):
        return any(_is_broad(e) for e in handler_type.elts)
    return False


def check_swallowed_exception(ctx, shared):
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.ExceptHandler):
            continue
        if not _is_broad(node.type):
            continue
        handled = False
        for sub in ast.walk(ast.Module(body=node.body,
                                       type_ignores=[])):
            if isinstance(sub, ast.Raise):
                handled = True
                break
            if isinstance(sub, ast.Call):
                fn = sub.func
                name = fn.attr if isinstance(fn, ast.Attribute) else (
                    fn.id if isinstance(fn, ast.Name) else None)
                if name in _LOG_CALL_NAMES:
                    handled = True
                    break
        if not handled:
            yield Finding(
                "HVD006", ctx.relpath, node.lineno, node.col_offset,
                "broad except that neither re-raises nor logs: on a "
                "control/data-plane path this turns real faults "
                "(mismatched collectives, dead peers, corrupt caches) "
                "into silent divergence. Narrow the exception type, log "
                "via common.hvd_logging, re-raise — or disable with a "
                "reason if swallowing is genuinely correct.")


# ---------------------------------------------------------------------------
# HVD007 — jit purity
# ---------------------------------------------------------------------------

_TRACER_NAMES = {"jit", "pjit", "pmap", "pallas_call", "shard_map"}
_IMPURE_TIME = {"time", "time_ns", "sleep", "monotonic", "perf_counter"}


def _is_tracer_expr(expr):
    """jax.jit / jit / pl.pallas_call / partial(jax.jit, ...) /
    jax.jit(...) used as a decorator factory."""
    chain = _attr_chain(expr)
    if chain and chain[-1] in _TRACER_NAMES:
        return True
    if isinstance(expr, ast.Call):
        fchain = _attr_chain(expr.func)
        if fchain and fchain[-1] in _TRACER_NAMES:
            return True
        if fchain and fchain[-1] == "partial" and expr.args:
            return _is_tracer_expr(expr.args[0])
    return False


def _traced_functions(tree):
    traced = []
    # decorated defs
    for func in _iter_function_defs(tree):
        if any(_is_tracer_expr(d) for d in func.decorator_list):
            traced.append(func)
    # defs/lambdas passed to jit(f) / pallas_call(f) / shard_map(f, ...)
    local_defs = {}
    for func in _iter_function_defs(tree):
        local_defs.setdefault(func.name, func)
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        fchain = _attr_chain(node.func)
        if not (fchain and fchain[-1] in _TRACER_NAMES):
            continue
        for arg in node.args[:1]:
            if isinstance(arg, ast.Lambda):
                traced.append(arg)
            elif isinstance(arg, ast.Name) and arg.id in local_defs:
                traced.append(local_defs[arg.id])
    return traced


def check_jit_purity(ctx, shared):
    seen = set()
    emitted = set()  # (line, col): os.environ.get() flags once, not as
    #                  both the Call and its inner Attribute
    for func in _traced_functions(ctx.tree):
        if id(func) in seen:
            continue
        seen.add(id(func))
        for node in ast.walk(func):
            impure = None
            if isinstance(node, ast.Call):
                chain = _attr_chain(node.func)
                if isinstance(node.func, ast.Name) and \
                        node.func.id in ("print", "input", "open"):
                    impure = f"{node.func.id}()"
                elif chain and chain[0] == "time" and len(chain) == 2 \
                        and chain[1] in _IMPURE_TIME:
                    impure = f"time.{chain[1]}()"
                elif chain and chain[0] == "random":
                    impure = "random.*"
                elif chain and len(chain) >= 2 and \
                        chain[0] in ("np", "numpy") and \
                        chain[1] == "random":
                    impure = "numpy.random.*"
                elif chain and len(chain) >= 2 and \
                        chain[:2] == ["os", "environ"]:
                    impure = "os.environ"
            elif isinstance(node, (ast.Subscript, ast.Attribute)):
                chain = _attr_chain(node if isinstance(
                    node, ast.Attribute) else node.value)
                if chain and chain[:2] == ["os", "environ"] and \
                        len(chain) == 2:
                    impure = "os.environ"
            if impure:
                if (node.lineno, node.col_offset) in emitted:
                    continue
                emitted.add((node.lineno, node.col_offset))
                yield Finding(
                    "HVD007", ctx.relpath, node.lineno, node.col_offset,
                    f"Python side effect ({impure}) inside a "
                    "jit/pjit/pallas-traced function: it runs at TRACE "
                    "time (once per compilation, not per step) and its "
                    "value is baked into the compiled graph — silent "
                    "staleness plus rank divergence if ranks trace at "
                    "different moments. Hoist it out of the traced "
                    "function, or use jax.debug.* / io_callback.")


# ---------------------------------------------------------------------------
# HVD008 — span leak
# ---------------------------------------------------------------------------

_SPAN_CLOSERS = {"close", "abort"}


def _is_span_call(node):
    """A tracing-plane span open: ``<tracer>.span(...)`` where the
    receiver is something tracer-shaped — a name/attribute containing
    'tracer' (``self._tracer``, ``tracer``) or a ``get_tracer()`` call
    chain (``hvd_tracing.get_tracer().span(...)``)."""
    if not isinstance(node, ast.Call):
        return False
    fn = node.func
    if not (isinstance(fn, ast.Attribute) and fn.attr == "span"):
        return False
    val = fn.value
    if isinstance(val, ast.Call):
        chain = _attr_chain(val.func)
        return bool(chain) and chain[-1] == "get_tracer"
    chain = _attr_chain(val)
    return bool(chain) and "tracer" in chain[-1].lower()


def _unwrap_span_chain(node):
    """``tracer.span(...).annotate(...)`` still yields the span."""
    while (isinstance(node, ast.Call) and
           isinstance(node.func, ast.Attribute) and
           node.func.attr == "annotate"):
        node = node.func.value
    return node


def _walk_scope(body):
    """Every node under ``body`` WITHOUT descending into nested function
    definitions — span lifetime is judged within one lexical scope."""
    out = []
    stack = list(body)
    while stack:
        n = stack.pop()
        out.append(n)
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef,
                          ast.Lambda)):
            continue  # inner scope: judged on its own pass
        stack.extend(ast.iter_child_nodes(n))
    return out


def _name_escapes(scope_nodes, name):
    """True if ``name`` reaches a close/abort call OR escapes the scope
    (returned, yielded, passed to a call, stored on an object, used as a
    context manager) — any of which hands off close responsibility."""
    for node in scope_nodes:
        if isinstance(node, ast.Call):
            fn = node.func
            if (isinstance(fn, ast.Attribute) and
                    fn.attr in _SPAN_CLOSERS and
                    isinstance(fn.value, ast.Name) and
                    fn.value.id == name):
                return True
            for arg in list(node.args) + [kw.value for kw in node.keywords]:
                if isinstance(arg, ast.Name) and arg.id == name:
                    return True
        elif isinstance(node, (ast.Return, ast.Yield)):
            for sub in ast.walk(node):
                if isinstance(sub, ast.Name) and sub.id == name:
                    return True
        elif isinstance(node, ast.withitem):
            ce = node.context_expr
            if isinstance(ce, ast.Name) and ce.id == name:
                return True
        elif isinstance(node, ast.Assign):
            if isinstance(node.value, ast.Name) and node.value.id == name:
                if any(isinstance(t, (ast.Attribute, ast.Subscript))
                       for t in node.targets):
                    return True
    return False


def check_span_leak(ctx, shared):
    scopes = [ctx.tree.body] + \
        [f.body for f in _iter_function_defs(ctx.tree)]
    for body in scopes:
        scope_nodes = _walk_scope(body)
        for node in scope_nodes:
            if isinstance(node, ast.Expr) and \
                    _is_span_call(_unwrap_span_chain(node.value)):
                yield Finding(
                    "HVD008", ctx.relpath, node.lineno, node.col_offset,
                    "span opened and immediately discarded: nothing can "
                    "ever close() or abort() it, so it stays in the "
                    "tracer's open-span table forever and the flight "
                    "recorder reports it as eternally in flight. Use the "
                    "context-manager form (`with tracer.span(...)`) or "
                    "keep the reference and close it on every path.")
            elif isinstance(node, ast.Assign) and \
                    len(node.targets) == 1 and \
                    isinstance(node.targets[0], ast.Name) and \
                    _is_span_call(_unwrap_span_chain(node.value)):
                name = node.targets[0].id
                if not _name_escapes(scope_nodes, name):
                    yield Finding(
                        "HVD008", ctx.relpath, node.lineno,
                        node.col_offset,
                        f"span assigned to '{name}' but no close()/"
                        "abort() (or escape: return/yield/arg-pass/"
                        "attribute store/with) is reachable in this "
                        "scope — the span leaks open and pollutes the "
                        "flight recorder's open-span table. Close it on "
                        "every path or use the context-manager form.")


# ---------------------------------------------------------------------------
# HVD009 — ad-hoc numerics probe
# ---------------------------------------------------------------------------

# the isnan family: any call whose terminal attribute (jnp.isnan,
# np.isfinite, math.isinf, jax.numpy.nan_to_num) or bare imported name
# is one of these is gradient-health math and belongs in the sanctioned
# module
_NUMERICS_PROBE_NAMES = {"isnan", "isinf", "isfinite", "isposinf",
                         "isneginf", "nan_to_num"}
_NUMERICS_SANCTIONED_SUFFIXES = ("horovod_tpu/utils/numerics.py",)


def check_adhoc_numerics(ctx, shared):
    if ctx.relpath.endswith(_NUMERICS_SANCTIONED_SUFFIXES):
        return
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call):
            continue
        if isinstance(node.func, ast.Name):
            probe = node.func.id
        else:
            chain = _attr_chain(node.func)
            probe = chain[-1] if chain else None
        if probe in _NUMERICS_PROBE_NAMES:
            yield Finding(
                "HVD009", ctx.relpath, node.lineno, node.col_offset,
                f"ad-hoc numerics probe '{probe}(...)': gradient-health "
                "math outside utils/numerics.py. Per-tensor nan/inf and "
                "norm checks must ride the fused one-pass stats path "
                "(utils/numerics.py tensor_stats/segment_stats, or "
                "fusion.bucket_stats) so the <=2% overhead contract and "
                "the cross-rank digest stay honest — a stray isnan scan "
                "is a second full pass over the gradient and its result "
                "never reaches the divergence sentinel.")


# ---------------------------------------------------------------------------
# HVD010 — wire-dtype cast outside the codec registry
# ---------------------------------------------------------------------------

# dtypes that only exist as wire/quantization formats in this codebase:
# a direct .astype() to one of these is an encode, and encodes belong to
# the codec registry so the negotiated plan stays the single source of
# truth for what crosses the wire
_WIRE_DTYPE_NAMES = {"int8", "uint8", "float8_e4m3fn", "float8_e4m3",
                     "float8_e5m2"}
_QUANT_SANCTIONED_SUFFIXES = ("horovod_tpu/ops/quantization.py",
                              "horovod_tpu/ops/compression.py")


def _wire_dtype_of(node):
    """The wire-dtype name an astype argument resolves to, if any:
    jnp.int8 / np.int8 / bare int8 / "int8" / np.dtype("int8")."""
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value if node.value in _WIRE_DTYPE_NAMES else None
    if isinstance(node, ast.Name):
        return node.id if node.id in _WIRE_DTYPE_NAMES else None
    chain = _attr_chain(node)
    if chain and chain[-1] in _WIRE_DTYPE_NAMES:
        return chain[-1]
    if isinstance(node, ast.Call):
        fchain = _attr_chain(node.func)
        if fchain and fchain[-1] == "dtype" and node.args:
            return _wire_dtype_of(node.args[0])
    return None


def check_wire_dtype_cast(ctx, shared):
    if ctx.relpath.endswith(_QUANT_SANCTIONED_SUFFIXES):
        return
    for node in ast.walk(ctx.tree):
        if not (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr == "astype"
                and node.args):
            continue
        name = _wire_dtype_of(node.args[0])
        if name:
            yield Finding(
                "HVD010", ctx.relpath, node.lineno, node.col_offset,
                f"direct wire-dtype cast '.astype({name})' outside the "
                "codec registry: a bare narrow cast drops the per-block "
                "scales, skips error feedback, and bypasses the "
                "negotiated per-tensor codec plan — peers decode "
                "garbage or the sums silently lose 2-3 decimal digits. "
                "Encode through ops/quantization.py "
                "(encode/wire_dtype) or a registered codec "
                "(Compression.from_name), the two sanctioned homes for "
                "wire-width casts.")


# ---------------------------------------------------------------------------
# HVD011 — blocking host sync in the serving decode loop
# ---------------------------------------------------------------------------

# the serving plane's decode-loop modules: code that runs once per
# generated token. Fixture files opt in with `# hvdlint: role=serve_loop`.
_SERVE_LOOP_SUFFIXES = (
    "horovod_tpu/serving/engine.py",
    "horovod_tpu/serving/decode.py",
    "horovod_tpu/serving/sampling.py",
    "horovod_tpu/serving/kv_cache.py",
)
# numpy receivers whose asarray() forces a device->host transfer when
# handed a jax array (jnp.asarray is the opposite direction and fine)
_HOST_NUMPY_NAMES = {"np", "numpy", "onp"}


def check_decode_host_sync(ctx, shared):
    if not ("serve_loop" in ctx.roles or
            ctx.relpath.endswith(_SERVE_LOOP_SUFFIXES)):
        return
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call):
            continue
        sync = None
        if isinstance(node.func, ast.Attribute) and \
                node.func.attr == "block_until_ready":
            sync = ".block_until_ready()"
        else:
            chain = _attr_chain(node.func)
            if chain:
                if chain[-1] == "device_get":
                    sync = ".".join(chain) + "(...)"
                elif chain[-1] == "asarray" and (
                        len(chain) == 1 or chain[0] in _HOST_NUMPY_NAMES):
                    sync = ".".join(chain) + "(...)"
        if sync:
            yield Finding(
                "HVD011", ctx.relpath, node.lineno, node.col_offset,
                f"blocking host sync '{sync}' in a serving decode-loop "
                "module: every device_get/block_until_ready/np.asarray "
                "on a device value stalls the decode step for a full "
                "host round-trip, and at one call per token that is THE "
                "classic inter-token-latency killer. The engine's "
                "contract is exactly one sanctioned readback per decode "
                "step (the sampled token batch) and one per prefill "
                "(the first token) — both carry an inline disable with "
                "a reason. Keep everything else on device.")


# ---------------------------------------------------------------------------
# HVD012 — ad-hoc training-state serialization outside the checkpoint plane
# ---------------------------------------------------------------------------

# array-dump entry points that write training state to disk without the
# checkpoint plane's commit protocol (atomic rename, checksums, manifest)
_SERIALIZE_CALL_NAMES = {"save", "savez", "savez_compressed"}
_SERIALIZE_RECEIVERS = {"np", "numpy", "onp", "jnp", "torch"}
_CKPT_SANCTIONED_SUFFIXES = ("horovod_tpu/utils/checkpoint.py",)


def check_adhoc_serialization(ctx, shared):
    if ctx.relpath.endswith(_CKPT_SANCTIONED_SUFFIXES):
        return
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call):
            continue
        chain = _attr_chain(node.func)
        if not chain or len(chain) < 2:
            continue
        if chain[-1] in _SERIALIZE_CALL_NAMES and \
                chain[0] in _SERIALIZE_RECEIVERS:
            call = ".".join(chain)
            yield Finding(
                "HVD012", ctx.relpath, node.lineno, node.col_offset,
                f"ad-hoc training-state serialization '{call}(...)' "
                "outside the checkpoint plane: a bare array dump has no "
                "atomic commit (a crash mid-write leaves a torn file "
                "that loads as garbage), no checksums (bit rot restores "
                "silently), no manifest (restores cannot validate "
                "completeness), and no retention/GC. Route durable "
                "state through utils/checkpoint.py — "
                "CheckpointManager.save for the step loop, "
                "checkpoint.save for one-shot dumps — so every byte on "
                "disk rides the commit protocol docs/checkpoint.md "
                "documents and the torture tests exercise.")


# ---------------------------------------------------------------------------
# HVD013 — ad-hoc step timing in hot-path modules
# ---------------------------------------------------------------------------

# the planes where a stray timer means a parallel, unpublished timing
# story: collective ops, the serving loop, and the trainer itself
_HOT_PATH_DIRS = ("horovod_tpu/ops/", "horovod_tpu/serving/")
_HOT_PATH_SUFFIXES = ("horovod_tpu/trainer.py",)
_STEP_TIMER_CALLS = {"perf_counter", "perf_counter_ns"}


def _inside_instrument_step(node):
    """True when the call sits lexically inside trainer.instrument_step
    (including its nested ``wrapped`` closure) — the ONE sanctioned
    step timer."""
    cur = getattr(node, "hvdlint_parent", None)
    while cur is not None:
        if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef)) and \
                cur.name == "instrument_step":
            return True
        cur = getattr(cur, "hvdlint_parent", None)
    return False


def check_adhoc_step_timer(ctx, shared):
    if not ("hot_path" in ctx.roles or
            any(d in ctx.relpath for d in _HOT_PATH_DIRS) or
            ctx.relpath.endswith(_HOT_PATH_SUFFIXES)):
        return
    # `from time import perf_counter` aliases
    aliases = set()
    for node in ast.walk(ctx.tree):
        if isinstance(node, ast.ImportFrom) and node.module == "time":
            for a in node.names:
                if a.name in _STEP_TIMER_CALLS:
                    aliases.add(a.asname or a.name)
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call):
            continue
        chain = _attr_chain(node.func)
        hit = ((chain is not None and len(chain) == 2 and
                chain[0] == "time" and chain[1] in _STEP_TIMER_CALLS) or
               (isinstance(node.func, ast.Name) and
                node.func.id in aliases))
        if not hit or _inside_instrument_step(node):
            continue
        yield Finding(
            "HVD013", ctx.relpath, node.lineno, node.col_offset,
            "ad-hoc step timer in a hot-path module: a raw "
            "perf_counter() here starts a parallel timing story that "
            "never reaches the metrics registry or the perf-attribution "
            "gauges — the numbers it produces get "
            "compared against instrumented ones and the discrepancy "
            "burns a debugging day. Step walls belong to "
            "trainer.instrument_step (hvd_step_seconds + the attribution "
            "gauges); sub-step durations belong to utils/profiling "
            "captures; timestamps belong to "
            "utils.metrics.shared_clock(). Keep a local timer only with "
            "a disable reason naming what it measures and why no shared "
            "instrument fits.")


# ---------------------------------------------------------------------------
# HVD014 — ad-hoc per-request timing outside the request-trace layer
# ---------------------------------------------------------------------------

# serving/tracing.py is the one sanctioned place for request timing;
# everywhere else in the serving plane a clock delta against a request
# timestamp is a rival latency story
_SERVE_DIR = "horovod_tpu/serving/"
_SERVE_TRACE_LAYER = "serving/tracing.py"
# request-lifecycle timestamp attributes: subtracting one measures a
# request phase
_REQUEST_TS_ATTRS = {"arrival_ts", "last_token_ts", "finish_ts"}


def check_adhoc_request_timer(ctx, shared):
    if "serve_path" not in ctx.roles and not (
            _SERVE_DIR in ctx.relpath and
            not ctx.relpath.endswith(_SERVE_TRACE_LAYER)):
        return
    for node in ast.walk(ctx.tree):
        if not (isinstance(node, ast.BinOp) and
                isinstance(node.op, ast.Sub)):
            continue
        attr = next((side.attr for side in (node.left, node.right)
                     if isinstance(side, ast.Attribute) and
                     side.attr in _REQUEST_TS_ATTRS), None)
        if attr is None:
            continue
        yield Finding(
            "HVD014", ctx.relpath, node.lineno, node.col_offset,
            f"ad-hoc per-request timer in the serving plane: a clock "
            f"delta against a request timestamp ({attr}) measures a "
            f"phase the request-trace layer already accounts. "
            "serving/tracing.py is the one sanctioned place for "
            "request timing — it publishes the queue_wait/requeue/"
            "prefill/decode/scheduler_stall decomposition to the "
            "flight recorder, hvd_serve_phase_seconds, and the "
            "hvd_slo tail analyzer. A second stopwatch here produces "
            "a latency number with different boundaries (no requeue "
            "credit, no stall residual) that never reaches the tail "
            "report, and the two numbers get debugged against each "
            "other. Route the measurement through RequestTrace or "
            "annotate its spans; keep a local delta only with a "
            "disable reason naming the SLO instrument that consumes "
            "it.")


# ---------------------------------------------------------------------------
# HVD015 — ad-hoc weight loading in the serving plane
# ---------------------------------------------------------------------------

# checkpoint/param-load entry points that put weights into a serving
# process without the fleet plane's verify-then-arm protocol
_WEIGHT_LOAD_CALLS = {"restore", "restore_with_extra", "load", "resume"}
_WEIGHT_LOAD_RECEIVERS = {"checkpoint", "hvd_checkpoint", "ckpt",
                          "manager", "np", "numpy", "onp", "jnp",
                          "torch"}
_WEIGHT_PLANE_DIRS = ("horovod_tpu/serving/", "horovod_tpu/fleet/")
_SUBSCRIBER_LAYER = "fleet/subscriber.py"


def check_adhoc_weight_load(ctx, shared):
    if "serve_path" not in ctx.roles and not any(
            d in ctx.relpath for d in _WEIGHT_PLANE_DIRS):
        return
    if ctx.relpath.endswith(_SUBSCRIBER_LAYER):
        return  # the one sanctioned weight-load path
    # `from ...checkpoint import restore` aliases
    aliases = set()
    for node in ast.walk(ctx.tree):
        if isinstance(node, ast.ImportFrom) and node.module and \
                node.module.rsplit(".", 1)[-1] == "checkpoint":
            for a in node.names:
                if a.name in _WEIGHT_LOAD_CALLS:
                    aliases.add(a.asname or a.name)
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call):
            continue
        chain = _attr_chain(node.func)
        hit = ((chain is not None and len(chain) >= 2 and
                chain[-1] in _WEIGHT_LOAD_CALLS and
                chain[-2] in _WEIGHT_LOAD_RECEIVERS) or
               (isinstance(node.func, ast.Name) and
                node.func.id in aliases))
        if not hit:
            continue
        call = ".".join(chain) if chain else node.func.id
        yield Finding(
            "HVD015", ctx.relpath, node.lineno, node.col_offset,
            f"ad-hoc weight load '{call}(...)' in the serving plane, "
            "outside the WeightSubscriber: a direct checkpoint/param "
            "load skips the fleet plane's verify-then-arm protocol — "
            "no checksum verification before the tree is visible (a "
            "corrupt shard reaches decode), no double buffering (a "
            "half-loaded tree can serve a step), no generation id (the "
            "tokens it produces are unattributable), no refusal path "
            "(a bad publish takes the replica down instead of being "
            "refused loudly). Route weight ingestion through "
            "fleet.WeightSubscriber — load_initial() at startup, "
            "poll()/take_armed() for hot swaps — so every tree that "
            "reaches the engine rode the docs/fleet.md state machine.")


# ---------------------------------------------------------------------------
# HVD016 — full-tree barrier between backward and optimizer apply
# ---------------------------------------------------------------------------

# the modules that own the backward → allreduce → apply window; the
# overlap plane (docs/tensor-fusion.md) exists so nothing in it drains
# the whole gradient tree at once
_BARRIER_SUFFIXES = ("horovod_tpu/trainer.py", "horovod_tpu/optim.py")


def check_full_tree_barrier(ctx, shared):
    if not ("hot_path" in ctx.roles or
            ctx.relpath.endswith(_BARRIER_SUFFIXES)):
        return
    for node in ast.walk(ctx.tree):
        # idiom 1: [synchronize(h) for h in handles] — drain every
        # outstanding handle in one comprehension; the whole gradient
        # tree barriers before the first result is usable
        if isinstance(node, (ast.ListComp, ast.SetComp,
                             ast.GeneratorExp)):
            elt = node.elt
            if not isinstance(elt, ast.Call):
                continue
            chain = _attr_chain(elt.func)
            callee = (chain[-1] if chain else
                      elt.func.id if isinstance(elt.func, ast.Name)
                      else None)
            if callee != "synchronize":
                continue
            yield Finding(
                "HVD016", ctx.relpath, node.lineno, node.col_offset,
                "full-tree barrier in the backward→apply window: a "
                "comprehension that synchronize()s every handle at "
                "once serializes the entire gradient tree behind the "
                "slowest collective — the exact pattern the overlap "
                "plane (HOROVOD_OVERLAP_EAGER, docs/tensor-fusion.md) "
                "replaces with readiness-ordered bucket dispatch "
                "inside the backward window. Enqueue in reverse layer "
                "order with coordinator.flush_ready() between "
                "enqueues, and synchronize per bucket as results are "
                "consumed; keep a whole-tree drain only with a "
                "disable/baseline reason naming why every result must "
                "materialize here.")
        # idiom 2: jax.block_until_ready(grads) / grads
        # .block_until_ready() on a gradient tree — a device-wide
        # barrier between backward and apply
        elif isinstance(node, ast.Call):
            chain = _attr_chain(node.func)
            callee = (chain[-1] if chain else
                      node.func.id if isinstance(node.func, ast.Name)
                      else None)
            if callee != "block_until_ready":
                continue
            if _inside_instrument_step(node):
                continue  # the sanctioned measurement sync
            yield Finding(
                "HVD016", ctx.relpath, node.lineno, node.col_offset,
                "block_until_ready in the backward→apply window: a "
                "host-side device barrier here drains the dispatch "
                "pipeline and exposes every millisecond of comm the "
                "overlap plane could have hidden under backward "
                "compute. The step's one sanctioned sync lives in "
                "trainer.instrument_step (it IS the measurement "
                "boundary); anywhere else, let results stay futures "
                "until the optimizer apply consumes them, or carry a "
                "disable/baseline reason naming what must be "
                "materialized and why.")


# ---------------------------------------------------------------------------
# HVD017 — direct engine admission outside the router front door
# ---------------------------------------------------------------------------

# client-side surfaces that should reach the serving plane through the
# Router (horovod_tpu/router/), never a bare engine; fixtures opt in
# with `# hvdlint: role=client_path`
_CLIENT_DIRS = ("examples/", "tools/")
# receiver names that read as "a ServeEngine" at a call site
_ENGINE_RECEIVERS = {"engine", "eng", "serve_engine", "serving_engine"}
_ADMISSION_CTORS = {"AdmissionQueue"}


def check_direct_engine_submit(ctx, shared):
    if "client_path" not in ctx.roles and not any(
            d in ctx.relpath for d in _CLIENT_DIRS):
        return
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call):
            continue
        chain = _attr_chain(node.func)
        if (chain is not None and len(chain) >= 2 and
                chain[-1] == "submit" and
                chain[-2] in _ENGINE_RECEIVERS):
            yield Finding(
                "HVD017", ctx.relpath, node.lineno, node.col_offset,
                "direct ServeEngine.submit in a client surface: a "
                "request admitted behind the router's back is "
                "invisible to the dispatch ledger — it skips load "
                "scoring and cache affinity, its result carries no "
                "replica stamp, a canary rollout cannot steer or "
                "observe it, and when the replica dies nobody reroutes "
                "it. The router (horovod_tpu/router/) is the ONE "
                "admission point for multi-replica serving "
                "(docs/routing.md). Submit through Router.submit, or "
                "keep a direct call only with a disable/baseline "
                "reason naming why a single bare engine is the point.")
        elif ((chain is not None and chain[-1] in _ADMISSION_CTORS) or
              (isinstance(node.func, ast.Name) and
               node.func.id in _ADMISSION_CTORS)):
            yield Finding(
                "HVD017", ctx.relpath, node.lineno, node.col_offset,
                "direct AdmissionQueue construction in a client "
                "surface: hand-building the admission path couples the "
                "caller to one engine's queue and bypasses the "
                "router's single front door — no load-aware dispatch, "
                "no reroute on replica loss, no canary cohorting "
                "(docs/routing.md). Front the engines with a Router, "
                "or carry a disable/baseline reason naming why this "
                "tool is deliberately single-replica.")


# ---------------------------------------------------------------------------
# HVD018 — unbounded retry loop
# ---------------------------------------------------------------------------

# control/serving planes where a silent spin must instead become a
# loud, bounded-time error; fixtures opt in with
# `# hvdlint: role=retry_path`
_RETRY_DIRS = ("horovod_tpu/router/", "horovod_tpu/serving/",
               "horovod_tpu/fleet/", "horovod_tpu/run/")
# call names that make a while-True loop a *waiting* loop (the shape
# this rule cares about) rather than a worker drain loop
_WAIT_CALLEES = {"sleep", "wait"}
# clock calls whose presence in a comparison reads as a deadline check
_CLOCK_CALLEES = {"monotonic", "time", "perf_counter"}
# operand names that read as a time bound
_BOUND_NAME = re.compile(
    r"deadline|timeout|time_out|budget|until|expires|expiry|give_up",
    re.IGNORECASE)


def _is_constant_true(test):
    return isinstance(test, ast.Constant) and bool(test.value)


def _names_in(node):
    for sub in ast.walk(node):
        if isinstance(sub, ast.Name):
            yield sub.id
        elif isinstance(sub, ast.Attribute):
            yield sub.attr


def _has_time_bound(loop):
    """True if the loop body contains something that reads as a
    deadline/timeout check: a comparison whose operands call a clock
    or name a bound (deadline/timeout/budget/until/...), or a
    ``something_deadline.check()``-style call."""
    for node in ast.walk(loop):
        if isinstance(node, ast.Compare):
            for name in _names_in(node):
                if name in _CLOCK_CALLEES or _BOUND_NAME.search(name):
                    return True
        elif isinstance(node, ast.Call):
            chain = _attr_chain(node.func)
            if (chain is not None and len(chain) >= 2 and
                    chain[-1] in ("check", "remaining", "expired") and
                    _BOUND_NAME.search(chain[-2])):
                return True
    return False


def check_unbounded_retry_loop(ctx, shared):
    if "retry_path" not in ctx.roles and not any(
            d in ctx.relpath for d in _RETRY_DIRS):
        return
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.While):
            continue
        if not _is_constant_true(node.test):
            continue
        sleeps = False
        for sub in ast.walk(node):
            if not isinstance(sub, ast.Call):
                continue
            chain = _attr_chain(sub.func)
            callee = (chain[-1] if chain else
                      sub.func.id if isinstance(sub.func, ast.Name)
                      else None)
            if callee in _WAIT_CALLEES:
                sleeps = True
                break
        if not sleeps:
            continue  # a drain/dispatch loop, not a waiting loop
        if _has_time_bound(node):
            continue
        yield Finding(
            "HVD018", ctx.relpath, node.lineno, node.col_offset,
            "unbounded retry loop: `while True` + sleep with no "
            "deadline or timeout check anywhere in the body. On the "
            "control and serving planes a condition that never "
            "arrives must become a LOUD bounded-time error, never a "
            "silent spin — this loop waits forever instead. Add a "
            "deadline (`if time.monotonic() > deadline: raise ...`) "
            "or a bounded attempt budget, or carry a disable/baseline "
            "reason naming the external event that bounds the loop.")


# ---------------------------------------------------------------------------
# HVD019 — ad-hoc sharding outside the mesh plane
# ---------------------------------------------------------------------------

# the one sanctioned NamedSharding constructor lives here
_MESH_PLANE_SUFFIX = "horovod_tpu/parallel/mesh.py"
_MESH_SCOPE_DIRS = ("horovod_tpu/serving/", "horovod_tpu/ops/")
_MESH_SCOPE_FILES = ("horovod_tpu/trainer.py",)
_SHARDING_CTORS = {"NamedSharding", "Mesh"}


def _sharding_aliases(tree):
    """Local names bound to jax.sharding.{NamedSharding, Mesh} via
    ``from ... import`` (with or without ``as``)."""
    aliases = {}
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom) and node.module and \
                "sharding" in node.module.split("."):
            for a in node.names:
                if a.name in _SHARDING_CTORS:
                    aliases[a.asname or a.name] = a.name
    return aliases


def _ctor_name(node, aliases):
    """'NamedSharding'/'Mesh' when ``node`` constructs one, else None."""
    if not isinstance(node, ast.Call):
        return None
    if isinstance(node.func, ast.Name):
        return aliases.get(node.func.id)
    chain = _attr_chain(node.func)
    if chain and chain[-1] in _SHARDING_CTORS and len(chain) >= 2 and \
            chain[-2] == "sharding":
        return chain[-1]  # jax.sharding.NamedSharding(...) spelled out
    return None


def check_adhoc_sharding(ctx, shared):
    if ctx.relpath.endswith(_MESH_PLANE_SUFFIX):
        return
    if "mesh_path" not in ctx.roles and not (
            any(d in ctx.relpath for d in _MESH_SCOPE_DIRS) or
            any(ctx.relpath.endswith(f) for f in _MESH_SCOPE_FILES)):
        return
    aliases = _sharding_aliases(ctx.tree)
    flagged = set()
    for node in ast.walk(ctx.tree):
        name = _ctor_name(node, aliases)
        if name == "NamedSharding":
            flagged.add(id(node))
            yield Finding(
                "HVD019", ctx.relpath, node.lineno, node.col_offset,
                "ad-hoc NamedSharding construction outside "
                "parallel/mesh.py: a sharding built here bypasses the "
                "data plane's one placement contract (docs/mesh.md) — "
                "it can name axes the committed global mesh doesn't "
                "have, pin arrays to a private mesh that silently "
                "cross-reshards against the rest of the tree, and "
                "hides wire traffic from the per-axis accounting. "
                "Route placement through mesh_lib.named_sharding / "
                "tree_shardings / device_put_tree; keep a local "
                "construction only with a reason naming why the array "
                "genuinely lives off the data-plane mesh.")
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call):
            continue
        chain = _attr_chain(node.func)
        is_dput = (chain is not None and chain[-1] == "device_put") or \
            (isinstance(node.func, ast.Name) and
             node.func.id == "device_put")
        if not is_dput:
            continue
        inline = [n for arg in list(node.args) +
                  [k.value for k in node.keywords]
                  for n in ast.walk(arg)
                  if _ctor_name(n, aliases) and id(n) not in flagged]
        if not inline:
            continue
        yield Finding(
            "HVD019", ctx.relpath, node.lineno, node.col_offset,
            "jax.device_put with an inline mesh/sharding construction "
            "outside parallel/mesh.py: placement decided at the call "
            "site instead of through the spec-tree contract "
            "(docs/mesh.md). Build the spec once and place with "
            "mesh_lib.device_put_tree so training, checkpoint restore "
            "and serving agree on where every leaf lives.")


# ---------------------------------------------------------------------------
# HVD020 — ad-hoc memory probe outside the memory plane
# ---------------------------------------------------------------------------

# allocator/live-set introspection calls: device.memory_stats(),
# jax.live_arrays(), compiled.memory_analysis(). The memory plane
# (utils/memory.py) is the one sanctioned home for these probes —
# everywhere else they are a second, unattributed accountant whose
# numbers never reach the HBM ledger or the flight dump.
_MEMORY_PROBE_NAMES = {"live_arrays", "memory_stats", "memory_analysis"}
_MEMORY_SANCTIONED_SUFFIXES = ("horovod_tpu/utils/memory.py",)
_MEMORY_SCOPE_DIRS = ("horovod_tpu/serving/", "horovod_tpu/ops/")
_MEMORY_SCOPE_FILES = ("horovod_tpu/trainer.py",)


def check_adhoc_memory_probe(ctx, shared):
    if ctx.relpath.endswith(_MEMORY_SANCTIONED_SUFFIXES):
        return
    if "mem_path" not in ctx.roles and not (
            any(d in ctx.relpath for d in _MEMORY_SCOPE_DIRS) or
            any(ctx.relpath.endswith(f) for f in _MEMORY_SCOPE_FILES)):
        return
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call):
            continue
        # the terminal attribute, whatever the base expression —
        # `device.memory_stats()` and `jax.devices()[0].memory_stats()`
        # are the same probe
        if isinstance(node.func, ast.Name):
            probe = node.func.id
        elif isinstance(node.func, ast.Attribute):
            probe = node.func.attr
        else:
            probe = None
        if probe in _MEMORY_PROBE_NAMES:
            yield Finding(
                "HVD020", ctx.relpath, node.lineno, node.col_offset,
                f"ad-hoc memory probe '{probe}(...)': device-memory "
                "introspection outside utils/memory.py. Allocator stats "
                "and live-array scans must ride the memory plane "
                "(memory.device_memory_stats / step_peak_bytes / "
                "live_array_bytes, docs/memory.md) so every byte the "
                "process observes lands in ONE ledger — a stray probe "
                "reads the allocator on the hot path (a host sync on "
                "some backends), and its numbers never reach the "
                "hvd_hbm_bytes gauges, the flight dump, or the OOM "
                "forecast.")


# ---------------------------------------------------------------------------
# HVD023 — ad-hoc alert outside the alerting plane
# ---------------------------------------------------------------------------

# The alerting plane (utils/alerts.py, docs/alerts.md) is the one
# sanctioned home for "metric crosses threshold -> escalate" logic.
# Everywhere else, an If that thresholds an SLO-shaped signal and
# escalates in its body is a private alert: no pending->firing
# hysteresis (it flaps on one bad sample), no resolved edge, no
# incident capture, and its threshold never reaches the rule pack an
# operator can read.
_ALERT_SANCTIONED_SUFFIXES = ("horovod_tpu/utils/alerts.py",)
_ALERT_SCOPE_DIRS = ("horovod_tpu/serving/", "horovod_tpu/router/",
                     "horovod_tpu/ops/", "horovod_tpu/utils/")
_ALERT_SCOPE_FILES = ("horovod_tpu/trainer.py",)
# SLO-shaped signals on the test side: a windowed quantile, a burn
# rate, or a named pXX value
_ALERT_SIGNAL_CALLS = {"histogram_quantile", "burn_rate"}
_ALERT_SIGNAL_NAMES = {"p50", "p90", "p95", "p99"}
_ALERT_SIGNAL_SUFFIXES = ("_p99", "_p95", "_p90", "_p50")
_ALERT_SIGNAL_SUBSTRINGS = ("burn_rate", "burnrate")
# escalation terminals in the body: the ladder a real alert rides
_ALERT_ESCALATION_ATTRS = {"warning", "warn", "error", "critical",
                           "dump", "dump_on_failure", "event"}


def _terminal_name(func):
    if isinstance(func, ast.Name):
        return func.id
    if isinstance(func, ast.Attribute):
        return func.attr
    return None


def _alert_signal_in(test):
    """The SLO-shaped read inside an If test, or None."""
    for t in ast.walk(test):
        if isinstance(t, ast.Call):
            name = _terminal_name(t.func)
            if name in _ALERT_SIGNAL_CALLS:
                return f"{name}(...)"
        elif isinstance(t, (ast.Name, ast.Attribute)):
            name = t.id if isinstance(t, ast.Name) else t.attr
            low = name.lower()
            if low in _ALERT_SIGNAL_NAMES or \
                    low.endswith(_ALERT_SIGNAL_SUFFIXES) or \
                    any(s in low for s in _ALERT_SIGNAL_SUBSTRINGS):
                return name
    return None


def check_adhoc_alert(ctx, shared):
    if ctx.relpath.endswith(_ALERT_SANCTIONED_SUFFIXES):
        return
    if "alert_path" not in ctx.roles and not (
            any(d in ctx.relpath for d in _ALERT_SCOPE_DIRS) or
            any(ctx.relpath.endswith(f) for f in _ALERT_SCOPE_FILES)):
        return
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.If):
            continue
        # reading a quantile is fine; THRESHOLDING it is the alert shape
        if not any(isinstance(t, ast.Compare)
                   for t in ast.walk(node.test)):
            continue
        signal = _alert_signal_in(node.test)
        if signal is None:
            continue
        escalation = None
        for stmt in node.body:
            for t in ast.walk(stmt):
                if isinstance(t, ast.Call) and \
                        _terminal_name(t.func) in _ALERT_ESCALATION_ATTRS:
                    escalation = _terminal_name(t.func)
                    break
            if escalation:
                break
        if escalation is None:
            continue
        yield Finding(
            "HVD023", ctx.relpath, node.lineno, node.col_offset,
            f"ad-hoc alert: thresholding SLO signal '{signal}' and "
            f"escalating via '{escalation}(...)' outside the alerting "
            "plane. A private threshold-and-warn has no pending->firing "
            "hysteresis (one bad sample flaps it), no resolved edge, no "
            "incident capture, and its threshold is invisible to the "
            "rule pack operators read. Declare it as a Rule on "
            "utils/alerts.py's AlertManager (docs/alerts.md) so the "
            "breach rides the shared lifecycle — or, for an in-plane "
            "*control* decision that actuates rather than pages, keep "
            "it with a disable reason naming the actuator.")


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

RULES = {
    r.code: r for r in [
        Rule(
            "HVD001", "rank-divergent-iteration",
            "unsorted set iteration in a wire module",
            """HVD001 — rank-divergent iteration

Horovod's core invariant: every rank executes IDENTICAL collectives in
IDENTICAL order (Sergeev & Del Balso, arXiv:1802.05799 §3). Python set
iteration order depends on per-process hash randomization, so a set
iterated without sorted() on any path that feeds a cross-rank message
(CycleRequest entry order, CycleResponse plans, fusion buckets) produces
a different schedule on every rank — a hang or silent numeric corruption
that only reproduces under PYTHONHASHSEED variation.

History: the negotiation re-announce path (ops/eager.py) and the
coordinator's lost-rank list (ops/negotiation.py) both iterate sets that
ride the wire; each carries the sorted() this rule now enforces.
Deleting either sorted() makes this rule fail CI — by design.

Scope: modules with the 'wire' role (see rules.py / `# hvdlint:
role=wire`). Fix: wrap the iterable in sorted().""",
            check_rank_divergence),
        Rule(
            "HVD002", "lock-order-deadlock",
            "self-deadlock or inconsistent lock order",
            """HVD002 — lock order / self-deadlock

Flags three shapes, all statically decidable within one module:
(1) re-acquiring a non-reentrant threading.Lock already held in the
same function; (2) calling, while holding lock L, a same-module
function/method that (transitively) acquires L again; (3) two code
paths nesting locks A->B and B->A.

History: the telemetry PR's metrics-registry reset() held the module
_registry_lock and then called get_registry(), which takes the same
lock — a guaranteed self-deadlock, shipped and then hot-fixed (shape 2).
Re-introducing that pattern makes this rule fail CI.

Fix: release before calling, restructure into an _unlocked helper, or
use an RLock when re-entrancy is the intended design.""",
            check_lock_order),
        Rule(
            "HVD003", "blocking-call-in-coordinator-loop",
            "unbounded blocking call at cycle cadence",
            """HVD003 — blocking call in the coordinator loop

The negotiation cycle runs every ~5 ms on every rank; the coordinator's
handler runs inside request handling. Any unbounded blocking call there
(sleep >= 1 s, connect/recv with no timeout, argless .wait()/.join(),
synchronous file I/O, subprocess without timeout) freezes the control
plane for EVERY rank: stall detection, liveness escalation and shutdown
drains all ride this loop (MPI progress hazards: arXiv:1810.11112).

Scope: modules with the 'loop' role. Fix: pass a timeout, pace sleeps
by the cycle time, or queue the work to a side thread (the
utils/timeline.py writer-thread pattern).""",
            check_blocking_in_loop),
        Rule(
            "HVD004", "raw-clock",
            "time.time() instead of the shared Clock",
            """HVD004 — raw wall clock

Timeline traces and metrics events correlate instant-for-instant only
because both stamp from ONE shared monotonic clock with one wall-clock
epoch anchor (utils.metrics.shared_clock; the Timeline adopts it and
writes the pairing as its clock_sync event). A raw time.time() read is
(a) un-correlatable with those streams and (b) not monotonic — NTP
steps make deadlines computed from it jump.

History: 7 raw time.time() sites predated this rule; the launcher
Timeout helper now rides the shared clock, and the genuinely
cross-process wall-clock stamps (mpirun rendezvous freshness, the
disk-cache TTL, and the Clock's own epoch anchor) are baselined with
reasons in tools/hvdlint/baseline.json.

Fix: shared_clock().ts_us() for durations/deadlines,
shared_clock().epoch_us() for wall-ish stamps; baseline only stamps
that must compare across processes/restarts.""",
            check_raw_clock),
        Rule(
            "HVD005", "env-registry-drift",
            "HVD_*/HOROVOD_* read missing from ENV_REGISTRY",
            """HVD005 — env-registry drift

Every HVD_*/HOROVOD_* environment variable is an API surface: ranks
must agree on it, operators must be able to discover it, and drift
between code and docs is how knobs become folklore. The single source
of truth is ENV_REGISTRY in horovod_tpu/common/config.py (a pure
literal, parsed — never imported — by this rule); docs/envvars.md is
generated from it (`--emit-envdoc`) and CI fails if the doc drifts
(`--check-envdoc`).

This rule flags any literal env read (os.environ get/[]/in/pop/
setdefault, os.getenv, the config helpers env_bool/env_int/env_float/
env_str/_env, and _env_first) whose variable is not registered.

Fix: add a registry entry (name, aliased, default, owner, description)
and regenerate docs/envvars.md.""",
            check_env_registry),
        Rule(
            "HVD006", "swallowed-exception",
            "broad except that neither raises nor logs",
            """HVD006 — swallowed exception

`except Exception: pass` on a control/data-plane path converts real
faults — mismatched collectives, dead peers, corrupt rendezvous state —
into silent divergence that surfaces ranks later as a hang. The rule
flags any handler catching Exception/BaseException/bare whose body
neither raises, nor logs (common.hvd_logging / logging / warnings /
traceback.print_exc), nor records a metrics event.

History: the chaos PR found the lost-response unknown_ids dedupe bug
hiding behind exactly this shape; several probing helpers
(`_bound_axis_names`, jax-internal lookups) also swallowed
ImportError-class probes with Exception breadth — those are now
narrowed to (ImportError, AttributeError).

Fix: narrow the type to what the probe can actually raise, log it, or
re-raise; disable with a reason only where swallowing is the contract
(e.g. best-effort teardown of an already-failed peer).""",
            check_swallowed_exception),
        Rule(
            "HVD007", "jit-purity",
            "Python side effect inside a traced function",
            """HVD007 — jit purity

A function under jax.jit/pjit/pmap/shard_map/pallas_call executes its
Python body at TRACE time only. A print fires once per compilation; an
os.environ or time.time() read is frozen into the compiled graph — and
because ranks may trace at different moments (or hit different caches),
a trace-time read of mutable process state is also a rank-divergence
hazard: two ranks can bake DIFFERENT constants into the "same"
collective program.

Flags print/input/open, os.environ access, time.* reads/sleeps, and
random/np.random calls lexically inside traced functions.

Fix: hoist the read out and pass it as an argument (static or traced),
or use jax.debug.print / jax.experimental.io_callback for intentional
runtime effects.""",
            check_jit_purity),
        Rule(
            "HVD008", "span-leak",
            "tracing span opened without a close/abort path",
            """HVD008 — span leak

The tracing plane (utils/tracing.py) keeps every open span in the
tracer's open-span table until close() or abort() moves it into the
flight-recorder ring. A span that is opened and then discarded — or
bound to a local that no path ever closes — sits in that table forever:
the flight dump reports it as eternally in flight, the postmortem's
'still waiting' analysis names it as a blocked tensor that never
existed, and the per-stage hvd_span_seconds histogram silently loses
the stage. That is an observability plane lying about the data plane —
worse than no data.

Flags two shapes at tracer call sites (receivers named *tracer* or
get_tracer() chains): (1) a ``.span(...)`` call used as a bare
expression statement (annotate-chained or not) — nothing holds the
span, nothing can close it; (2) a span assigned to a local name with
no reachable close()/abort() in the same scope AND no escape that
hands off responsibility (returned, yielded, passed as an argument,
stored on an object attribute, or used as a context manager).

The negotiate spans in ops/eager.py live across methods by design:
they are stored on the TensorTableEntry (an attribute store — an
escape) and closed in _apply_cycle_response or aborted on the failure
paths; that pattern stays clean under this rule.

Fix: prefer the context-manager form (``with tracer.span(...)``) for
lexical extents; for spans that outlive the function, store them on the
owning object and audit every terminal path (success, error, shutdown)
for a close()/abort().""",
            check_span_leak),
        Rule(
            "HVD009", "ad-hoc-numerics-probe",
            "isnan-family call outside the sanctioned numerics module",
            """HVD009 — ad-hoc numerics probe

The numerics plane (utils/numerics.py) computes every per-tensor
gradient-health statistic — L2 norm, max-abs, nan/inf counts, zero
fraction, checksum — as a single fused pass over buffers the collective
already materialized, and folds the results into the cross-rank digest
the coordinator's divergence sentinel compares. That design carries two
contracts: the stats ride a read the collective already pays for, and
every health signal reaches the digest so the sentinel can name the
divergent rank.

An ad-hoc ``jnp.isnan(grad).any()`` sprinkled at a call site breaks
both. It is a second full read of the gradient (a separate kernel
launch), it runs at trace time inside
jitted code unless carefully guarded (see HVD007), and its verdict
stays local — the coordinator never sees it, so the one rank that
noticed the NaN logs a line while the postmortem blames nobody. The
historical shape: debugging probes added during an incident that stick
around, each one cheap alone, collectively doubling the flush path's
memory traffic.

Flags calls to the isnan family (isnan/isinf/isfinite/isposinf/
isneginf/nan_to_num — any receiver: jnp, np, math, jax.numpy, or a
bare imported name) in every module except utils/numerics.py.

Fix: route the check through the numerics plane —
``utils.numerics.tensor_stats`` / ``stats_vector`` for one tensor,
``segment_stats`` (or ``fusion.bucket_stats``) for a fused buffer —
and read the verdict from the monitor's records or the
``hvd_nonfinite_total`` counter. Tests and examples are outside the
lint scope and may assert finiteness directly.""",
            check_adhoc_numerics),
        Rule(
            "HVD010", "wire-dtype-cast-bypasses-codec",
            "direct narrow-dtype astype outside the codec registry",
            """HVD010 — wire-dtype cast that bypasses the codec registry

The quantized wire (ops/quantization.py, PR 8) is block-scaled: every
narrow payload travels WITH its per-block f32 max-abs scales, the
reduction dequantizes to f32 before summing, and an error-feedback
residual carries the rounding to the next step. All of that lives
behind two sanctioned modules — ops/quantization.py (the kernels) and
ops/compression.py (the codec registry the negotiated plan and the
``compression=`` API select from).

A direct ``x.astype(jnp.int8)`` (or uint8/float8_*) anywhere else is
an unscaled, residual-less encode: values outside [-128, 127] wrap,
e4m3 overflows to NaN, and because the cast never consulted the
negotiated plan, peers may decode the buffer with a different codec —
the exact rank-asymmetric corruption the coordinator's codec
fingerprint check exists to refuse. The historical shape: a quick
"cast to int8 to save bandwidth" in an op or example that works on the
author's toy tensor (range happens to fit) and corrupts real
gradients.

Flags ``.astype(d)`` where d resolves to int8/uint8/float8_e4m3fn/
float8_e4m3/float8_e5m2 — as jnp.X/np.X attribute chains, bare
imported names, "int8" strings, or np.dtype("int8") calls — in every
module except the two sanctioned ones. Tests and examples are outside
the lint scope. fp16/bf16 casts are NOT flagged: they are value-exact
for gradients' range and legitimately appear in mixed-precision
compute, not just on the wire.

Fix: ``quantization.encode(x, block, codec)`` for wire encodes (or
``wire_dtype(codec)`` if you genuinely need the dtype object);
``Compression.from_name(name)`` when the codec is user-selected.""",
            check_wire_dtype_cast),
        Rule(
            "HVD011", "blocking-host-sync-in-decode-loop",
            "device_get/block_until_ready/np.asarray in a serving "
            "decode-loop module",
            """HVD011 — blocking host sync in the serving decode loop

The serving plane (horovod_tpu/serving/, PR 9) holds inter-token
latency to one device step per generated token by keeping the decode
loop asynchronous: the host enqueues the next step's work while the
device executes the current one, and the ONLY forced host<->device
rendezvous are the engine's two sanctioned readbacks — the batched
sampled-token ids once per decode pass, and the first token once per
prefill (both in serving/engine.py, both carrying an inline disable
with a reason). The per-pass one is still ONE statement
(ServeEngine._read_unread); where nothing at the step's boundary waits
for the ids it runs a step late, after the next pass is launched, so
the chip is never idle for it. The per-prefill one is ONE statement too
(ServeEngine._read_first_tokens), and runs after everything the
admitting step runs is launched, the decode pass over the new rows
included, so the chip works on behind the prefill while the host reads
and books. A second readback site would put the wait back on the chip's
critical path.

Any other jax.device_get(...), .block_until_ready(), or
np.asarray(device_value) on that path adds a full host round-trip per
token. At decode cadence that is the classic inter-token-latency
killer: the device idles while the host copies, the dispatch pipeline
drains, and a 2x tail-latency regression ships with no functional
symptom — generation stays correct, only slower. The historical shape:
a debugging print or an eager shape probe left in the step loop.

Scope: the decode-loop modules (serving/engine.py, decode.py,
sampling.py, kv_cache.py) plus any file opting in with `# hvdlint:
role=serve_loop`. Flags device_get calls (any receiver chain),
.block_until_ready() method calls, and asarray via np/numpy or a bare
name — jnp.asarray is host->device and stays legal.

Fix: keep values on device and fold the work into the jitted step; if
a readback is genuinely the loop's output, batch it with the
sanctioned per-step one, or carry a disable comment stating why one
more rendezvous per token is acceptable.""",
            check_decode_host_sync),
        Rule(
            "HVD012", "ad-hoc-state-serialization",
            "np/torch array dump outside the checkpoint plane",
            """HVD012 — ad-hoc training-state serialization

The checkpoint plane (utils/checkpoint.py, PR 10) makes exactly one
promise: anything it committed, restore() returns complete and
checksum-valid — or fails loud. The machinery behind that promise is
all in one place: tmp + fsync + atomic rename for every file, per-file
CRCs recorded in a manifest whose own rename is THE commit point,
restore-side verification, keep-last-K retention, and a torture test
that kills the writer at every failure point and asserts the promise
anyway.

A stray ``np.savez(path, **params)`` in an op or a trainer keeps none
of it. A crash mid-write leaves a torn .npz that numpy happily opens
and fails inside lazily; a full disk truncates silently; nothing
records what SHOULD be in the file, so a partial write restores as a
partial model — the failure mode that costs a week of training, found
only when the loss curve disagrees with the logbook. The historical
shape: a quick "dump the weights here" during an experiment that
becomes the de-facto checkpoint path.

Flags ``save/savez/savez_compressed`` calls received by np/numpy/onp/
jnp/torch in every module except utils/checkpoint.py (the sanctioned
home). Bare-name calls and pickle are NOT flagged: optim/cache/network
legitimately pickle for the wire and for non-durable scratch, and a
bare ``save(...)`` is usually this repo's own checkpoint.save. Tests
and examples are outside the lint scope.

Fix: ``CheckpointManager(dir).save(tree, step)`` for the training
loop (async, sharded, preemption-safe); ``checkpoint.save(path,
tree)`` for one-shot dumps. Both give you the commit protocol for
free.""",
            check_adhoc_serialization),
        Rule(
            "HVD013", "adhoc-step-timer",
            "raw perf_counter step timing in hot-path modules",
            """HVD013 — ad-hoc step timing in hot-path modules

The perf-attribution plane gives step time exactly one front door:
``trainer.instrument_step`` wraps the step, syncs, and publishes
hvd_step_seconds / hvd_tokens_per_second / hvd_mfu plus (at
HOROVOD_PERF_ATTRIB_EVERY cadence) the per-class breakdown and overlap
gauges; ``utils/profiling`` decomposes sub-step device time from
profiler captures; ``utils.metrics.shared_clock()`` anchors
timestamps. Every number from those paths lands in the registry —
comparable across runs and ranks.

A stray ``t0 = time.perf_counter()`` around a step in an op or the
serving loop produces a second, unpublished number for the "same"
thing — usually measuring subtly different boundaries (no device sync,
or sync included where the instrumented number excludes it). The
historical shape: a printf-timing experiment that ships, then disagrees
with hvd_step_seconds by 8%, and the 8% gets chased as a perf bug when
it is two stopwatches timing two different races.

Flags ``time.perf_counter()/perf_counter_ns()`` calls (module attribute
or from-import alias) in horovod_tpu/ops/, horovod_tpu/serving/ and
horovod_tpu/trainer.py — except lexically inside ``instrument_step``
itself, the sanctioned wrapper. ``time.monotonic`` is not flagged (it
is the shared clock's own base and the wire planes' timeout primitive);
``time.time`` is already HVD004. Fixtures opt in with ``# hvdlint:
role=hot_path``.

Fix: wrap the loop with ``trainer.instrument_step`` (it composes —
pass ``name=`` to keep loops distinct); for durations that feed a
histogram on the shared registry, keep the timer and add a disable
reason saying which instrument consumes it.""",
            check_adhoc_step_timer),
        Rule(
            "HVD014", "adhoc-request-timer",
            "raw clock deltas on request timestamps outside the "
            "request-trace layer",
            """HVD014 — ad-hoc per-request timing outside serving/tracing.py

The serving plane gives request latency exactly one front door:
``serving/tracing.py``. Every admitted ``Request`` is one trace whose
phase decomposition (queue_wait / requeue / prefill / decode /
scheduler_stall, in ms) lands in the root span's attrs, the
``hvd_serve_phase_seconds`` histogram, the serve_retire event, and the
``RequestResult`` — which is what tools/hvd_slo.py attributes the tail
from and what hvd_top renders live.

A stray ``now - request.arrival_ts`` anywhere else in
``horovod_tpu/serving/`` starts a second, unpublished latency story
for the "same" request — usually with different boundaries: it
ignores requeue credit, folds scheduler stall into whatever phase it
thinks it is measuring, and never reaches the tail analyzer. The
historical shape: a p99 chased for a day because an ad-hoc TTFT
number disagreed with the trace's prefill phase by the admission
wait.

Flags binary subtractions where either operand is an attribute access
on a request-lifecycle timestamp (``arrival_ts``, ``last_token_ts``,
``finish_ts``) in ``horovod_tpu/serving/`` — except in
``serving/tracing.py`` itself, the sanctioned layer. Fixtures opt in
with ``# hvdlint: role=serve_path``.

Fix: drive the measurement through ``RequestTrace`` (on_pop /
on_prefill_end / on_decode_tick / on_retire already stamp every
phase) or annotate its spans; keep a local delta only with a disable
reason naming the SLO instrument on the shared registry that consumes
it (the engine's TTFT/intertoken histograms and the deadline checks
are the baselined examples).""",
            check_adhoc_request_timer),
        Rule(
            "HVD015", "adhoc-weight-load",
            "direct checkpoint/param loads in the serving plane "
            "outside the WeightSubscriber",
            """HVD015 — ad-hoc weight loading in the serving plane

The fleet plane gives serving weights exactly one front door:
``fleet/subscriber.py``. A ``WeightSubscriber`` watches the
publication pointer, background-loads new generations off the decode
hot path, checksum-verifies every file BEFORE the tree becomes
visible, double-buffers so the engine never touches a half-loaded
tree, stamps the monotonic generation id every token gets attributed
to, and refuses corrupt or mismatched publishes loudly (fleet_refuse
event + hvd_fleet_refusals_total) while the old generation keeps
serving (docs/fleet.md).

A direct ``checkpoint.restore(...)`` / ``np.load(...)`` anywhere else
under ``horovod_tpu/serving/`` or ``horovod_tpu/fleet/`` bypasses all
of that: it blocks the step loop for the full deserialize, hands the
engine a tree no checksum vouched for, produces tokens no generation
id can attribute, and turns a bad publish into a replica crash
instead of a refusal. The historical shape this rule pins: replicas
loading weights once at startup with a bare restore — the exact
pattern the fleet plane replaced.

Flags calls whose attribute chain ends in restore /
restore_with_extra / load / resume on a checkpoint-ish or array-
library receiver (checkpoint, ckpt, manager, np, jnp, torch, ...),
plus bare-name aliases imported from a checkpoint module. Scope:
``horovod_tpu/serving/`` and ``horovod_tpu/fleet/`` (fixtures opt in
with ``# hvdlint: role=serve_path``); ``fleet/subscriber.py`` itself
is the sanctioned layer.

Fix: take weights from the replica's WeightSubscriber
(``load_initial()`` at startup, the engine's ``_maybe_swap`` for hot
swaps); keep a direct load only with a disable reason naming why the
verify-then-arm protocol cannot apply.""",
            check_adhoc_weight_load),
        Rule(
            "HVD016", "full-tree-barrier-in-hot-path",
            "whole-gradient-tree synchronize/block_until_ready between "
            "backward and optimizer apply",
            """HVD016 — full-tree barrier in the backward→apply window

The overlap plane (PR 14, docs/tensor-fusion.md) dispatches fused
gradient buckets in reverse-layer readiness order while backward is
still producing later leaves, so collective time hides under compute
— the framework's core perf story (overlap_frac / exposed_comm_ms in
the attribution gauges). One line
can undo all of it: a whole-tree barrier between backward and the
optimizer apply forces every bucket to finish before anything is
consumed, re-serializing comm behind compute exactly as if the plane
did not exist — with no functional symptom, only a slower step.

Two idioms are flagged in horovod_tpu/trainer.py, horovod_tpu/optim.py
and ``# hvdlint: role=hot_path`` modules:

  * ``[synchronize(h) for h in handles]`` — a comprehension draining
    every outstanding handle at once (the barrier the reference's
    per-tensor hooks exist to avoid, torch/__init__.py:95-130);
  * ``jax.block_until_ready(tree)`` / ``.block_until_ready()`` — a
    host-side device barrier (except lexically inside
    ``trainer.instrument_step``, the sanctioned measurement sync).

The historical shape: a debugging "wait for the grads" that ships, or
a barrier-path fallback that quietly becomes the only path.

Sanctioned sites ride the baseline with reasons: optim.py's barrier
fallback (the reference behavior when HOROVOD_OVERLAP_EAGER is off),
the overlap path's own final drain (dispatch already overlapped;
results must materialize before apply returns), and
broadcast_parameters' init-time drain (one-shot, not the step loop).

Fix: enqueue in reverse layer order with
``coordinator.flush_ready()`` between enqueues and synchronize per
bucket as consumed; for device sync, rely on instrument_step's
boundary or carry a disable reason naming what must materialize.""",
            check_full_tree_barrier),
        Rule(
            "HVD017", "direct-engine-submit",
            "ServeEngine.submit / AdmissionQueue use in client "
            "surfaces outside the router front door",
            """HVD017 — direct engine admission outside the router

The router plane (horovod_tpu/router/, docs/routing.md) gives
multi-replica serving exactly one admission point: ``Router.submit``
scores every live replica's heartbeat-carried load snapshot, applies
cache-affinity stickiness, records the assignment in the reroute
ledger, and lets the canary controller steer the request's cohort.
Everything downstream depends on admission going through it: a
request submitted straight into a ``ServeEngine`` is invisible to the
ledger (nobody reroutes it when its replica dies), skips load scoring
(it lands on whichever engine the caller happened to hold, however
loaded), carries no replica stamp in its result, and punches through
a canary rollout's traffic split — the SLO comparison silently loses
samples to the wrong cohort.

The historical shape this rule pins: single-engine demo code
(examples/serve_lm.py, tools/hvd_fleet.py) copy-pasted into a
multi-replica deployment, where "submit to the engine I have" becomes
a second, unrouted front door.

Flags, in ``examples/`` and ``tools/`` (fixtures opt in with
``# hvdlint: role=client_path``):

  * calls whose attribute chain ends ``.submit`` on an engine-ish
    receiver (engine / eng / serve_engine / serving_engine) —
    ``Router.submit`` (receiver ``router``) is the sanctioned call;
  * ``AdmissionQueue(...)`` construction — hand-building the
    admission path couples the caller to one engine's queue.

``horovod_tpu/`` itself is out of scope: the router and the engine's
own internals are the implementation, not a client. The baselined
sites are the deliberately single-replica ones: serve_lm.py's
policy-comparison arms (fresh engine per arm IS the experiment) and
hvd_fleet's drill (one victim replica by design).

Fix: front the engines with a ``Router`` (it accepts one replica
fine) and submit through it; keep a direct call only with a reason
naming why a bare single engine is the point.""",
            check_direct_engine_submit),
        Rule(
            "HVD018", "unbounded-retry-loop",
            "while-True + sleep with no deadline in the control/"
            "serving planes",
            """HVD018 — unbounded retry loop

The repo's liveness discipline (docs/chaos.md): a peer that goes
silent must become a LOUD, bounded-time error — RanksLostError after
``rank_lost_timeout_s``, a drain past ``HVD_ELASTIC_DRAIN_TIMEOUT_S``
force-retires and reroutes, BasicClient gives up after ``attempts``.
Every waiting path owns a clock.

A ``while True: ... sleep(...)`` loop with no deadline check is the
opposite: when the condition it polls for never arrives (coordinator
died, file never appears, replica wedged mid-request), the process
waits FOREVER with no event, no metric, no error — the silent hang
the chaos drills exist to make impossible. The historical shape: a
rendezvous poll written for the happy path, discovered the first time
a 256-host job sat overnight on one missing peer.

Flags ``ast.While`` with a constant-true test whose body both calls a
``sleep``/``wait`` and contains nothing that reads as a time bound —
no comparison touching a clock call (time.monotonic / time.time /
perf_counter) or a deadline/timeout/budget/until-named operand, and
no ``deadline.check()``-style call. Loops without a sleep are NOT
flagged (a blocking-recv drain loop is bounded by its peer's EOF, and
pure dispatch loops are the serving plane's normal shape). Scope:
``horovod_tpu/router/``, ``horovod_tpu/serving/``,
``horovod_tpu/fleet/``, ``horovod_tpu/run/`` (fixtures opt in with
``# hvdlint: role=retry_path``).

The baselined site is run/network.py's handler loop: its only sleep
is an injected chaos ``delay_request``/``delay_response`` fault, and
the loop itself is bounded by the peer closing the connection
(``_wire.read`` raises EOF), not by a clock.

Fix: compute ``deadline = time.monotonic() + timeout_s`` before the
loop and raise past it (run/mpi.py's rendezvous poll is the model),
or bound attempts and surface the give-up as an event/exception.""",
            check_unbounded_retry_loop),
        Rule(
            "HVD019", "adhoc-sharding",
            "NamedSharding / inline-mesh device_put outside "
            "parallel/mesh.py in the data plane",
            """HVD019 — ad-hoc sharding outside the mesh plane

The named-mesh data plane (docs/mesh.md) has exactly one placement
contract: a process-global Mesh committed by parallel/mesh.py, and
PartitionSpec trees resolved to NamedShardings through
``mesh_lib.named_sharding`` / ``tree_shardings`` /
``device_put_tree``. Training, cross-layout checkpoint restore, and
tensor-parallel serving all assume every data-plane leaf was placed
through that contract.

A ``NamedSharding(...)`` built at a call site — or a
``jax.device_put`` carrying an inline ``NamedSharding``/``Mesh``
construction — re-decides placement locally. The failure modes are
quiet: the spec can name an axis the committed mesh doesn't have
(raises only on the layout that ships), the array can land on a
private mesh and silently cross-reshard against every collective
that touches it, donation breaks when in_shardings disagree with the
actual placement, and the transfer never reaches the per-axis wire
accounting (hvd_wire_bytes_total{axis}).

Scope: ``horovod_tpu/trainer.py``, ``horovod_tpu/serving/``,
``horovod_tpu/ops/`` (fixtures opt in with ``# hvdlint:
role=mesh_path``); ``parallel/mesh.py`` itself is the sanctioned
constructor. The baselined sites are
ops/process_collectives.py's rendezvous shardings — built over its
own per-process grid mesh for host-side collectives, deliberately
not the data plane.

Fix: express placement as a PartitionSpec and route it through
mesh_lib (``named_sharding(spec, mesh)`` accepts an explicit mesh
for the rare off-global case); keep a local construction only with
a reason naming why the array lives off the data-plane mesh.""",
            check_adhoc_sharding),
        Rule(
            "HVD020", "adhoc-memory-probe",
            "device-memory introspection outside utils/memory.py in "
            "the trainer/serving/ops planes",
            """HVD020 — ad-hoc memory probe outside the memory plane

The memory & compile observability plane (docs/memory.md) sanctions
exactly one home for device-memory introspection:
``horovod_tpu/utils/memory.py``, whose ``device_memory_stats`` /
``step_peak_bytes`` / ``live_array_bytes`` wrappers feed the per-chip
HBM ledger, the ``hvd_hbm_bytes{component}`` gauges, the flight-dump
memory section, and the serving OOM forecast.

A direct ``device.memory_stats()``, ``jax.live_arrays()`` or
``compiled.memory_analysis()`` call anywhere else is a second,
unattributed accountant. The failure modes: the probe runs on the hot
path (``live_arrays`` walks the whole live set; ``memory_stats`` is a
host sync on some backends) without the plane's enabled() gate, its
numbers never reach the
ledger so hvd_top and the postmortem tell a different story than the
call site saw, and CPU CI silently diverges from TPU because the raw
call has no None-on-missing-stats contract.

Scope: ``horovod_tpu/trainer.py``, ``horovod_tpu/serving/``,
``horovod_tpu/ops/`` (other files opt in with ``# hvdlint:
role=mem_path``); ``utils/memory.py`` itself is the sanctioned home.

Fix: call the memory-plane wrapper (it is None-safe and gated), or —
for byte *attribution* rather than measurement — account the tree
into the ledger (``get_ledger().account_tree(...)``) and let the
gauges carry the number.""",
            check_adhoc_memory_probe),
        Rule(
            "HVD023", "adhoc-alert",
            "threshold-and-escalate on an SLO signal outside the "
            "alerting plane",
            """HVD023 — ad-hoc alert outside the alerting plane

The alerting plane gives "metric crosses threshold" exactly one front
door: a declarative ``Rule`` on ``utils/alerts.py``'s AlertManager,
evaluated on the existing instrument ticks. A rule there gets the
whole lifecycle for free — pending->firing hysteresis (a breach must
hold HVD_ALERT_FOR_S before paging, and hold clear before resolving),
multi-window burn-rate predicates, the ``hvd_alert_state`` gauge
hvd_top renders, the one-shot flight-dump escalation, and an incident
file bundling the alert window's durable history slice
(docs/alerts.md).

An ``if ttft_p99 > slo: log.warning(...)`` anywhere else is a private
alert with none of that: it flaps on a single bad sample, never
resolves, pages nobody consistently (the warning drowns in the log),
and captures no evidence — by the time a human reads it, the window
that explains it has rolled out of every ring. The historical shape:
a debugging guard that ships, then three planes each grow their own
slightly different p99 threshold and an operator cannot answer "what
alerts exist and at what levels" without grepping.

Flags ``If`` statements whose test THRESHOLDS (contains a comparison
over) an SLO-shaped signal — a ``histogram_quantile``/``burn_rate``
call or a name ending in ``_p99/_p95/_p90/_p50`` or containing
``burn_rate`` — and whose body escalates (``log.warning/error``,
``warnings.warn``, a flight ``dump``/``dump_on_failure``, or a
registry ``event``). Scope: horovod_tpu/serving/, router/, ops/,
utils/ and trainer.py (other files opt in with ``# hvdlint:
role=alert_path``); utils/alerts.py itself is the sanctioned home.
Reading a quantile without comparing it, or comparing without
escalating (a control decision that only actuates), is not flagged.

Fix: declare the predicate as a Rule in the AlertManager's pack (or
extend ``default_rules()``); for a deliberate in-plane control ladder
that actuates rather than pages (canary rollback, elastic grading),
keep it with a disable reason naming the actuator and the metric the
alerting plane watches instead.""",
            check_adhoc_alert),
    ]
}
