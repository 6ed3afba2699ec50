"""mxlint rule families.

T1  host-sync calls (``asnumpy``/``.item()``/``np.asarray``/
    ``jax.device_get``/``block_until_ready``/``float()``...) — errors
    inside traced regions, warnings for unambiguous syncs anywhere else.
T2  python ``if``/``while``/``assert`` on traced values inside traced
    regions (the trace either fails to concretize or silently bakes one
    branch into every execution).
T3  op-registry consistency: registrations must be unique, documented,
    and ops whose pure body is non-differentiable must carry an explicit
    ``no_grad=True`` marker (mxnet_tpu/ops/registry.py) instead of
    silently producing garbage cotangents.
T4  nondeterminism inside traced regions: host ``time.*`` or
    ``random``/``np.random`` calls get baked in as trace-time constants —
    every execution replays the same "random" numbers.
T5  in-place numpy mutation of jax-backed buffers (``x.asnumpy()[i] = v``
    mutates a host copy — or a read-only view — never device memory).
T6  use-after-donation: a binding passed at a donated position of a
    ``jax.jit(..., donate_argnums=...)`` call is read after the call
    before being rebound (tools/lint/dataflow.py).
T7  donation aliasing: the same array — or a view/member of the same
    parent — reaches a donating call at both a donated and another
    position, or is captured by the donated callee's closure.
T8  partition-rule sanity: literal rule tables handed to
    ``PartitionRules`` / ``Trainer(partition_rules=...)`` /
    ``place_params`` with a pattern that cannot compile, a rule
    statically unreachable (after a catch-all, or a duplicate pattern
    under first-match-wins), or model-axis specs with no terminal
    catch-all — unmatched parameters then silently replicate, which on
    a mesh with a model axis is a memory regression that trains fine.
T9  memory-policy bypass: hand-rolled ``jax.checkpoint``/``jax.remat``
    in MODEL code (under ``models/`` or a file defining a
    ``hybrid_forward`` block) sidesteps the auto-remat tier ladder —
    use ``memory.policy.checkpoint_wrap`` / ``hybridize(remat=...)``;
    and planner calls (``plan_model``/``auto_tier``/...) as bare
    statements discard the fit verdict they exist to produce.
"""
from __future__ import annotations

import ast
import re

from .core import (Violation, SEVERITY_ERROR, SEVERITY_WARNING, dotted_name,
                   last_name)
from .compile_discipline import check_compile_discipline
from .concurrency import check_concurrency
from .dataflow import check_donation
from .hotpath import FunctionIndex, function_taint, expr_tainted

RULES = {
    "T1": "host-sync call reachable from a traced hot path",
    "T2": "python control flow on a traced value",
    "T3": "op-registry inconsistency (docstring / duplicate / grad path)",
    "T4": "host nondeterminism inside a traced region",
    "T5": "in-place numpy mutation of a jax-backed buffer",
    "T6": "use of a buffer after it was donated to a jitted call",
    "T7": "aliased array reaches a donating call (donation aliasing)",
    "T8": "partition-rule sanity (dead rule / silent replicate)",
    "T9": "memory-policy bypass (hand-rolled remat / dropped verdict)",
    "T10": "shared state accessed bare where it is lock-guarded elsewhere",
    "T11": "lock-order cycle / unbounded blocking call under a held lock",
    "T12": "thread lifecycle (unnamed / unjoined non-daemon / silent worker)",
    "T13": "retrace hazard (baked scalar / shape branch / unstable key)",
    "T14": "compile-site discipline (fresh callable / unbounded entry)",
    "T15": "signature budget (__compile_signatures__) missing or stale",
}

#: families whose cross-file halves the analyzer finalizes after the
#: per-file sweep
_CONCURRENCY_RULES = frozenset({"T10", "T11", "T12"})

#: compile-discipline tier (tools/lint/compile_discipline.py) — fully
#: per-file, so per-content-hash caching holds with no cross-file facts
_COMPILE_RULES = frozenset({"T13", "T14", "T15"})

# --- T1 ---------------------------------------------------------------------

#: method-style syncs: ``x.asnumpy()``, ``x.item()``, ...  With the
#: async engine tier (PR 7) ``wait_to_read`` may block on the worker
#: thread's completion event rather than the device — still a host
#: sync.  ``result`` covers ticket-style waits (async checkpoint
#: tickets, executor futures): joining one inside a traced region
#: serializes the trace on host progress.  It is deliberately NOT in
#: SYNC_METHODS_ANYWHERE — ``ticket.result()`` in eager glue
#: (checkpoint.py drain paths) is the intended usage.
SYNC_METHODS = {"asnumpy", "asscalar", "item", "tolist",
                "block_until_ready", "wait_to_read", "wait_to_write",
                "result"}

#: syncs unambiguous enough to warn about even in eager glue code
SYNC_METHODS_ANYWHERE = {"asnumpy", "asscalar", "item",
                         "block_until_ready"}

#: designated result-materialization defs: a function carrying one of
#: these names IS the module's sanctioned batch-boundary sync point
#: (the serving scheduler's ``_materialize`` — one device->host wait
#: per dispatched batch, at demux; see docs/serving.md).  Sync methods
#: inside such a def skip the eager T1 warning — the same shape as the
#: PR 7 ``ticket.result()`` treatment (intentional eager waits stay
#: legal) but scoped by enclosing-def name instead of method name.
#: Inside a TRACED region the error still fires: naming a hot function
#: ``_materialize`` buys nothing.  ``_lane_materialize`` is the
#: disaggregated serving lanes' twin (serving/lanes.py): the decode
#: drain and the prefill→decode handoff sync there, and nowhere else.
#: ``_fleet_exchange`` (telemetry/fleet.py, r13) is the stride-gated
#: allgather of the packed step-stats vector: an intentional eager
#: collective+sync at the fleet-exchange boundary, never per-step and
#: never inside a trace — exempt the same way.
#: ``_prefetch`` (data/prefetch.py, r14) is the data plane's transfer
#: thread: it device_puts the NEXT batch and ``block_until_ready``s it
#: so the trainer inherits a landed array instead of a lazy copy — the
#: sync IS the prefetch, off the consumer thread by construction, never
#: in a trace.
MATERIALIZE_DEFS = {"_materialize", "_lane_materialize", "_fleet_exchange",
                    "_prefetch"}

#: function-style syncs, matched on dotted name
SYNC_FUNCS_ANYWHERE = {"jax.device_get"}
SYNC_FUNCS_TRACED = {"np.asarray", "numpy.asarray", "onp.asarray",
                     "_np.asarray", "np.array", "numpy.array",
                     "jax.device_get",
                     # engine.flush() executes the thread's pending bulk
                     # segment — a host-side sync site (docs/engine.md);
                     # inside a traced region it is at best a no-op and at
                     # worst hides a real sync the eager path would hit
                     "engine.flush", "_engine.flush",
                     "mxnet_tpu.engine.flush"}

#: builtins that force a tracer to a host scalar
SCALAR_BUILTINS = {"float", "int", "bool"}

#: dotted heads naming the observability layer: ``telemetry.count(...)`` /
#: ``prof.record_span_event(...)`` never sync and never run inside a trace
#: (spans enter the trace path only via _trace_guard-stripped replays), so
#: T1/T4 skip them outright
RECORDING_HEADS = {"telemetry", "profiler", "prof",
                   # memory/cost observability (telemetry.memwatch /
                   # telemetry.costs, conventionally imported as _mw /
                   # _costs): ledger and registry updates are host-side
                   # arithmetic behind one-boolean flags — never a sync
                   "memwatch", "costs", "_mw", "_costs",
                   # r12 request tracing + the serving metrics endpoint
                   # (telemetry.tracing / serving.metrics): span records
                   # are retroactive dict/list appends from perf_counter
                   # stamps the lanes already take, and the scrape
                   # renderer reads telemetry snapshots — host-side by
                   # contract, never a device sync
                   "tracing", "_tracing", "metrics",
                   # lane log (telemetry.tracing.lane_record, a dict and
                   # a deque append) and the ``mxt.*`` profiler spans at
                   # the same boundaries: ``jax.profiler.TraceAnnotation``
                   # is a TraceMe — one atomic load while no profile
                   # runs, a host-side event record while one does
                   "TraceAnnotation",
                   # r13 fleet observability (telemetry.fleet, aliased
                   # _fleet_mod in telemetry/__init__; promtext is the
                   # shared scrape renderer): ring appends, watchdog
                   # arithmetic and text rendering — host-side; the one
                   # collective lives in _fleet_exchange (see
                   # MATERIALIZE_DEFS), stride-gated off the hot path
                   "fleet", "_fleet", "_fleet_mod", "promtext",
                   # r17 numerics tier (telemetry.numerics, conventionally
                   # imported as _numerics): taps are pure jnp stat math
                   # that rides the trace as side outputs — never
                   # jax.debug, never a host sync; the one materialize is
                   # stride-gated inside numerics._materialize
                   # (MATERIALIZE_DEFS) and the forensic replay half never
                   # runs in training code
                   "numerics", "_numerics",
                   # r18 recompile sanitizer (telemetry.retrace): observe
                   # hooks ride compile-miss branches only — structural
                   # bookkeeping behind one boolean, never a device sync,
                   # and replays never reach them
                   "retrace", "_retrace",
                   # r20 capacity accounting (telemetry.capacity, aliased
                   # _capacity_mod in telemetry/__init__): note hooks are
                   # retroactive interval/EWMA appends from perf_counter
                   # stamps the serving lanes already take — one boolean
                   # disabled, a few float ops under one lock enabled,
                   # never a device touch
                   "capacity", "_capacity", "_capacity_mod"}


def _is_recording_call(dotted: str) -> bool:
    return bool(dotted) and dotted.split(".", 1)[0] in RECORDING_HEADS


# --- T4 ---------------------------------------------------------------------

_TIME_LAST = {"time", "perf_counter", "monotonic", "process_time",
              "time_ns", "perf_counter_ns", "now", "utcnow", "today"}
_NP_RANDOM_PREFIXES = ("np.random.", "numpy.random.", "onp.random.",
                       "_np.random.")


def _is_nondet_call(dotted: str) -> bool:
    if not dotted:
        return False
    if dotted.startswith(_NP_RANDOM_PREFIXES):
        return True
    if dotted.startswith("random."):
        return True  # stdlib random (jax.random is keyed => deterministic)
    if dotted.startswith(("time.", "datetime.")) and \
            dotted.rsplit(".", 1)[-1] in _TIME_LAST:
        return True
    return False


# --- T3 ---------------------------------------------------------------------

#: jnp/lax calls whose output carries no useful cotangent: an op whose
#: pure body *returns* one of these needs an explicit no_grad marker
NONDIFF_CALLS = {"argmax", "argmin", "argsort", "sign", "floor", "ceil",
                 "round", "rint", "trunc", "searchsorted", "nonzero",
                 "logical_not", "logical_and", "logical_or", "logical_xor",
                 "isnan", "isinf", "isfinite", "equal", "not_equal",
                 "greater", "greater_equal", "less", "less_equal",
                 "one_hot", "bincount", "sort_key_val"}

#: wrappers transparent to differentiability: ``nondiff(...).astype(...)``
#: is still nondiff
_TRANSPARENT_WRAPPERS = {"astype", "reshape", "moveaxis", "swapaxes",
                         "transpose", "squeeze", "expand_dims", "ravel"}


class Registration:
    """One static ``@defop`` / ``_export`` site."""

    __slots__ = ("name", "aliases", "no_grad", "func_node", "path", "line",
                 "col", "dynamic")

    def __init__(self, name, aliases, no_grad, func_node, path, line, col,
                 dynamic=False):
        self.name = name
        self.aliases = aliases
        self.no_grad = no_grad
        self.func_node = func_node
        self.path = path
        self.line = line
        self.col = col
        self.dynamic = dynamic


def _const_str(node):
    return node.value if isinstance(node, ast.Constant) and \
        isinstance(node.value, str) else None


def _const_str_tuple(node):
    if isinstance(node, (ast.Tuple, ast.List)):
        vals = [_const_str(e) for e in node.elts]
        if all(v is not None for v in vals):
            return tuple(vals)
    return None


def _kw(call, name):
    for kw in call.keywords:
        if kw.arg == name:
            return kw.value
    return None


def collect_registrations(src, index: FunctionIndex):
    """Find every static op-registration site in a file.

    Handles both exporter idioms in this codebase:
      * registry-style  ``_export(fn, name="x", aliases=(...), no_grad=True)``
        and the ``@defop("x", aliases=..., no_grad=...)`` decorator;
      * elemwise-style  ``_export("x", fn, aliases, no_grad=True)``
        (string first).
    Registrations whose name is computed (a loop variable) are recorded as
    ``dynamic`` and left to the runtime registry check.
    """
    regs = []
    decorator_calls = set()
    for node in ast.walk(src.tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for deco in node.decorator_list:
                if isinstance(deco, ast.Call) and \
                        last_name(deco.func) == "defop":
                    decorator_calls.add(id(deco))
                    name = None
                    if deco.args:
                        name = _const_str(deco.args[0])
                    kw_name = _kw(deco, "name")
                    if kw_name is not None:
                        name = _const_str(kw_name)
                    regs.append(_make_reg(name or node.name, deco, node,
                                          src.path))
                elif last_name(deco) == "defop":
                    regs.append(Registration(node.name, (), False, node,
                                             src.path, node.lineno,
                                             node.col_offset))
        if not isinstance(node, ast.Call) or id(node) in decorator_calls:
            continue
        if last_name(node.func) not in ("_export", "_export_fn", "defop"):
            continue
        if not node.args:
            continue
        first = node.args[0]
        if _const_str(first) is not None:
            # elemwise-style: _export(name, fn, aliases=...)
            fn_node = node.args[1] if len(node.args) > 1 else None
            regs.append(_make_reg(_const_str(first), node,
                                  _resolve_func(fn_node, index), src.path,
                                  alias_pos=2))
        elif isinstance(first, (ast.Name, ast.Lambda, ast.Attribute)):
            name_expr = _kw(node, "name")
            if name_expr is None and len(node.args) > 1:
                name_expr = node.args[1]
            if name_expr is not None:
                name = _const_str(name_expr)
                dynamic = name is None
            else:
                name = last_name(first) if not isinstance(first, ast.Lambda) \
                    else None
                dynamic = name is None
            regs.append(_make_reg(name, node,
                                  _resolve_func(first, index), src.path,
                                  dynamic=dynamic))
        else:
            # _export(_scalar_op(_name, _fn), name=_name): fully dynamic
            regs.append(Registration(None, (), False, None, src.path,
                                     node.lineno, node.col_offset,
                                     dynamic=True))
    return regs


def _make_reg(name, call, func_node, path, alias_pos=None, dynamic=False):
    aliases = ()
    alias_expr = _kw(call, "aliases")
    if alias_expr is None and alias_pos is not None and \
            len(call.args) > alias_pos:
        alias_expr = call.args[alias_pos]
    if alias_expr is not None:
        aliases = _const_str_tuple(alias_expr) or ()
    ng_expr = _kw(call, "no_grad")
    no_grad = isinstance(ng_expr, ast.Constant) and ng_expr.value is True
    return Registration(name, aliases, no_grad, func_node, path,
                        call.lineno, call.col_offset, dynamic=dynamic)


def _resolve_func(node, index: FunctionIndex):
    if isinstance(node, ast.Lambda):
        return node
    if isinstance(node, (ast.Name, ast.Attribute)):
        cands = index.by_name.get(last_name(node), ())
        if len(cands) == 1:
            return cands[0]
    return None


def _returns_nondiff(expr, func_node, _depth=0) -> bool:
    """Does ``expr`` (a return value) derive directly from a
    non-differentiable primitive?  Unwraps dtype/layout-transparent
    wrappers and follows one level of local assignment."""
    if _depth > 4 or expr is None:
        return False
    if isinstance(expr, ast.Compare):
        return True
    if isinstance(expr, ast.Call):
        name = last_name(expr.func)
        if name in NONDIFF_CALLS:
            return True
        if name in _TRANSPARENT_WRAPPERS and \
                isinstance(expr.func, ast.Attribute):
            return _returns_nondiff(expr.func.value, func_node, _depth + 1)
        return False
    if isinstance(expr, ast.Name):
        assigned = None
        for n in ast.walk(func_node):
            if isinstance(n, ast.Assign) and any(
                    isinstance(t, ast.Name) and t.id == expr.id
                    for t in n.targets):
                assigned = n.value
        return _returns_nondiff(assigned, func_node, _depth + 1)
    if isinstance(expr, ast.Attribute):
        return _returns_nondiff(expr.value, func_node, _depth + 1)
    return False


def _pure_bodies(func_node, index: FunctionIndex):
    """Inner callables handed to ``apply_op`` inside an op wrapper — the
    functions that actually trace."""
    out = []
    for call in ast.walk(func_node):
        if isinstance(call, ast.Call) and \
                last_name(call.func) == "apply_op" and call.args:
            inner = call.args[0]
            if isinstance(inner, ast.Lambda):
                out.append(inner)
            elif isinstance(inner, ast.Name):
                resolved = _resolve_func(inner, index)
                if resolved is not None and resolved is not func_node:
                    out.append(resolved)
    return out


def _all_returns_nondiff(fn) -> bool:
    if isinstance(fn, ast.Lambda):
        return _returns_nondiff(fn.body, fn)
    returns = [n for n in ast.walk(fn) if isinstance(n, ast.Return)
               and n.value is not None]
    if not returns:
        return False
    return all(_returns_nondiff(r.value, fn) for r in returns)


# --- T8 ---------------------------------------------------------------------

#: regexes that match every parameter path — a rule after one of these
#: is dead under first-match-wins
_CATCH_ALL_PATTERNS = {"", ".*", ".+", "^.*", ".*$", "^.*$", "^.+$"}

#: spec axis names that shard the MODEL (vs the batch): a table using
#: these must say what happens to everything else
_MODEL_AXES = {"tp", "ep", "mp", "sp", "model", "expert", "tensor"}


def _literal_rule_table(node, src):
    """``node`` as a literal ((pattern, spec), ...) rule table, following
    one level of module-scope Name assignment.  Returns a list of
    (pattern_str_or_None, spec_elements_or_None, ast_node) entries, or
    None when the expression is not a literal table (dynamic tables are
    the engine's problem at runtime, not the linter's)."""
    if isinstance(node, ast.Name):
        assigned = None
        for stmt in src.tree.body:
            if isinstance(stmt, ast.Assign) and any(
                    isinstance(t, ast.Name) and t.id == node.id
                    for t in stmt.targets):
                assigned = stmt.value
        node = assigned
    if not isinstance(node, (ast.Tuple, ast.List)):
        return None
    entries = []
    for elt in node.elts:
        if not isinstance(elt, (ast.Tuple, ast.List)) or \
                len(elt.elts) != 2:
            return None  # not a (pattern, spec) table after all
        pat = _const_str(elt.elts[0])
        spec_node = elt.elts[1]
        spec = None
        if isinstance(spec_node, (ast.Tuple, ast.List)):
            vals = [e.value for e in spec_node.elts
                    if isinstance(e, ast.Constant)]
            if len(vals) == len(spec_node.elts):
                spec = vals
        entries.append((pat, spec, elt))
    return entries


# --- T9 ---------------------------------------------------------------------

#: direct remat primitives — the policy engine's ``checkpoint_wrap`` is
#: the ONE sanctioned call site for model code (memory/policy.py), so a
#: dotted call to any of these inside model code bypasses the tier ladder
_T9_CHECKPOINT_CALLS = {"jax.checkpoint", "jax.remat",
                        "jax.ad_checkpoint.checkpoint",
                        "ad_checkpoint.checkpoint"}

#: planner/policy entry points whose RETURN VALUE is the product: a fit
#: verdict, a prescription, or a selected tier.  Called as a bare
#: statement, the verdict is discarded and nothing gates on it.
_T9_PLANNER_FUNCS = {"plan_model", "auto_tier", "plan_from_artifact",
                     "select_tier", "prescribe"}

#: dotted heads that identify the planner (``planner.plan_model`` /
#: ``mem.auto_tier``); a bare imported name also counts
_T9_PLANNER_HEADS = {"planner", "policy", "memory", "mem", "_mem",
                     "_planner", "_policy", "_mem_planner", "_mem_policy",
                     "mxnet_tpu"}


def _t9_is_model_code(src) -> bool:
    """Model code = a file under a ``models`` package, or one defining a
    class with a ``hybrid_forward`` method (a gluon block)."""
    parts = src.path.replace("\\", "/").split("/")
    if "models" in parts:
        return True
    for node in ast.walk(src.tree):
        if isinstance(node, ast.ClassDef):
            for item in node.body:
                if isinstance(item, (ast.FunctionDef,
                                     ast.AsyncFunctionDef)) and \
                        item.name == "hybrid_forward":
                    return True
    return False


def _t9_stmt_calls(tree):
    """ids of Call nodes that ARE a whole expression statement — their
    value is discarded."""
    out = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Expr) and isinstance(node.value, ast.Call):
            out.add(id(node.value))
    return out


# ---------------------------------------------------------------------------
# Per-file rule driver
# ---------------------------------------------------------------------------

class FileChecker:
    """Runs T1/T2/T4/T5 over one parsed file and collects T3
    registrations for the cross-file pass."""

    def __init__(self, src, enabled=None):
        self.src = src
        self.enabled = enabled
        self.index = FunctionIndex(src.tree)
        self.violations = []
        self.registrations = []
        self.reg_facts = []       # serializable T3 facts (cacheable)
        self.lock_facts = {"path": src.path, "edges": []}  # T11 facts
        self._taint_cache = {}

    def _on(self, rule):
        return self.enabled is None or rule in self.enabled

    def run(self):
        if self._on("T3"):
            self.registrations = collect_registrations(self.src, self.index)
            self.reg_facts = [registration_facts(r, self.src, self.index)
                              for r in self.registrations]
        if self.enabled is None or (self.enabled & _CONCURRENCY_RULES):
            conc, self.lock_facts = check_concurrency(
                self.src, self.index, enabled=self.enabled)
            self.violations.extend(conc)
        if self.enabled is None or (self.enabled & _COMPILE_RULES):
            self.violations.extend(check_compile_discipline(
                self.src, self.index, enabled=self.enabled))
        if self._on("T6") or self._on("T7"):
            self.violations.extend(check_donation(
                self.src, self.index, enabled=self.enabled))
        t5_taint = self._t5_taint() if self._on("T5") else {}
        t9_model = _t9_is_model_code(self.src) if self._on("T9") else False
        t9_stmts = _t9_stmt_calls(self.src.tree) if self._on("T9") \
            else frozenset()
        for node in ast.walk(self.src.tree):
            hot = self.index.in_traced_region(node)
            if isinstance(node, ast.Call):
                if self._on("T1"):
                    self._check_t1(node, hot)
                if self._on("T4") and hot:
                    self._check_t4(node)
                if self._on("T5"):
                    self._check_t5_mutator_call(node, t5_taint)
                if self._on("T8"):
                    self._check_t8(node)
                if self._on("T9"):
                    self._check_t9(node, t9_model, t9_stmts)
            elif isinstance(node, (ast.If, ast.While, ast.Assert)) and hot:
                if self._on("T2"):
                    self._check_t2(node)
            elif isinstance(node, (ast.Assign, ast.AugAssign)):
                if self._on("T5"):
                    self._check_t5_store(node, t5_taint)
        return self.violations

    def _emit(self, rule, severity, node, message):
        line = getattr(node, "lineno", 0)
        if self.src.is_suppressed(rule, line):
            return
        self.violations.append(Violation(
            rule=rule, severity=severity, path=self.src.path, line=line,
            col=getattr(node, "col_offset", 0),
            context=self.index.qualname_of(node), message=message,
            source=self.src.line_text(line)))

    # -- T1 ------------------------------------------------------------------
    def _check_t1(self, call, hot):
        func = call.func
        dotted = dotted_name(func)
        if _is_recording_call(dotted):
            return
        if isinstance(func, ast.Attribute):
            meth = func.attr
            if hot and meth in SYNC_METHODS:
                self._emit("T1", SEVERITY_ERROR, call,
                           f".{meth}() forces a host sync inside a traced "
                           "hot path")
                return
            if not hot and meth in SYNC_METHODS_ANYWHERE:
                fn_node = self.index.enclosing_function(call)
                if fn_node is not None and \
                        getattr(fn_node, "name", None) in MATERIALIZE_DEFS:
                    return  # sanctioned batch-boundary sync point
                self._emit("T1", SEVERITY_WARNING, call,
                           f".{meth}() blocks on the dispatch queue; keep "
                           "it out of per-step loops or waiver it")
                return
        if hot and dotted in SYNC_FUNCS_TRACED:
            self._emit("T1", SEVERITY_ERROR, call,
                       f"{dotted}() on a traced value concretizes the "
                       "tracer (host sync) inside a hot path")
            return
        if not hot and dotted in SYNC_FUNCS_ANYWHERE:
            self._emit("T1", SEVERITY_WARNING, call,
                       f"{dotted}() is a blocking device->host transfer")
            return
        if hot and isinstance(func, ast.Name) and \
                func.id in SCALAR_BUILTINS and len(call.args) == 1 and \
                not isinstance(call.args[0], ast.Constant):
            fn_node = self.index.enclosing_function(call)
            taint = self._taint_for(fn_node)
            if fn_node is not None and expr_tainted(call.args[0], taint):
                self._emit("T1", SEVERITY_ERROR, call,
                           f"{func.id}() on a traced value forces a host "
                           "sync / concretization inside a hot path")

    # -- T2 ------------------------------------------------------------------
    def _taint_for(self, fn_node):
        if fn_node is None:
            return set()
        key = id(fn_node)
        if key not in self._taint_cache:
            if isinstance(fn_node, ast.Lambda):
                taint = {a.arg for a in fn_node.args.args}
            else:
                taint = function_taint(fn_node)
            self._taint_cache[key] = taint
        return self._taint_cache[key]

    def _check_t2(self, node, ):
        fn_node = self.index.enclosing_function(node)
        if fn_node is None:
            return
        taint = self._taint_for(fn_node)
        test = node.test
        if expr_tainted(test, taint):
            kind = {ast.If: "if", ast.While: "while",
                    ast.Assert: "assert"}[type(node)]
            self._emit("T2", SEVERITY_ERROR, node,
                       f"python `{kind}` on a traced value inside a traced "
                       "region — use lax.cond/jnp.where or hoist the check "
                       "out of the trace")

    # -- T4 ------------------------------------------------------------------
    def _check_t4(self, call):
        dotted = dotted_name(call.func)
        if _is_recording_call(dotted):
            return
        if _is_nondet_call(dotted):
            self._emit("T4", SEVERITY_ERROR, call,
                       f"{dotted}() inside a traced region is evaluated "
                       "once at trace time and baked in as a constant — "
                       "thread a jax PRNG key / pass timestamps as inputs")

    # -- T8 ------------------------------------------------------------------
    def _check_t8(self, call):
        """Static sanity on LITERAL partition-rule tables at the sites
        that consume them."""
        name = last_name(call.func)
        table_expr = None
        if name == "PartitionRules" and call.args:
            table_expr = call.args[0]
        elif name == "place_params" and len(call.args) > 1:
            table_expr = call.args[1]
        if table_expr is None:
            kw = _kw(call, "partition_rules") or _kw(call, "rules")
            table_expr = kw
        if table_expr is None:
            return
        entries = _literal_rule_table(table_expr, self.src)
        if not entries:
            return
        seen, dead_after = {}, None
        uses_model_axis = False
        for pat, spec, node in entries:
            if pat is None:
                continue  # computed pattern: runtime's problem
            try:
                re.compile(pat)
            except re.error as e:
                self._emit("T8", SEVERITY_ERROR, node,
                           f"partition rule pattern {pat!r} does not "
                           f"compile ({e}) — it can never match a "
                           "parameter")
                continue
            if dead_after is not None:
                self._emit("T8", SEVERITY_ERROR, node,
                           f"rule {pat!r} is unreachable: it follows the "
                           f"catch-all {dead_after!r} and first match "
                           "wins — reorder the table")
            elif pat in seen:
                self._emit("T8", SEVERITY_ERROR, node,
                           f"duplicate pattern {pat!r}: first match wins, "
                           "this rule never fires — merge or reorder")
            seen[pat] = node
            if pat.strip("$^") in ("", ".*", ".+") or \
                    pat in _CATCH_ALL_PATTERNS:
                dead_after = dead_after or pat
            if spec and any(a in _MODEL_AXES for a in spec
                            if isinstance(a, str)):
                uses_model_axis = True
        has_catch_all = dead_after is not None
        explicit_policy = _kw(call, "on_unmatched") is not None
        if uses_model_axis and not has_catch_all and not explicit_policy:
            self._emit("T8", SEVERITY_WARNING, call,
                       "rule table shards model axes but has no terminal "
                       "catch-all and no on_unmatched= policy: unmatched "
                       "parameters silently replicate over the mesh — add "
                       "an explicit ('.*', ()) fallback or "
                       "on_unmatched='error'")

    # -- T9 ------------------------------------------------------------------
    def _check_t9(self, call, model_code, stmt_calls):
        dotted = dotted_name(call.func)
        if model_code and dotted in _T9_CHECKPOINT_CALLS:
            self._emit("T9", SEVERITY_ERROR, call,
                       f"hand-rolled {dotted}() in model code bypasses "
                       "the remat policy engine — wrap with "
                       "memory.policy.checkpoint_wrap (or declare "
                       "hybridize(remat=...) / set_remat) so the "
                       "auto-tier ladder stays in control")
            return
        name = last_name(call.func)
        if name in _T9_PLANNER_FUNCS and id(call) in stmt_calls:
            head = dotted.split(".", 1)[0] if "." in dotted else ""
            if not head or head in _T9_PLANNER_HEADS:
                self._emit("T9", SEVERITY_WARNING, call,
                           f"{name}() called as a bare statement — the "
                           "returned plan/verdict is discarded; assign "
                           "it and gate on fits/headroom (or drop the "
                           "call)")

    # -- T5 ------------------------------------------------------------------
    def _t5_taint(self):
        """Names assigned from host views of device buffers."""
        taint = set()
        for node in ast.walk(self.src.tree):
            if not isinstance(node, ast.Assign):
                continue
            if _is_host_view(node.value):
                for t in node.targets:
                    if isinstance(t, ast.Name):
                        taint.add(t.id)
        return taint

    def _check_t5_store(self, node, taint):
        target = node.targets[0] if isinstance(node, ast.Assign) \
            else node.target
        root = _subscript_root(target)
        if root is None:
            return
        if isinstance(root, ast.Name) and root.id in taint:
            self._emit("T5", SEVERITY_ERROR, node,
                       f"in-place mutation of `{root.id}`, a host view of "
                       "a jax-backed buffer — the write never reaches "
                       "device memory (copy first, or build a new array)")
        elif _is_host_view(root):
            self._emit("T5", SEVERITY_ERROR, node,
                       "subscript-assign into a fresh host view of a "
                       "jax-backed buffer — the write is discarded")

    def _check_t5_mutator_call(self, call, taint):
        func = call.func
        if isinstance(func, ast.Attribute) and \
                func.attr in ("fill", "put", "itemset", "resize",
                              "setfield", "partition"):
            base = func.value
            if isinstance(base, ast.Name) and base.id in taint:
                self._emit("T5", SEVERITY_ERROR, call,
                           f"`.{func.attr}()` mutates `{base.id}`, a host "
                           "view of a jax-backed buffer")
            elif _is_host_view(base):
                self._emit("T5", SEVERITY_ERROR, call,
                           f"`.{func.attr}()` mutates a fresh host view "
                           "of a jax-backed buffer")
        if dotted_name(func) in ("np.copyto", "numpy.copyto") and call.args:
            dst = call.args[0]
            if (isinstance(dst, ast.Name) and dst.id in taint) or \
                    _is_host_view(dst):
                self._emit("T5", SEVERITY_ERROR, call,
                           "np.copyto into a host view of a jax-backed "
                           "buffer — the write never reaches the device")


def _subscript_root(target):
    """For ``a[i]`` / ``a[i][j]`` / ``a.flat[i]`` return the base
    expression ``a``; None if the target is a bare name/attribute."""
    if not isinstance(target, ast.Subscript):
        return None
    base = target.value
    while isinstance(base, ast.Subscript):
        base = base.value
    if isinstance(base, ast.Attribute) and base.attr == "flat":
        base = base.value
    return base


def _is_host_view(expr) -> bool:
    """``x.asnumpy()`` / ``jax.device_get(x)`` / ``np.asarray(x._data)``."""
    if not isinstance(expr, ast.Call):
        return False
    func = expr.func
    if isinstance(func, ast.Attribute) and func.attr == "asnumpy":
        return True
    dotted = dotted_name(func)
    if dotted == "jax.device_get":
        return True
    if dotted in ("np.asarray", "numpy.asarray", "onp.asarray") and \
            expr.args and isinstance(expr.args[0], ast.Attribute) and \
            expr.args[0].attr == "_data":
        return True
    return False


# ---------------------------------------------------------------------------
# Cross-file T3 finalization
# ---------------------------------------------------------------------------

def registration_facts(reg, src, index):
    """Reduce a Registration (which carries an AST node) to the
    serializable facts the cross-file pass needs.  Everything derived
    from the AST — docstrings, lambda-ness, the nondiff return scan —
    is computed here, per file, so cached files skip AST work
    entirely."""
    fn = reg.func_node
    is_lambda = isinstance(fn, ast.Lambda)
    has_doc = bool(ast.get_docstring(fn)) if fn is not None and \
        not is_lambda else False
    returns_nondiff = False
    if fn is not None and not is_lambda and not reg.no_grad:
        returns_nondiff = any(_all_returns_nondiff(body)
                              for body in _pure_bodies(fn, index))
    return {
        "name": reg.name,
        "aliases": list(reg.aliases),
        "no_grad": reg.no_grad,
        "dynamic": reg.dynamic,
        "path": reg.path,
        "line": reg.line,
        "col": reg.col,
        "has_func": fn is not None,
        "is_lambda": is_lambda,
        "has_doc": has_doc,
        "returns_nondiff": returns_nondiff,
        "suppressed": src.is_suppressed("T3", reg.line),
        "source": src.line_text(reg.line),
    }


def check_registrations(all_facts):
    """Duplicate / docstring / grad-path checks over every static
    registration fact collected in the run (see registration_facts)."""
    violations = []

    def emit(fact, message, severity=SEVERITY_ERROR, context=None):
        if fact["suppressed"]:
            return
        violations.append(Violation(
            rule="T3", severity=severity, path=fact["path"],
            line=fact["line"], col=fact["col"],
            context=context or (fact["name"] or "<dynamic>"),
            message=message, source=fact["source"]))

    seen = {}
    for fact in all_facts:
        if fact["dynamic"] or fact["name"] is None:
            continue
        for name in (fact["name"],) + tuple(fact["aliases"]):
            prev = seen.get(name)
            if prev is not None and (prev["path"], prev["line"]) != \
                    (fact["path"], fact["line"]):
                emit(fact, f"op name {name!r} already registered at "
                           f"{prev['path']}:{prev['line']} — duplicate "
                           "registration shadows the original",
                     context=name)
            else:
                seen[name] = fact
        if not fact["has_func"]:
            continue
        if not fact["name"].startswith("_"):
            if fact["is_lambda"]:
                emit(fact, f"op {fact['name']!r} is registered as a bare "
                           "lambda — give it a named, documented wrapper",
                     severity=SEVERITY_WARNING)
            elif not fact["has_doc"]:
                emit(fact, f"op {fact['name']!r} has no docstring",
                     severity=SEVERITY_WARNING)
        if fact["returns_nondiff"]:
            emit(fact, f"op {fact['name']!r} returns a "
                       "non-differentiable value but is not marked "
                       "no_grad=True — mark it (or wire a custom vjp) "
                       "so autograd skips the vjp trace instead of "
                       "emitting garbage cotangents")
    return violations
