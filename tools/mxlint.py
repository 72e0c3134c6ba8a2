#!/usr/bin/env python3
"""mxlint — project-specific AST lint + lightweight race detector.

Six PRs of hard-won correctness rules live in comments and CHANGES.md;
this tool encodes them mechanically (the TVM/Relay move: check graph-
program invariants on every build instead of re-learning them in review).
Stdlib-only, like launch.py and trace_report.py.

Rules (each descends from a real bug — docs/STATIC_ANALYSIS.md has the
full catalog with provenance):

  hot-sync             host readback (np.asarray / .item() / float() /
                       jax.device_get / block_until_ready) or memory
                       polling (.memory_stats() / jax.live_arrays() /
                       .memory_analysis() — PR 8: sample via memwatch at
                       step boundaries) reachable from a per-step
                       dispatch body (PR 4: one stray sync stalls the
                       whole async pipeline)
  wall-clock-duration  subtracting two time.time() reads for a duration
                       (PR 2: wall-clock steps gave negative samples/sec)
  retrace-hazard       jax.jit constructed inside a per-step function, or
                       an unhashable literal passed in a static_argnums
                       position (retrace storm / TypeError at runtime)
  signal-unsafe        import / lock-acquire / open() lexically inside a
                       registered signal handler (PR 1/4: imports take
                       the import lock; a handler interrupting an import
                       deadlocks)
  thread-shared-write  an attribute assigned both from a thread worker
                       and from consumer methods with no common lock
  silent-except        broad `except: pass` with no telemetry record and
                       no justification comment
  env-unregistered     a quoted MX_*/MXNET_* use-site absent from
                       env_vars.ENV_VARS (registry drift guard)
  jax-in-handler       jax import/use reachable from a declared jax-free
                       handler entry point (PR 13: the metrics endpoint
                       serves from the telemetry recorder's locked
                       rollups on a daemon thread — touching jax there
                       can deadlock runtime init or force a device sync
                       under the training loop); these entries also get
                       the full hot-sync readback checks

Suppression: `# mxlint: disable=rule[,rule] <justification>` on the
flagged line (or alone on the line above) silences the finding; an
unknown rule name in a suppression is itself a finding (bad-suppression).
Accepted legacy findings live in tools/mxlint_baseline.json, each entry
carrying a one-line justification.

Exit codes: 0 clean, 2 usage error, 3 findings.
"""
from __future__ import annotations

import argparse
import ast
import io
import json
import os
import re
import sys
import time
import tokenize

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEFAULT_BASELINE = os.path.join(REPO, "tools", "mxlint_baseline.json")
DEFAULT_PATHS = ("mxnet_tpu", "tools", "examples")

RULES = {
    "hot-sync": "host readback reachable from a per-step dispatch body",
    "wall-clock-duration": "time.time() subtraction used as a duration",
    "retrace-hazard": "jax.jit built per step / unhashable static argument",
    "signal-unsafe": "import, lock acquire or open() inside a signal handler",
    "thread-shared-write": "attribute written by worker thread and consumer "
                           "without a common lock",
    "silent-except": "broad except:pass with no telemetry or justification",
    "env-unregistered": "quoted MX_*/MXNET_* use-site not in ENV_VARS",
    "jax-in-handler": "jax import/use reachable from a jax-free handler "
                      "entry point",
    "bad-suppression": "mxlint suppression naming an unknown rule",
    "stale-hot-entry": "configured hot-path entry point no longer resolves",
    "pass-outside-pipeline": "op-dispatch body consults module-global pass "
                             "state outside the pass-hook protocol",
    "syntax-error": "file failed to parse",
}

# per-step dispatch bodies: the hot-sync / retrace-hazard reachability
# analysis starts here (repo-relative path -> function qualnames)
HOT_PATH_ENTRIES = {
    "mxnet_tpu/parallel/data_parallel.py": (
        "DataParallelStep._step_impl", "DataParallelStep.stage",
        # the Plan dispatch body: EVERY compiled-step execution (any
        # strategy Plan) funnels through it — a host sync here would
        # stall every strategy at once
        "DataParallelStep._plan_dispatch"),
    "mxnet_tpu/optimizer/fused.py": ("FusedUpdater._apply_impl",),
    # precision subsystem (docs/PRECISION.md): the fused overflow reduce
    # the eager loss-scale shim dispatches per step, and the int8
    # adapter's decode body (the trace body of the ONE quantized decode
    # executable — a host sync here would land inside engine tracing or
    # stall the serving pipeline)
    "mxnet_tpu/precision/loss_scale.py": ("overflow_flag",),
    "mxnet_tpu/precision/quantize.py": ("_RewriteAdapterBase.decode",),
    # the eager AMP compatibility shim: scale_loss/has_overflow run per
    # Trainer step — the PR 15 fix replaced its per-gradient asnumpy()
    # loop with ONE fused device reduce; these entries keep the old
    # readback pattern from creeping back in
    "mxnet_tpu/contrib/amp/amp.py": ("DynamicLossScaler.has_overflow",
                                     "unscale"),
    "mxnet_tpu/parallel/async_loss.py": (
        "InflightRing.make_room", "InflightRing.admit",
        "InflightRing.discard"),
    "mxnet_tpu/kvstore.py": ("KVStore.push_bucketed",),
    # serving engine: the per-step decode dispatch body — chains device
    # state through the compiled step and admits the lazy token handle;
    # a host sync here would serialize the whole serving pipeline.  The
    # front-door additions ride the same contract: the speculative
    # verify dispatch (_dispatch_spec) and the jitted trace bodies
    # (sampled decode, K-token verify, prefix ingest) are per-step code
    # — a readback inside any of them stalls every in-flight request
    "mxnet_tpu/serving/engine.py": (
        "ServingEngine._dispatch_step", "ServingEngine._dispatch_spec",
        "ServingEngine._decode_body", "ServingEngine._verify_body",
        "ServingEngine._ingest_body"),
}

# THE pass-pipeline consultation point (docs/PRECISION.md §Pass
# pipeline): repo-relative path -> the op-dispatch body, the hook-module
# alias it must consult, and the (module-alias, _attr) loads it is
# allowed.  Any OTHER `<module>._underscore` load inside the dispatch
# body is a graph pass smuggled around the pipeline — a module global
# the pipeline fingerprint cannot see, exactly the one-off pattern the
# pass registry absorbed.  Like HOT_PATH_ENTRIES, a stale entry (the
# body renamed away, or the hook consultation deleted) fails loudly
# instead of turning the rule into a silent no-op.
PASS_DISPATCH_ENTRIES = {
    "mxnet_tpu/ops/registry.py": {
        "function": "_invoke_impl",
        "hook_module": "_pass_hooks",
        "allowed": (("_pass_hooks", "_OP_HOOKS"),
                    # the row-sparse Embedding cotangent type — autograd
                    # tape plumbing, not trace-rewrite state
                    ("autograd", "_RowSparseCT")),
    },
}

# HTTP handler threads that must NEVER touch jax (repo-relative path ->
# function qualnames): the live metrics endpoint serves the telemetry
# recorder's locked rollups only — a jax import there can deadlock
# against runtime init, and any device readback stalls the training
# loop from a scrape.  Reachable functions get the hot-sync readback
# checks PLUS a lexical jax import/alias-use scan (jax-in-handler).
JAX_FREE_ENTRIES = {
    "mxnet_tpu/metrics_server.py": ("_Handler.do_GET",),
    # serving front door: replica + router HTTP handlers only build
    # Request objects, poll host-side stream flags and relay JSON — the
    # engine-driver thread owns the device.  A jax import here can
    # deadlock against runtime init; a readback stalls decode from an
    # HTTP request
    "mxnet_tpu/serving/router.py": (
        "_ReplicaHandler.do_GET", "_ReplicaHandler.do_POST",
        "_RouterHandler.do_GET", "_RouterHandler.do_POST"),
}

# env-unregistered applies where the registry contract always has:
# the package and the tools (examples set vars, they don't define knobs)
ENV_RULE_PREFIXES = ("mxnet_tpu", "tools")

_ENV_NAME = re.compile(r"^MX(?:NET)?_[A-Z][A-Z0-9_]*$")
_SUPPRESS = re.compile(r"#\s*mxlint:\s*disable=([A-Za-z0-9_,\- ]+)")

# attribute calls that force a device->host round-trip
SYNC_ATTRS = frozenset({"item", "asnumpy", "asscalar", "block_until_ready",
                        "device_get"})
# memory-introspection calls (PR 8): cheap-ish individually, but
# memory_stats() round-trips PjRt, live_arrays() walks every live buffer,
# and memory_analysis() XLA-compiles — none belong in a per-step dispatch
# body; sample at step boundaries via mxnet_tpu.memwatch instead
MEM_ATTRS = frozenset({"memory_stats", "memory_analysis", "live_arrays"})


class Finding:
    __slots__ = ("rule", "path", "line", "col", "context", "message")

    def __init__(self, rule, path, line, col, context, message):
        self.rule = rule
        self.path = path
        self.line = line
        self.col = col
        self.context = context
        self.message = message

    def as_dict(self):
        return {"rule": self.rule, "path": self.path, "line": self.line,
                "col": self.col, "context": self.context,
                "message": self.message}

    def render(self):
        loc = f"{self.path}:{self.line}:{self.col}"
        ctx = f" [{self.context}]" if self.context else ""
        return f"{loc}: {self.rule}: {self.message}{ctx}"


# ---------------------------------------------------------------------------
# scope / alias helpers
# ---------------------------------------------------------------------------
class _Scopes(ast.NodeVisitor):
    """Collect every function with a dotted qualname, its enclosing class,
    and module-level import aliases."""

    def __init__(self):
        self.functions = {}        # qualname -> FunctionDef
        self.func_class = {}       # qualname -> class name or None
        self.classes = {}          # class name -> ClassDef
        self.mod_aliases = {}      # local alias -> dotted module
        self.from_names = {}       # local name -> "module.attr"
        self._stack = []           # (kind, name)

    # -- imports ----------------------------------------------------------
    def visit_Import(self, node):
        for a in node.names:
            self.mod_aliases[a.asname or a.name.split(".")[0]] = a.name
        self.generic_visit(node)

    def visit_ImportFrom(self, node):
        if node.module:
            for a in node.names:
                self.from_names[a.asname or a.name] = \
                    f"{node.module}.{a.name}"
        self.generic_visit(node)

    # -- scopes -----------------------------------------------------------
    def _qual(self, name):
        return ".".join([n for _k, n in self._stack] + [name])

    def visit_ClassDef(self, node):
        self.classes.setdefault(node.name, node)
        self._stack.append(("class", node.name))
        self.generic_visit(node)
        self._stack.pop()

    def _visit_func(self, node):
        qual = self._qual(node.name)
        self.functions.setdefault(qual, node)
        cls = None
        for kind, name in reversed(self._stack):
            if kind == "class":
                cls = name
                break
        self.func_class.setdefault(qual, cls)
        self._stack.append(("func", node.name))
        self.generic_visit(node)
        self._stack.pop()

    visit_FunctionDef = _visit_func
    visit_AsyncFunctionDef = _visit_func


def _call_name(node):
    """('name', n) for foo(...), ('self', m) for self.m(...), ('attr', m)
    for anything_else.m(...), else None."""
    if not isinstance(node, ast.Call):
        return None
    f = node.func
    if isinstance(f, ast.Name):
        return ("name", f.id)
    if isinstance(f, ast.Attribute):
        if isinstance(f.value, ast.Name) and f.value.id == "self":
            return ("self", f.attr)
        return ("attr", f.attr)
    return None


def _is_module_call(node, scopes, module, attr):
    """Is `node` a Call of <module>.<attr> under any local alias (including
    `from module import attr [as x]`)?"""
    if not isinstance(node, ast.Call):
        return False
    f = node.func
    if isinstance(f, ast.Attribute) and f.attr == attr and \
            isinstance(f.value, ast.Name):
        mod = scopes.mod_aliases.get(f.value.id)
        return mod == module or (mod or "").startswith(module + ".")
    if isinstance(f, ast.Name):
        return scopes.from_names.get(f.id) == f"{module}.{attr}"
    return False


def _docstring_nodes(nodes):
    """The Constant nodes that are documentation, not use-sites."""
    out = set()
    for node in nodes:
        if isinstance(node, (ast.Module, ast.ClassDef, ast.FunctionDef,
                             ast.AsyncFunctionDef)):
            body = node.body
            if body and isinstance(body[0], ast.Expr) and \
                    isinstance(body[0].value, ast.Constant) and \
                    isinstance(body[0].value.value, str):
                out.add(id(body[0].value))
    return out


# ---------------------------------------------------------------------------
# per-file analysis
# ---------------------------------------------------------------------------
class FileLint:
    def __init__(self, abspath, relpath, text, env_registry, hot_entries,
                 active_rules, jax_free_entries=None, pass_entries=None):
        self.path = relpath
        self.text = text
        self.lines = text.splitlines()
        self.env_registry = env_registry
        self.hot_entries = hot_entries
        self.jax_free = (jax_free_entries if jax_free_entries is not None
                         else JAX_FREE_ENTRIES)
        self.pass_entries = (pass_entries if pass_entries is not None
                             else PASS_DISPATCH_ENTRIES)
        self.active = active_rules
        self.findings = []
        self.suppressed = 0
        self.tree = None
        self.comments = {}        # line -> comment text
        self.suppress_lines = {}  # line -> set of rule names
        try:
            self.tree = ast.parse(text, filename=relpath)
        except SyntaxError as e:
            self._emit("syntax-error", e.lineno or 1, 0, None,
                       f"does not parse: {e.msg}")
            return
        self._scan_comments()
        self.scopes = _Scopes()
        self.scopes.visit(self.tree)
        # one flat walk per file (and one per function, cached): the rule
        # passes share these instead of re-walking the tree ~7 times
        self.all_nodes = list(ast.walk(self.tree))
        self._fn_nodes = {}
        self.docstrings = _docstring_nodes(self.all_nodes)

    # -- plumbing ----------------------------------------------------------
    def _emit(self, rule, line, col, context, message):
        if rule not in self.active:
            return
        self.findings.append(
            Finding(rule, self.path, line, col, context or "", message))

    def _scan_comments(self):
        try:
            toks = tokenize.generate_tokens(io.StringIO(self.text).readline)
            for tok in toks:
                if tok.type != tokenize.COMMENT:
                    continue
                line = tok.start[0]
                self.comments[line] = tok.string
                m = _SUPPRESS.search(tok.string)
                if not m:
                    continue
                rules = [r.strip() for r in m.group(1).split(",")]
                rules = [r for r in rules if r]
                # each piece's first word is a rule name; trailing words in
                # a piece start the justification, and once a justification
                # has started, later comma-separated fragments belong to it
                # ("disable=hot-sync, staged input path" must not read
                # 'staged' as a rule).  A lone unknown word IS a finding —
                # a typo'd suppression must not silently do nothing.
                names = set()
                for i, r in enumerate(rules):
                    words = r.split()
                    name = words[0] if words else r
                    if name in RULES:
                        names.add(name)
                        if len(words) > 1:
                            break  # justification text begins here
                    elif i > 0 and len(words) > 1:
                        break      # multi-word fragment = justification
                    else:
                        self._emit("bad-suppression", line, tok.start[1],
                                   None,
                                   f"suppression names unknown rule "
                                   f"{name!r} (known: "
                                   f"{', '.join(sorted(RULES))})")
                own_line = tok.string.strip() == \
                    self.lines[line - 1].strip() if line <= len(self.lines) \
                    else False
                if not own_line:     # trailing comment: covers its line
                    self.suppress_lines.setdefault(line, set()).update(names)
                    continue
                # own-line comment: attaches to the next CODE line, skipping
                # blank lines and the justification's continuation comments
                target = line + 1
                while target <= len(self.lines):
                    stripped = self.lines[target - 1].strip()
                    if stripped and not stripped.startswith("#"):
                        break
                    target += 1
                self.suppress_lines.setdefault(target, set()).update(names)
        except tokenize.TokenizeError:
            pass

    def _nodes_in(self, fn):
        nodes = self._fn_nodes.get(id(fn))
        if nodes is None:
            nodes = self._fn_nodes[id(fn)] = list(ast.walk(fn))
        return nodes

    def _apply_suppressions(self):
        # findings are reported at a node's first line; a suppression on
        # that line (trailing comment) or alone on the line above (mapped
        # to the next line by _scan_comments) matches.
        # Dedupe first: a nested function's body is walked both as part of
        # its enclosing function and as its own scope entry, so one defect
        # can be emitted twice with different contexts — keep the first
        # (outermost) so the baseline needs exactly one entry per site.
        seen, unique = set(), []
        for f in self.findings:
            key = (f.rule, f.line, f.col, f.message)
            if key not in seen:
                seen.add(key)
                unique.append(f)
        kept = []
        for f in unique:
            if f.rule != "bad-suppression" and \
                    f.rule in self.suppress_lines.get(f.line, ()):
                self.suppressed += 1
            else:
                kept.append(f)
        self.findings = kept

    # -- driver ------------------------------------------------------------
    def run(self):
        if self.tree is None:
            return self.findings
        passes = (
            ("env-unregistered", self.rule_env_unregistered),
            ("wall-clock-duration", self.rule_wall_clock_duration),
            ("silent-except", self.rule_silent_except),
            ("signal-unsafe", self.rule_signal_unsafe),
            ("thread-shared-write", self.rule_thread_shared_write),
            # hot-sync + retrace-hazard share the reachability pass
            ("hot-sync", self.rule_hot_path),
            ("retrace-hazard", self.rule_static_argnums),
            ("jax-in-handler", self.rule_jax_free),
            ("pass-outside-pipeline", self.rule_pass_pipeline),
        )
        for rule, fn in passes:
            if rule in self.active or (
                    rule == "hot-sync" and "retrace-hazard" in self.active):
                fn()
        self._apply_suppressions()
        return self.findings

    # -- env-unregistered --------------------------------------------------
    def rule_env_unregistered(self):
        if self.env_registry is None:
            return
        if not any(self.path == p or self.path.startswith(p + "/")
                   for p in ENV_RULE_PREFIXES):
            return
        for node in self.all_nodes:
            if not (isinstance(node, ast.Constant)
                    and isinstance(node.value, str)):
                continue
            if id(node) in self.docstrings:
                continue
            if _ENV_NAME.match(node.value) and \
                    node.value not in self.env_registry:
                self._emit(
                    "env-unregistered", node.lineno, node.col_offset, None,
                    f"env var {node.value!r} is read/exported here but not "
                    f"registered in mxnet_tpu/env_vars.py ENV_VARS (add an "
                    f"entry with disposition + use-site)")

    # -- wall-clock-duration ----------------------------------------------
    def _is_wall_call(self, node):
        return _is_module_call(node, self.scopes, "time", "time")

    def rule_wall_clock_duration(self):
        # class-level: attrs assigned self.X = time.time() anywhere in the
        # class taint `time.time() - self.X` in every method
        class_wall_attrs = {}
        for qual, fn in self.scopes.functions.items():
            cls = self.scopes.func_class.get(qual)
            if cls is None:
                continue
            for node in self._nodes_in(fn):
                if isinstance(node, ast.Assign) and \
                        self._is_wall_call(node.value):
                    for t in node.targets:
                        if isinstance(t, ast.Attribute) and \
                                isinstance(t.value, ast.Name) and \
                                t.value.id == "self":
                            class_wall_attrs.setdefault(cls, set()).add(
                                t.attr)

        for qual, fn in self.scopes.functions.items():
            cls = self.scopes.func_class.get(qual)
            tainted = set()
            for node in self._nodes_in(fn):
                if isinstance(node, ast.Assign) and \
                        self._is_wall_call(node.value):
                    for t in node.targets:
                        if isinstance(t, ast.Name):
                            tainted.add(t.id)

            def _wall(expr):
                if self._is_wall_call(expr):
                    return True
                if isinstance(expr, ast.Name) and expr.id in tainted:
                    return True
                if isinstance(expr, ast.Attribute) and \
                        isinstance(expr.value, ast.Name) and \
                        expr.value.id == "self" and \
                        expr.attr in class_wall_attrs.get(cls, ()):
                    return True
                return False

            for node in self._nodes_in(fn):
                if isinstance(node, ast.BinOp) and \
                        isinstance(node.op, ast.Sub) and \
                        _wall(node.left) and _wall(node.right):
                    self._emit(
                        "wall-clock-duration", node.lineno, node.col_offset,
                        qual,
                        "duration from two time.time() reads — wall clock "
                        "can step (NTP) and gave negative samples/sec; use "
                        "time.perf_counter() (keep time.time() only for "
                        "cross-process wall stamps)")

    # -- silent-except -----------------------------------------------------
    def _is_broad(self, handler):
        t = handler.type
        if t is None:
            return True
        names = []
        if isinstance(t, ast.Tuple):
            names = [getattr(e, "id", getattr(e, "attr", "")) for e
                     in t.elts]
        else:
            names = [getattr(t, "id", getattr(t, "attr", ""))]
        return any(n in ("Exception", "BaseException") for n in names)

    def rule_silent_except(self):
        for node in self.all_nodes:
            if not isinstance(node, ast.Try):
                continue
            for handler in node.handlers:
                if not all(isinstance(s, ast.Pass) for s in handler.body):
                    continue
                if not self._is_broad(handler):
                    continue
                last = max(s.lineno for s in handler.body)
                if any(ln in self.comments
                       for ln in range(handler.lineno, last + 1)):
                    continue  # justified in place
                self._emit(
                    "silent-except", handler.lineno, handler.col_offset,
                    None,
                    "broad except swallowed with bare pass — narrow the "
                    "exception type, record via telemetry, or add a "
                    "justification comment")

    # -- signal-unsafe -----------------------------------------------------
    def rule_signal_unsafe(self):
        handlers = []
        for node in self.all_nodes:
            if _is_module_call(node, self.scopes, "signal", "signal") and \
                    len(node.args) >= 2:
                h = node.args[1]
                if isinstance(h, ast.Name):
                    handlers.append(h.id)
        if not handlers:
            return
        for qual, fn in self.scopes.functions.items():
            if fn.name not in handlers:
                continue
            for node in self._nodes_in(fn):
                if isinstance(node, (ast.Import, ast.ImportFrom)):
                    self._emit(
                        "signal-unsafe", node.lineno, node.col_offset, qual,
                        "import inside a registered signal handler — the "
                        "import machinery takes a lock the interrupted "
                        "thread may hold; use sys.modules.get() for "
                        "already-imported modules")
                elif isinstance(node, ast.Call):
                    cn = _call_name(node)
                    if cn and cn[0] == "name" and cn[1] == "__import__":
                        self._emit("signal-unsafe", node.lineno,
                                   node.col_offset, qual,
                                   "__import__ inside a signal handler")
                    elif _is_module_call(node, self.scopes, "importlib",
                                         "import_module"):
                        self._emit("signal-unsafe", node.lineno,
                                   node.col_offset, qual,
                                   "importlib.import_module inside a "
                                   "signal handler")
                    elif cn and cn[0] == "name" and cn[1] == "open":
                        self._emit("signal-unsafe", node.lineno,
                                   node.col_offset, qual,
                                   "open() inside a signal handler — file "
                                   "IO can block/allocate at an arbitrary "
                                   "interruption point")
                    elif cn and cn[0] == "attr" and cn[1] == "acquire":
                        self._emit("signal-unsafe", node.lineno,
                                   node.col_offset, qual,
                                   "lock acquire inside a signal handler — "
                                   "deadlocks when the interrupted thread "
                                   "holds the lock")

    # -- thread-shared-write ----------------------------------------------
    def _lock_attrs(self, cls_node):
        locks = set()
        for node in ast.walk(cls_node):
            if isinstance(node, ast.Assign):
                val = node.value
                is_lock = (
                    isinstance(val, ast.Call)
                    and isinstance(val.func, ast.Attribute)
                    and val.func.attr in ("Lock", "RLock", "Condition"))
                for t in node.targets:
                    if isinstance(t, ast.Attribute) and \
                            isinstance(t.value, ast.Name) and \
                            t.value.id == "self":
                        if is_lock or "lock" in t.attr.lower():
                            locks.add(t.attr)
        return locks

    def _self_writes(self, fn, lock_attrs):
        """[(attr, frozenset(held locks), lineno)] for self.X assignments
        lexically inside `fn` (nested defs included: closures over self)."""
        out = []

        def walk(node, held):
            if isinstance(node, ast.With):
                extra = set()
                for item in node.items:
                    ce = item.context_expr
                    if isinstance(ce, ast.Attribute) and \
                            isinstance(ce.value, ast.Name) and \
                            ce.value.id == "self" and ce.attr in lock_attrs:
                        extra.add(ce.attr)
                for child in node.body:
                    walk(child, held | extra)
                return
            if isinstance(node, (ast.Assign, ast.AugAssign)):
                targets = node.targets if isinstance(node, ast.Assign) \
                    else [node.target]
                for t in targets:
                    if isinstance(t, ast.Attribute) and \
                            isinstance(t.value, ast.Name) and \
                            t.value.id == "self":
                        out.append((t.attr, frozenset(held), node.lineno))
            for child in ast.iter_child_nodes(node):
                walk(child, held)

        for stmt in fn.body:
            walk(stmt, frozenset())
        return out

    def _worker_funcs(self, cls_name, cls_node, methods):
        """Qualnames of worker-side functions for a class: Thread targets
        plus `_produce` on _ThreadedIter subclasses, closed over self-call
        reachability within the class."""
        workers = set()
        bases = [getattr(b, "id", getattr(b, "attr", "")) for b
                 in cls_node.bases]
        if any("ThreadedIter" in b for b in bases) and \
                f"{cls_name}._produce" in methods:
            workers.add(f"{cls_name}._produce")
        for qual, fn in methods.items():
            for node in self._nodes_in(fn):
                if not (isinstance(node, ast.Call)
                        and _is_module_call(node, self.scopes, "threading",
                                            "Thread")):
                    continue
                for kw in node.keywords:
                    if kw.arg != "target":
                        continue
                    v = kw.value
                    if isinstance(v, ast.Attribute) and \
                            isinstance(v.value, ast.Name) and \
                            v.value.id == "self":
                        cand = f"{cls_name}.{v.attr}"
                        if cand in methods:
                            workers.add(cand)
                    elif isinstance(v, ast.Name):
                        # nested worker fn: its writes already count as
                        # part of the enclosing method's lexical extent,
                        # so mark the ENCLOSING method worker-side
                        workers.add(qual)
        # transitive: worker -> self.m() -> m is worker-side too
        changed = True
        while changed:
            changed = False
            for qual in list(workers):
                fn = methods.get(qual)
                if fn is None:
                    continue
                for node in self._nodes_in(fn):
                    cn = _call_name(node)
                    if cn and cn[0] == "self":
                        cand = f"{cls_name}.{cn[1]}"
                        if cand in methods and cand not in workers:
                            workers.add(cand)
                            changed = True
        return workers

    def rule_thread_shared_write(self):
        for cls_name, cls_node in self.scopes.classes.items():
            # direct methods only: a nested worker function's writes are
            # already covered by the lexical walk of its enclosing method —
            # listing it separately would count the same write on both
            # sides and fabricate a race with itself
            direct = {id(stmt) for stmt in cls_node.body
                      if isinstance(stmt, (ast.FunctionDef,
                                           ast.AsyncFunctionDef))}
            methods = {q: f for q, f in self.scopes.functions.items()
                       if self.scopes.func_class.get(q) == cls_name
                       and id(f) in direct}
            if not methods:
                continue
            workers = self._worker_funcs(cls_name, cls_node, methods)
            if not workers:
                continue
            lock_attrs = self._lock_attrs(cls_node)
            worker_writes = {}   # attr -> [(locks, line, qual)]
            consumer_writes = {}
            for qual, fn in methods.items():
                if qual.endswith(".__init__") and qual not in workers:
                    continue  # pre-thread-start writes are safe
                side = worker_writes if qual in workers else consumer_writes
                for attr, locks, line in self._self_writes(fn, lock_attrs):
                    side.setdefault(attr, []).append((locks, line, qual))
            for attr in sorted(set(worker_writes) & set(consumer_writes)):
                all_w = worker_writes[attr] + consumer_writes[attr]
                common = frozenset.intersection(
                    *[locks for locks, _l, _q in all_w]) if all_w else \
                    frozenset()
                if common:
                    continue  # every write holds a shared lock
                wl = worker_writes[attr][0]
                cl = consumer_writes[attr][0]
                self._emit(
                    "thread-shared-write", wl[1], 0, wl[2],
                    f"self.{attr} written by worker thread ({wl[2]} "
                    f"l.{wl[1]}) and consumer ({cl[2]} l.{cl[1]}) with no "
                    f"common lock — guard both writes with one lock or "
                    f"hand the value over a queue")

    # -- hot-path reachability (hot-sync + retrace-hazard part 1) ----------
    def _reachable_from(self, entries):
        seen = set()
        work = [q for q in entries if q in self.scopes.functions]
        while work:
            qual = work.pop()
            if qual in seen:
                continue
            seen.add(qual)
            fn = self.scopes.functions[qual]
            cls = self.scopes.func_class.get(qual)
            for node in self._nodes_in(fn):
                cn = _call_name(node)
                if not cn:
                    continue
                kind, name = cn
                cand = None
                if kind == "self" and cls:
                    cand = f"{cls}.{name}"
                elif kind == "name":
                    if f"{qual}.{name}" in self.scopes.functions:
                        cand = f"{qual}.{name}"      # nested helper
                    elif name in self.scopes.functions:
                        cand = name                  # module-level fn
                if cand in self.scopes.functions and cand not in seen:
                    work.append(cand)
        return seen

    def rule_hot_path(self):
        entries = self.hot_entries.get(self.path)
        if not entries:
            return
        for q in entries:
            if q not in self.scopes.functions:
                # a renamed/moved dispatch body must not silently turn the
                # flagship rule into a no-op for this file — fail loudly
                # so HOT_PATH_ENTRIES is updated alongside the refactor
                self._emit(
                    "stale-hot-entry", 1, 0, q,
                    f"hot-path entry point {q!r} (HOT_PATH_ENTRIES in "
                    f"tools/mxlint.py) does not resolve in this file — "
                    f"update the entry list to the renamed/moved per-step "
                    f"dispatch body")
        reach = self._reachable_from(entries)
        for qual in sorted(reach):
            fn = self.scopes.functions[qual]
            for node in self._nodes_in(fn):
                if not isinstance(node, ast.Call):
                    continue
                self._check_sync_call(node, qual)
                if _is_module_call(node, self.scopes, "jax", "jit"):
                    self._emit(
                        "retrace-hazard", node.lineno, node.col_offset,
                        qual,
                        "jax.jit constructed inside a per-step hot path — "
                        "every construction recompiles; hoist it or cache "
                        "the jitted callable by signature")

    def _check_sync_call(self, node, qual):
        f = node.func
        if isinstance(f, ast.Attribute) and f.attr in SYNC_ATTRS:
            # np.asarray-style module funcs handled below; any-receiver
            # method syncs (x.item(), x.block_until_ready()) land here
            self._emit(
                "hot-sync", node.lineno, node.col_offset, qual,
                f".{f.attr}() forces a device->host sync inside the "
                f"per-step dispatch path — defer readback (AsyncLoss) or "
                f"move it off the hot path")
            return
        if isinstance(f, ast.Attribute) and f.attr in MEM_ATTRS:
            # any-receiver memory probes (dev.memory_stats(),
            # compiled.memory_analysis()) and jax.live_arrays()
            self._emit(
                "hot-sync", node.lineno, node.col_offset, qual,
                f".{f.attr}() polls memory inside the per-step dispatch "
                f"path — sample at step boundaries via mxnet_tpu.memwatch "
                f"(on_step/on_checkpoint) instead")
            return
        if _is_module_call(node, self.scopes, "jax", "live_arrays"):
            # from-import form: `from jax import live_arrays`
            self._emit(
                "hot-sync", node.lineno, node.col_offset, qual,
                "jax.live_arrays() walks every live buffer inside the "
                "per-step dispatch path — sample at step boundaries via "
                "mxnet_tpu.memwatch instead")
            return
        if _is_module_call(node, self.scopes, "numpy", "asarray"):
            arg = node.args[0] if node.args else None
            if isinstance(arg, (ast.List, ast.Tuple, ast.Dict, ast.ListComp,
                                ast.DictComp, ast.GeneratorExp,
                                ast.Constant)):
                return  # building from host literals, not reading a device
            self._emit(
                "hot-sync", node.lineno, node.col_offset, qual,
                "np.asarray() on a (possibly device) array inside the "
                "per-step dispatch path blocks until the value is on host")
            return
        if isinstance(f, ast.Name) and f.id == "float":
            arg = node.args[0] if node.args else None
            if arg is None or isinstance(arg, ast.Constant):
                return
            self._emit(
                "hot-sync", node.lineno, node.col_offset, qual,
                "float() inside the per-step dispatch path — on a device "
                "value this is a hidden blocking readback")

    # -- jax-in-handler: jax-free reachability ----------------------------
    def _is_jax_module(self, name) -> bool:
        return name == "jax" or (name or "").startswith("jax.")

    def rule_jax_free(self):
        entries = self.jax_free.get(self.path)
        if not entries:
            return
        for q in entries:
            if q not in self.scopes.functions:
                self._emit(
                    "stale-hot-entry", 1, 0, q,
                    f"jax-free entry point {q!r} (JAX_FREE_ENTRIES in "
                    f"tools/mxlint.py) does not resolve in this file — "
                    f"update the entry list to the renamed/moved handler")
        # aliases bound to the jax module anywhere in the file: a
        # module-level `import jax as j` used inside the handler is the
        # same defect as an inline import
        jax_aliases = {alias for alias, mod in self.scopes.mod_aliases.items()
                       if self._is_jax_module(mod)}
        jax_names = {name for name, target in self.scopes.from_names.items()
                     if self._is_jax_module(target.rsplit(".", 1)[0])
                     or target.startswith("jax.")}
        reach = self._reachable_from(entries)
        for qual in sorted(reach):
            fn = self.scopes.functions[qual]
            for node in self._nodes_in(fn):
                if isinstance(node, ast.Import):
                    for a in node.names:
                        if self._is_jax_module(a.name):
                            self._emit(
                                "jax-in-handler", node.lineno,
                                node.col_offset, qual,
                                "jax import inside a jax-free handler — "
                                "the metrics endpoint must serve the "
                                "recorder's rollups only (no runtime "
                                "init, no device sync, from a scrape)")
                elif isinstance(node, ast.ImportFrom):
                    if self._is_jax_module(node.module or ""):
                        self._emit(
                            "jax-in-handler", node.lineno, node.col_offset,
                            qual,
                            "jax import inside a jax-free handler — "
                            "serve the recorder's rollups only")
                elif isinstance(node, ast.Name) and \
                        (node.id in jax_aliases or node.id in jax_names):
                    self._emit(
                        "jax-in-handler", node.lineno, node.col_offset,
                        qual,
                        f"{node.id!r} resolves to jax — a jax-free "
                        "handler must not reach the runtime (serve the "
                        "recorder's rollups only)")
                elif isinstance(node, ast.Call):
                    # the handler also gets the full hot-sync readback
                    # checks: .item()/np.asarray()/memory_stats() from a
                    # scrape thread stalls the training loop just as a
                    # per-step sync would
                    self._check_sync_call(node, qual)

    # -- pass-outside-pipeline --------------------------------------------
    def rule_pass_pipeline(self):
        """The op-dispatch body may consult module-global trace-rewrite
        state ONLY through the pass-hook protocol: the one
        ``_pass_hooks._OP_HOOKS`` read (plus explicitly allowed
        non-pass plumbing).  Any other ``<module>._underscore`` load in
        the body is a pass smuggled around the pipeline — invisible to
        the pipeline fingerprint, so two different traced programs
        would carry one name."""
        cfg = self.pass_entries.get(self.path)
        if not cfg:
            return
        qual = cfg["function"]
        fn = self.scopes.functions.get(qual)
        if fn is None:
            # a renamed/moved dispatch body must not silently turn the
            # rule into a no-op — same contract as stale-hot-entry
            self._emit(
                "pass-outside-pipeline", 1, 0, qual,
                f"configured dispatch body {qual!r} (PASS_DISPATCH_ENTRIES "
                f"in tools/mxlint.py) does not resolve in this file — "
                f"update the entry to the renamed/moved dispatch point")
            return
        hook_mod = cfg.get("hook_module")
        allowed = {tuple(a) for a in cfg.get("allowed", ())}
        # names bound by ANY import in the file (incl. `from .. import
        # autograd` inside functions): only module aliases are candidate
        # global-state carriers — locals like `x._data` are not
        imported = set()
        for n in self.all_nodes:
            if isinstance(n, (ast.Import, ast.ImportFrom)):
                for a in n.names:
                    imported.add((a.asname or a.name).split(".")[0])
        saw_hook = False
        for node in self._nodes_in(fn):
            if not (isinstance(node, ast.Attribute)
                    and isinstance(node.ctx, ast.Load)
                    and isinstance(node.value, ast.Name)
                    and node.value.id in imported
                    and node.attr.startswith("_")):
                continue
            pair = (node.value.id, node.attr)
            if pair in allowed:
                if pair[0] == hook_mod:
                    saw_hook = True
                continue
            self._emit(
                "pass-outside-pipeline", node.lineno, node.col_offset,
                qual,
                f"dispatch body consults {pair[0]}.{pair[1]} — "
                f"module-global pass state outside the pass-hook "
                f"protocol; register a GraphPass (passes/pipeline.py) "
                f"whose scope pushes an OpHook, and let the one "
                f"{hook_mod}._OP_HOOKS read carry it")
        if hook_mod and not saw_hook:
            self._emit(
                "pass-outside-pipeline", fn.lineno, fn.col_offset, qual,
                f"dispatch body no longer consults "
                f"{hook_mod}._OP_HOOKS — the pass pipeline is "
                f"disconnected from dispatch (or the consultation moved: "
                f"update PASS_DISPATCH_ENTRIES in tools/mxlint.py)")

    # -- retrace-hazard part 2: unhashable static args --------------------
    def rule_static_argnums(self):
        jitted = {}  # name -> static positions
        for node in self.all_nodes:
            if isinstance(node, ast.Assign) and \
                    _is_module_call(node.value, self.scopes, "jax", "jit"):
                positions = []
                for kw in node.value.keywords:
                    if kw.arg != "static_argnums":
                        continue
                    v = kw.value
                    elts = v.elts if isinstance(v, ast.Tuple) else [v]
                    for e in elts:
                        if isinstance(e, ast.Constant) and \
                                isinstance(e.value, int):
                            positions.append(e.value)
                if positions:
                    for t in node.targets:
                        if isinstance(t, ast.Name):
                            jitted[t.id] = positions
        if not jitted:
            return
        for node in self.all_nodes:
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Name)
                    and node.func.id in jitted):
                continue
            for pos in jitted[node.func.id]:
                if pos < len(node.args) and isinstance(
                        node.args[pos], (ast.List, ast.Dict, ast.Set,
                                         ast.ListComp, ast.DictComp,
                                         ast.SetComp)):
                    self._emit(
                        "retrace-hazard", node.lineno, node.col_offset,
                        None,
                        f"unhashable literal passed in static_argnums "
                        f"position {pos} of jitted "
                        f"{node.func.id!r} — static arguments must be "
                        f"hashable (tuple, not list/dict/set)")


# ---------------------------------------------------------------------------
# project driver
# ---------------------------------------------------------------------------
def load_env_registry(root):
    """ENV_VARS keys, parsed statically from mxnet_tpu/env_vars.py (mxlint
    never imports the package — stdlib-only, importable-tree-independent)."""
    path = os.path.join(root, "mxnet_tpu", "env_vars.py")
    try:
        with open(path, encoding="utf-8") as f:
            tree = ast.parse(f.read())
    except (OSError, SyntaxError):
        return None
    for node in ast.walk(tree):
        if isinstance(node, ast.Assign):
            for t in node.targets:
                if isinstance(t, ast.Name) and t.id == "ENV_VARS" and \
                        isinstance(node.value, ast.Dict):
                    return {k.value for k in node.value.keys
                            if isinstance(k, ast.Constant)
                            and isinstance(k.value, str)}
        if isinstance(node, ast.AnnAssign) and \
                isinstance(node.target, ast.Name) and \
                node.target.id == "ENV_VARS" and \
                isinstance(node.value, ast.Dict):
            return {k.value for k in node.value.keys
                    if isinstance(k, ast.Constant)
                    and isinstance(k.value, str)}
    return None


def iter_py_files(paths, root):
    for p in paths:
        ap = p if os.path.isabs(p) else os.path.join(root, p)
        if not os.path.exists(ap):
            raise ValueError(f"no such file or directory: {p}")
        if os.path.isfile(ap) and ap.endswith(".py"):
            yield ap
        elif os.path.isdir(ap):
            for dirpath, dirnames, filenames in os.walk(ap):
                dirnames[:] = [d for d in dirnames
                               if d != "__pycache__"
                               and not d.startswith(".")]
                for fname in sorted(filenames):
                    if fname.endswith(".py"):
                        yield os.path.join(dirpath, fname)


def _rel(path, root):
    ap = os.path.abspath(path)
    r = os.path.abspath(root)
    if ap.startswith(r + os.sep):
        return os.path.relpath(ap, r).replace(os.sep, "/")
    return ap.replace(os.sep, "/")


def run_lint(paths=None, root=None, rules=None, hot_entries=None,
             env_registry=None, jax_free_entries=None, pass_entries=None):
    """Analyze `paths` (files or dirs); returns (findings, stats).

    `rules`: iterable restricting which rules run (default: all).
    `hot_entries`/`env_registry`/`jax_free_entries`/`pass_entries`:
    overrides for tests/fixtures.
    """
    root = root or REPO
    paths = list(paths) if paths else list(DEFAULT_PATHS)
    active = set(rules) if rules else set(RULES)
    unknown = active - set(RULES)
    if unknown:
        raise ValueError(f"unknown rule(s): {', '.join(sorted(unknown))}")
    # meta rules always run: suppressions must be spellable, files
    # parsable, configured entry points resolvable
    active |= {"bad-suppression", "syntax-error", "stale-hot-entry"}
    registry_missing = False
    if env_registry is None:
        env_registry = load_env_registry(root)
        registry_missing = env_registry is None and \
            "env-unregistered" in active
    entries = hot_entries if hot_entries is not None else HOT_PATH_ENTRIES
    jax_free = (jax_free_entries if jax_free_entries is not None
                else JAX_FREE_ENTRIES)
    findings, nfiles, suppressed = [], 0, 0
    for ap in iter_py_files(paths, root):
        rel = _rel(ap, root)
        try:
            with open(ap, encoding="utf-8", errors="replace") as f:
                text = f.read()
        except OSError as e:
            raise ValueError(f"cannot read {ap}: {e}")
        nfiles += 1
        fl = FileLint(ap, rel, text, env_registry, entries, active,
                      jax_free_entries=jax_free, pass_entries=pass_entries)
        findings.extend(fl.run())
        suppressed += fl.suppressed
    findings.sort(key=lambda f: (f.path, f.line, f.col, f.rule))
    return findings, {"files": nfiles, "suppressed": suppressed,
                      "active_rules": sorted(active),
                      "env_registry_missing": registry_missing}


# ---------------------------------------------------------------------------
# baseline
# ---------------------------------------------------------------------------
def _fingerprint(finding, root):
    """Line-number-independent identity: rule + path + context + the
    stripped source line (survives unrelated edits above the site)."""
    text = ""
    ap = os.path.join(root, finding.path)
    try:
        with open(ap, encoding="utf-8", errors="replace") as f:
            lines = f.read().splitlines()
        if 0 < finding.line <= len(lines):
            text = lines[finding.line - 1].strip()
    except OSError:
        pass
    return {"rule": finding.rule, "path": finding.path,
            "context": finding.context, "line_text": text}


def load_baseline(path):
    try:
        with open(path, encoding="utf-8") as f:
            data = json.load(f)
    except FileNotFoundError:
        return []
    except (OSError, ValueError) as e:
        raise ValueError(f"baseline {path} unreadable: {e}")
    entries = data.get("entries", []) if isinstance(data, dict) else data
    for e in entries:
        if not isinstance(e, dict) or "rule" not in e or "path" not in e:
            raise ValueError(f"baseline {path}: malformed entry {e!r}")
    return entries


def apply_baseline(findings, entries, root):
    """Split findings into (new, baselined); also returns stale baseline
    entries that matched nothing (candidates for removal)."""
    remaining = list(entries)
    new, baselined = [], []
    for f in findings:
        fp = _fingerprint(f, root)
        hit = None
        for e in remaining:
            if (e["rule"] == fp["rule"] and e["path"] == fp["path"]
                    and e.get("context", "") == fp["context"]
                    and e.get("line_text", "").strip() == fp["line_text"]):
                hit = e
                break
        if hit is not None:
            remaining.remove(hit)
            baselined.append(f)
        else:
            new.append(f)
    return new, baselined, remaining


def write_baseline(path, findings, root, old_entries, extra_entries=()):
    """Regenerate the baseline from current findings, carrying forward
    justifications for entries that still match; new entries are marked
    UNREVIEWED and must be justified by hand before review.
    `extra_entries` pass through verbatim (entries of rules the current
    invocation didn't run and therefore cannot re-derive)."""
    old = {(e["rule"], e["path"], e.get("context", ""),
            e.get("line_text", "").strip()): e.get("justification", "")
           for e in old_entries}
    entries = list(extra_entries)
    for f in findings:
        fp = _fingerprint(f, root)
        key = (fp["rule"], fp["path"], fp["context"], fp["line_text"])
        fp["justification"] = old.get(key) or f"UNREVIEWED: {f.message}"
        entries.append(fp)
    with open(path, "w", encoding="utf-8") as f:
        json.dump({"version": 1, "entries": entries}, f, indent=1)
        f.write("\n")
    return entries


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------
def main(argv=None):
    ap = argparse.ArgumentParser(
        prog="mxlint",
        description="project AST lint + lightweight race detector "
                    "(exit 0 clean / 2 usage / 3 findings)")
    ap.add_argument("paths", nargs="*",
                    help=f"files/dirs to scan (default: "
                         f"{' '.join(DEFAULT_PATHS)} under the repo root)")
    ap.add_argument("--json", action="store_true", dest="as_json",
                    help="machine-readable report on stdout")
    ap.add_argument("--rules", default=None,
                    help="comma-separated subset of rules to run")
    ap.add_argument("--list-rules", action="store_true",
                    help="print the rule catalog and exit")
    ap.add_argument("--baseline", default=None,
                    help=f"baseline file (default {DEFAULT_BASELINE})")
    ap.add_argument("--no-baseline", action="store_true",
                    help="ignore the baseline (report legacy findings too)")
    ap.add_argument("--write-baseline", action="store_true",
                    help="rewrite the baseline from current findings "
                         "(carries forward existing justifications)")
    ap.add_argument("--root", default=REPO, help=argparse.SUPPRESS)
    args = ap.parse_args(argv)

    if args.list_rules:
        for name in sorted(RULES):
            print(f"{name:22s} {RULES[name]}")
        return 0

    rules = None
    if args.rules:
        rules = [r.strip() for r in args.rules.split(",") if r.strip()]
    t0 = time.perf_counter()
    try:
        findings, stats = run_lint(args.paths or None, root=args.root,
                                   rules=rules)
    except ValueError as e:
        print(f"mxlint: {e}", file=sys.stderr)
        return 2

    baseline_path = args.baseline or DEFAULT_BASELINE
    baselined, stale = [], []
    if args.write_baseline:
        try:
            # a malformed baseline must be a loud usage error here too —
            # silently regenerating would discard every reviewed
            # justification in the file being "recovered"
            old = load_baseline(baseline_path)
        except ValueError as e:
            print(f"mxlint: {e}", file=sys.stderr)
            return 2
        # entries for rules that did NOT run this invocation (--rules
        # subset) are out of scope: carry them through untouched instead
        # of deleting them along with their justifications
        keep = [e for e in old if e["rule"] not in stats["active_rules"]]
        entries = write_baseline(baseline_path, findings, args.root, old,
                                 extra_entries=keep)
        print(f"mxlint: wrote {len(entries)} baseline entries to "
              f"{baseline_path}", file=sys.stderr)
        return 0
    if not args.no_baseline:
        try:
            entries = load_baseline(baseline_path)
        except ValueError as e:
            print(f"mxlint: {e}", file=sys.stderr)
            return 2
        findings, baselined, stale = apply_baseline(findings, entries,
                                                    args.root)
        # an entry whose rule didn't run this invocation can't be judged
        # stale — only report entries the active rules had a shot at
        stale = [e for e in stale if e["rule"] in stats["active_rules"]]

    elapsed = time.perf_counter() - t0
    if stats.get("env_registry_missing"):
        print("mxlint: mxnet_tpu/env_vars.py not found/parsable under "
              f"{args.root} — env-unregistered rule skipped",
              file=sys.stderr)
    counts = {}
    for f in findings:
        counts[f.rule] = counts.get(f.rule, 0) + 1
    if args.as_json:
        print(json.dumps({
            "version": 1,
            "root": args.root,
            "files_scanned": stats["files"],
            "elapsed_s": round(elapsed, 3),
            "counts": counts,
            "findings": [f.as_dict() for f in findings],
            "suppressed": stats["suppressed"],
            "baselined": len(baselined),
            "stale_baseline": stale,
        }, indent=1))
    else:
        for f in findings:
            print(f.render())
        for e in stale:
            print(f"mxlint: stale baseline entry (no longer fires): "
                  f"{e['rule']} {e['path']} [{e.get('context', '')}]",
                  file=sys.stderr)
        print(f"mxlint: {len(findings)} finding(s) in "
              f"{stats['files']} files ({elapsed:.2f}s; "
              f"{stats['suppressed']} suppressed inline, "
              f"{len(baselined)} baselined)", file=sys.stderr)
    return 3 if findings else 0


if __name__ == "__main__":
    sys.exit(main())
