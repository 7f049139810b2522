"""fedlint rules FL000-FL007 (rule catalog in DESIGN.md §14 and §16).

Each rule is ``check_flNNN(project) -> list[Finding]``.  Rules locate the
repo anchors STRUCTURALLY (the ``SALT_*`` registry is wherever module-level
``SALT_*`` int constants live; ``FedConfig``/``fingerprint``/
``EXECUTION_ONLY`` are found by name anywhere in the tree; thread targets
are wherever ``threading.Thread(target=...)``/``.submit(...)`` appear), so
the same rules run unchanged over the shipped ``src/repro`` tree and over
the seeded fixture trees in ``tests/fedlint_fixtures/``.

FL003/FL004 are interprocedural within a module: the ``CallGraph`` in
``core.py`` follows bare-name and ``self.method(...)`` calls, so a donated
binding read inside a helper called after the jitted call, or a traced
value concretized two helpers deep, still reports at the offending call
site.  Calls through any other object boundary (``self.stager.stage(...)``)
intentionally stop propagation — that is the blessed-entry-point contract.
"""
from __future__ import annotations

import ast
from typing import Optional

from tools.fedlint.core import (CallGraph, Finding, Module, Project,
                                assigned_names, dotted_name, int_tuple,
                                last_segment)

# The canonical salt slot in every SeedSequence entropy list:
# [seed, round-slot, SALT, ...extra discriminators].
SALT_INDEX = 2

# fed/sharded.py round-program factories and the donated positions of the
# callables they RETURN (FL003 follows the returned callee, not the factory).
DONATING_FACTORIES = {
    "make_packed_kd_round": (0, 1, 2, 3),
    "make_leader_kd_round": (2, 3),
    "make_packed_baseline_round": (0, 1),
    "make_packed_teacher_phase": (0, 1),
}

# Canonical between-round state (the (K, ...) stacks / global params) that
# must NEVER sit in a donated position: the async checkpoint writer and the
# next round's gather still read these buffers (DESIGN.md §13).
CANONICAL_NAMES = {
    "tp_k", "ts_k", "sp_global", "global_student", "global_p",
    "global_params", "teachers", "t_opts",
}

# Python-side casts/escapes that force a concrete value out of a tracer.
CONCRETIZERS = {"float", "int", "bool"}
CONCRETIZING_METHODS = {"item", "tolist", "tobytes"}

# Array constructors whose comprehension-shaped argument bakes a Python
# value into the array SHAPE (FL005).
SHAPE_CONSTRUCTORS = {"asarray", "array", "stack", "concatenate"}

# FL006: attribute types that are their own synchronization (writing through
# them is an immutable-handoff, not a shared mutation) and the lock types
# whose ``with self.<lock>:`` blocks count as guarded.
THREAD_SAFE_TYPES = {
    "Queue", "SimpleQueue", "LifoQueue", "PriorityQueue", "Lock", "RLock",
    "Event", "Condition", "Semaphore", "BoundedSemaphore", "Barrier",
}
LOCK_TYPES = {"Lock", "RLock"}

# FL006: method calls that mutate their receiver in place (list/set/dict/
# queue mutators).  ``self.attr.append(...)`` is a write to ``attr``.
MUTATING_METHODS = {
    "append", "extend", "insert", "add", "remove", "discard", "clear",
    "update", "setdefault", "pop", "popitem", "put", "put_nowait",
    "get", "get_nowait", "task_done",
}

# FL006: attribute names exempted by construction (none today — the queue/
# lock structural blessing covers the shipped tree; extend with care).
FL006_BLESSED: frozenset[str] = frozenset()

# FL007: the steady-round compute spans (perf.span names) that must never
# block, and the call bases blessed to appear inside them (instrumentation
# and the jitter harness are the sanctioned entry points).
HOT_SPANS = {"stage", "compute", "aggregate"}
FL007_BLESSED_BASES = ("perf", "guards")


# =========================================================== FL000: pragmas
def check_fl000(project: Project) -> list[Finding]:
    """Every ``# fedlint: allow=...`` pragma must carry a `` -- reason``
    suffix.  FL000 findings are exempt from the allowlist (core.run_rules):
    a pragma cannot vouch for itself."""
    findings: list[Finding] = []
    for m in project.modules:
        for line, (rules, reason) in sorted(m.pragmas.items()):
            if reason is None:
                findings.append(Finding(
                    "FL000", m.rel, line,
                    f"bare fedlint pragma (allow={','.join(sorted(rules))}):"
                    " every allowlist entry needs a ' -- reason' suffix"
                    " saying why the rule is waived here (auditable"
                    " allowlists, DESIGN.md §16)"))
    return findings


# =========================================================== FL001: streams
def _salt_registry(project: Project) -> tuple[dict, list[Finding]]:
    """Module-level ``SALT_* = <int>`` constants across the project, plus
    duplicate-value findings (two salts with one value = one stream)."""
    registry: dict[str, tuple[int, str, int]] = {}
    findings: list[Finding] = []
    for m in project.modules:
        for node in m.tree.body:
            if not (isinstance(node, ast.Assign) and len(node.targets) == 1):
                continue
            t = node.targets[0]
            if not (isinstance(t, ast.Name) and t.id.startswith("SALT_")):
                continue
            if not (isinstance(node.value, ast.Constant)
                    and isinstance(node.value.value, int)):
                findings.append(Finding(
                    "FL001", m.rel, node.lineno,
                    f"salt constant {t.id} must be an int literal "
                    "(registry must be statically checkable)"))
                continue
            val = node.value.value
            for name, (v, rel, line) in registry.items():
                if v == val:
                    findings.append(Finding(
                        "FL001", m.rel, node.lineno,
                        f"salt {t.id} duplicates the value 0x{val:X} of "
                        f"{name} ({rel}:{line}) — every salt must be a "
                        "distinct stream"))
            registry[t.id] = (val, m.rel, node.lineno)
    return registry, findings


def check_fl001(project: Project) -> list[Finding]:
    registry, findings = _salt_registry(project)
    shapes: dict[str, tuple[int, str, int]] = {}
    for m in project.modules:
        for node in ast.walk(m.tree):
            if not (isinstance(node, ast.Call)
                    and last_segment(node.func) == "SeedSequence"):
                continue
            if not node.args or not isinstance(node.args[0], ast.List):
                findings.append(Finding(
                    "FL001", m.rel, node.lineno,
                    "SeedSequence entropy must be a list literal so the "
                    "salt slot is statically checkable"))
                continue
            elts = node.args[0].elts
            if len(elts) <= SALT_INDEX:
                findings.append(Finding(
                    "FL001", m.rel, node.lineno,
                    f"unsalted stream (entropy length {len(elts)}): every "
                    "stream must carry a registered SALT_* constant at "
                    f"index {SALT_INDEX}"))
                continue
            salt_name = last_segment(elts[SALT_INDEX])
            if salt_name is None or salt_name not in registry:
                findings.append(Finding(
                    "FL001", m.rel, node.lineno,
                    f"entropy index {SALT_INDEX} must be a registered "
                    f"SALT_* constant, got {m.src_of(elts[SALT_INDEX])!r} "
                    "(magic salts defeat the stream registry)"))
                continue
            n = len(elts)
            if salt_name in shapes and shapes[salt_name][0] != n:
                first_n, rel, line = shapes[salt_name]
                findings.append(Finding(
                    "FL001", m.rel, node.lineno,
                    f"{salt_name} used with entropy length {n} but length "
                    f"{first_n} at {rel}:{line} — one tuple shape per salt "
                    "(shape is part of the stream identity)"))
            shapes.setdefault(salt_name, (n, m.rel, node.lineno))
    return findings


# ======================================================= FL002: fingerprint
def _find_class(project: Project, name: str
                ) -> Optional[tuple[Module, ast.ClassDef]]:
    for m in project.modules:
        for node in ast.walk(m.tree):
            if isinstance(node, ast.ClassDef) and node.name == name:
                return m, node
    return None


def _find_function(project: Project, name: str
                   ) -> Optional[tuple[Module, ast.FunctionDef]]:
    for m in project.modules:
        for node in ast.walk(m.tree):
            if isinstance(node, ast.FunctionDef) and node.name == name:
                return m, node
    return None


def _str_elts(node: ast.AST) -> Optional[set[str]]:
    """String elements of a set/frozenset/tuple/list literal (or a
    ``frozenset({...})`` call)."""
    if isinstance(node, ast.Call) and last_segment(node.func) in (
            "frozenset", "set") and node.args:
        return _str_elts(node.args[0])
    if isinstance(node, (ast.Set, ast.Tuple, ast.List)):
        out = set()
        for e in node.elts:
            if not (isinstance(e, ast.Constant) and isinstance(e.value, str)):
                return None
            out.add(e.value)
        return out
    return None


def check_fl002(project: Project) -> list[Finding]:
    cls = _find_class(project, "FedConfig")
    fn = _find_function(project, "fingerprint")
    if cls is None or fn is None:
        return []                      # nothing to check in this tree
    cfg_mod, cfg_cls = cls
    fp_mod, fp_fn = fn

    fields: dict[str, int] = {}
    for node in cfg_cls.body:
        if isinstance(node, ast.AnnAssign) and isinstance(node.target,
                                                          ast.Name):
            fields[node.target.id] = node.lineno

    fp_keys: set[str] = set()
    for node in ast.walk(fp_fn):
        if isinstance(node, ast.Dict):
            for k in node.keys:
                if isinstance(k, ast.Constant) and isinstance(k.value, str):
                    fp_keys.add(k.value)
        if isinstance(node, ast.Assign):
            for t in node.targets:
                if (isinstance(t, ast.Subscript)
                        and isinstance(t.slice, ast.Constant)
                        and isinstance(t.slice.value, str)):
                    fp_keys.add(t.slice.value)

    findings: list[Finding] = []
    excl: set[str] = set()
    excl_line = fp_fn.lineno
    found_excl = False
    for m in project.modules:
        for node in m.tree.body:
            if (isinstance(node, ast.Assign) and len(node.targets) == 1
                    and isinstance(node.targets[0], ast.Name)
                    and node.targets[0].id == "EXECUTION_ONLY"):
                found_excl = True
                excl_line = node.lineno
                vals = _str_elts(node.value)
                if vals is None:
                    findings.append(Finding(
                        "FL002", m.rel, node.lineno,
                        "EXECUTION_ONLY must be a literal set of field-name "
                        "strings (statically checkable exclusion set)"))
                    vals = set()
                excl = vals
                excl_mod = m
    if not found_excl:
        excl_mod = fp_mod

    for name, line in sorted(fields.items()):
        in_fp, in_excl = name in fp_keys, name in excl
        if not in_fp and not in_excl:
            findings.append(Finding(
                "FL002", cfg_mod.rel, line,
                f"FedConfig field '{name}' is neither fingerprinted "
                f"(fingerprint() in {fp_mod.rel}) nor declared execution-"
                "only (EXECUTION_ONLY) — a silent resume-identity hole"))
        elif in_fp and in_excl:
            findings.append(Finding(
                "FL002", cfg_mod.rel, line,
                f"FedConfig field '{name}' is both fingerprinted and in "
                "EXECUTION_ONLY — pick one"))
    for name in sorted(excl - set(fields)):
        findings.append(Finding(
            "FL002", excl_mod.rel, excl_line,
            f"EXECUTION_ONLY entry '{name}' is not a FedConfig field "
            "(stale exclusion)"))
    return findings


# ========================================================= FL003: donation
def _donated_of_jit_call(call: ast.Call, fn_scope: list[ast.stmt]
                         ) -> Optional[tuple[int, ...]]:
    """Donated positions of a ``jax.jit(...)`` call, resolving a
    ``donate_argnums=`` that is a literal, an IfExp, or a local name
    assigned one of those earlier in the enclosing function."""
    for kw in call.keywords:
        if kw.arg != "donate_argnums":
            continue
        got = int_tuple(kw.value)
        if got is not None:
            return got
        if isinstance(kw.value, ast.Name):
            for stmt in fn_scope:
                if (isinstance(stmt, ast.Assign)
                        and any(isinstance(t, ast.Name)
                                and t.id == kw.value.id
                                for t in stmt.targets)):
                    got = int_tuple(stmt.value)
            return got
        return None
    return None


def _collect_donors(m: Module) -> dict[str, tuple[int, ...]]:
    """Bindings in this module that hold a donating jitted callable:
    ``{'round_fn': (0, 1, 2, 3), '_finish': (0, 1, 2), 'warm': (0, 1)}``.
    Attribute targets are keyed by their bare attribute name so a callee
    assigned in ``_setup_engine`` is recognised at its ``run_round`` call
    site; factory calls donate per DONATING_FACTORIES unless they pass a
    literal ``donate=False``.  Assignments inside ``if``/``else`` blocks
    count too, and a binding assigned from several donating callables
    (one per branch) donates the union of their positions."""
    donors: dict[str, tuple[int, ...]] = {}
    scopes = [m.tree.body] + [n.body for n in ast.walk(m.tree)
                              if isinstance(n, (ast.FunctionDef,
                                                ast.AsyncFunctionDef))]
    for scope in scopes:
        for stmt in _flat_stmts(scope):
            if not isinstance(stmt, ast.Assign):
                continue
            call = stmt.value
            if not isinstance(call, ast.Call):
                continue
            seg = last_segment(call.func)
            donated: Optional[tuple[int, ...]] = None
            if seg == "jit":
                donated = _donated_of_jit_call(call, scope)
            elif seg in DONATING_FACTORIES:
                donated = DONATING_FACTORIES[seg]
                for kw in call.keywords:
                    if (kw.arg == "donate"
                            and isinstance(kw.value, ast.Constant)
                            and kw.value.value is False):
                        donated = None
            if not donated:
                continue
            for t in stmt.targets:
                key = (t.id if isinstance(t, ast.Name) else t.attr
                       if isinstance(t, ast.Attribute) else None)
                if key is not None:
                    donors[key] = tuple(sorted(
                        set(donors.get(key, ())) | set(donated)))
    return donors


def _jit_param_findings(m: Module) -> list[Finding]:
    """Canonical names must not be donated PARAMETERS of a jitted local
    function: ``jax.jit(finish, donate_argnums=(3,))`` where param 3 is
    ``tp_k`` donates a canonical stack by construction."""
    findings: list[Finding] = []
    defs = {n.name: n for n in ast.walk(m.tree)
            if isinstance(n, ast.FunctionDef)}
    scopes = [m.tree.body] + [n.body for n in ast.walk(m.tree)
                              if isinstance(n, (ast.FunctionDef,
                                                ast.AsyncFunctionDef))]
    for scope in scopes:
        for stmt in scope:
            for call in ast.walk(stmt):
                if not (isinstance(call, ast.Call)
                        and last_segment(call.func) == "jit"
                        and call.args
                        and isinstance(call.args[0], ast.Name)
                        and call.args[0].id in defs):
                    continue
                donated = _donated_of_jit_call(call, scope) or ()
                params = [a.arg for a in defs[call.args[0].id].args.args]
                for i in donated:
                    if i < len(params) and params[i] in CANONICAL_NAMES:
                        findings.append(Finding(
                            "FL003", m.rel, call.lineno,
                            f"canonical state '{params[i]}' (param {i} of "
                            f"{call.args[0].id}) is in a donated position "
                            "— canonical (K, ...) stacks / global params "
                            "must never be donated"))
    return findings


def _own_nodes(stmt: ast.stmt) -> list[ast.AST]:
    """AST nodes belonging to this statement PROPER — compound-statement
    bodies are scanned as their own ``_flat_stmts`` entries, and nested
    function/lambda bodies run later under their own bindings."""
    out: list[ast.AST] = []
    stack: list[ast.AST] = [stmt]
    while stack:
        node = stack.pop()
        out.append(node)
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.stmt, ast.Lambda)):
                continue
            stack.append(child)
    return out


def _loads_in(stmt: ast.stmt) -> list[tuple[str, int]]:
    """(identifier, line) for every Name/self-attribute LOAD in the
    statement proper."""
    out = []
    for node in _own_nodes(stmt):
        if isinstance(node, ast.Name) and isinstance(node.ctx, ast.Load):
            out.append((node.id, node.lineno))
        elif (isinstance(node, ast.Attribute)
              and isinstance(node.ctx, ast.Load)):
            d = dotted_name(node)
            if d and d.startswith("self."):
                out.append((d, node.lineno))
    return out


def _donatable_ident(node: ast.AST) -> Optional[str]:
    """The identifier an argument expression pins: a bare name or a
    ``self.attr`` chain; anything else (a call result, a subscript) has no
    lasting binding to poison."""
    d = dotted_name(node)
    if d and (("." not in d) or d.startswith("self.")):
        return d
    return None


def _helper_donation_summaries(graph: CallGraph,
                               donors: dict[str, tuple[int, ...]]
                               ) -> dict[str, tuple[int, ...]]:
    """Module-local helpers that forward a parameter into a donated
    position — calling them donates that argument too.  Fixpoint so a
    helper forwarding into another forwarding helper is still caught.
    Summary indices are CALL-ARG positions (``self`` excluded)."""
    summaries: dict[str, tuple[int, ...]] = {}
    changed = True
    while changed:
        changed = False
        table = {**summaries, **donors}
        for name, fn in graph.functions.items():
            if name in donors:
                continue
            params = [a.arg for a in fn.args.args]
            offset = 1 if params and params[0] == "self" else 0
            donated = set(summaries.get(name, ()))
            for node in ast.walk(fn):
                if not isinstance(node, ast.Call):
                    continue
                key = _donor_key(node.func)
                if key is None or key not in table:
                    continue
                for i in table[key]:
                    if i >= len(node.args):
                        continue
                    arg = node.args[i]
                    if isinstance(arg, ast.Name) and arg.id in params:
                        pos = params.index(arg.id) - offset
                        if pos >= 0:
                            donated.add(pos)
            new = tuple(sorted(donated))
            if new and new != summaries.get(name):
                summaries[name] = new
                changed = True
    return summaries


def check_fl003(project: Project) -> list[Finding]:
    findings: list[Finding] = []
    for m in project.modules:
        findings.extend(_jit_param_findings(m))
        donors = _collect_donors(m)
        if not donors:
            continue
        graph = CallGraph(m)
        # helpers that forward args into donated positions donate too
        donors = {**_helper_donation_summaries(graph, donors), **donors}
        for fn in ast.walk(m.tree):
            if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            findings.extend(_scan_consumed(m, fn, donors, graph))
    return findings


def _flat_stmts(body: list[ast.stmt]) -> list[ast.stmt]:
    """Statements in execution-ish order, recursing through compound
    statements but NOT into nested function definitions."""
    out: list[ast.stmt] = []
    for stmt in body:
        out.append(stmt)
        if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.ClassDef)):
            continue
        for field in ("body", "orelse", "finalbody"):
            out.extend(_flat_stmts(getattr(stmt, field, []) or []))
        for h in getattr(stmt, "handlers", []) or []:
            out.extend(_flat_stmts(h.body))
    return out


def _scan_consumed(m: Module, fn: ast.FunctionDef,
                   donors: dict[str, tuple[int, ...]],
                   graph: Optional[CallGraph] = None) -> list[Finding]:
    """Linear read-after-donate scan over one function body, with an
    interprocedural branch: a call to a module-local helper whose
    transitive external loads touch a consumed binding reads donated
    memory even though no load appears at this call site."""
    findings: list[Finding] = []
    consumed: dict[str, int] = {}      # identifier -> donating call line
    for stmt in _flat_stmts(fn.body):
        # 1. loads of already-consumed bindings (before this statement's
        # own donation/rebinding take effect: RHS evaluates first)
        for ident, line in _loads_in(stmt):
            if ident in consumed:
                findings.append(Finding(
                    "FL003", m.rel, line,
                    f"'{ident}' is read after being donated to a jitted "
                    f"callee at line {consumed[ident]} — the buffer was "
                    "consumed in place (DESIGN.md §13 donation contract)"))
                del consumed[ident]    # report once per donation
        # 1b. helper calls that READ a consumed binding from inside
        # (module-local functions only: attribute-boundary calls are the
        # blessed entry points and do not propagate)
        if graph is not None and consumed:
            for node in _own_nodes(stmt):
                if not isinstance(node, ast.Call):
                    continue
                key = CallGraph.callee_key(node.func)
                if key is None or key not in graph.functions:
                    continue
                for ident in sorted(set(consumed) &
                                    graph.transitive_loads(key)):
                    findings.append(Finding(
                        "FL003", m.rel, node.lineno,
                        f"'{ident}' (donated at line {consumed[ident]}) is "
                        f"read inside '{key}' called here — helpers see "
                        "donated buffers too (DESIGN.md §13)"))
                    del consumed[ident]
        # 2. donating calls in this statement consume their donated args
        for node in _own_nodes(stmt):
            if not isinstance(node, ast.Call):
                continue
            callee = _donor_key(node.func)
            if callee is None or callee not in donors:
                continue
            for i in donors[callee]:
                if i >= len(node.args):
                    continue
                ident = _donatable_ident(node.args[i])
                if ident is None:
                    continue
                bare = ident.rsplit(".", 1)[-1]
                if bare in CANONICAL_NAMES:
                    findings.append(Finding(
                        "FL003", m.rel, node.lineno,
                        f"canonical state '{ident}' passed in donated "
                        f"position {i} of '{callee}' — canonical stacks / "
                        "global params must never be donated"))
                else:
                    consumed[ident] = node.lineno
        # 3. (re)bindings make the name safe again
        rebound: list[str] = []
        if isinstance(stmt, (ast.Assign, ast.AnnAssign, ast.AugAssign)):
            targets = (stmt.targets if isinstance(stmt, ast.Assign)
                       else [stmt.target])
            for t in targets:
                rebound.extend(assigned_names(t))
        elif isinstance(stmt, ast.For):
            rebound.extend(assigned_names(stmt.target))
        elif isinstance(stmt, (ast.With, ast.AsyncWith)):
            for item in stmt.items:
                if item.optional_vars is not None:
                    rebound.extend(assigned_names(item.optional_vars))
        for ident in rebound:
            consumed.pop(ident, None)
    return findings


def _donor_key(func: ast.AST) -> Optional[str]:
    """Call target -> donor-table key: bare names as-is, ``self.x``/
    ``obj.x`` attributes by their attribute name."""
    if isinstance(func, ast.Name):
        return func.id
    if isinstance(func, ast.Attribute):
        return func.attr
    return None


# =========================================================== FL004: tracers
def _static_params(fn: ast.FunctionDef, deco: ast.AST) -> set[str]:
    """Params pinned static by a ``functools.partial(jax.jit,
    static_argnums=...)`` decorator (static args are Python values, not
    tracers)."""
    out: set[str] = set()
    if isinstance(deco, ast.Call):
        params = [a.arg for a in fn.args.args]
        for kw in deco.keywords:
            if kw.arg == "static_argnums":
                for i in int_tuple(kw.value) or ():
                    if i < len(params):
                        out.add(params[i])
            if kw.arg == "static_argnames":
                names = _str_elts(kw.value)
                if names:
                    out.update(names)
                elif (isinstance(kw.value, ast.Constant)
                      and isinstance(kw.value.value, str)):
                    out.add(kw.value.value)
    return out


def _traced_defs(m: Module) -> list[tuple[ast.AST, set[str]]]:
    """(function node, statically-pinned params) for every def/lambda this
    module hands to the tracer: jit/pmap/vmap/shard_map/pallas_call
    decorators, the same as call arguments, and lambdas passed directly."""
    wrappers = {"jit", "pmap", "vmap", "shard_map", "pallas_call"}
    defs = {n.name: n for n in ast.walk(m.tree)
            if isinstance(n, ast.FunctionDef)}
    traced: dict[ast.AST, set[str]] = {}
    for fn in defs.values():
        for deco in fn.decorator_list:
            seg = (last_segment(deco.func) if isinstance(deco, ast.Call)
                   else last_segment(deco))
            if seg in wrappers:
                traced.setdefault(fn, set())
            elif seg == "partial" and isinstance(deco, ast.Call):
                inner = deco.args and last_segment(deco.args[0])
                if inner in wrappers:
                    traced.setdefault(fn, set()).update(
                        _static_params(fn, deco))
    for node in ast.walk(m.tree):
        if not isinstance(node, ast.Call):
            continue
        if last_segment(node.func) not in wrappers:
            continue
        for arg in node.args[:1]:
            if isinstance(arg, ast.Name) and arg.id in defs:
                traced.setdefault(defs[arg.id], set())
            elif isinstance(arg, ast.Lambda):
                traced.setdefault(arg, set())
    return list(traced.items())


def _np_aliases(m: Module) -> set[str]:
    out = set()
    for node in ast.walk(m.tree):
        if isinstance(node, ast.Import):
            for alias in node.names:
                if alias.name == "numpy":
                    out.add(alias.asname or "numpy")
    return out


def check_fl004(project: Project) -> list[Finding]:
    findings: list[Finding] = []
    for m in project.in_dirs("fed", "core", "kernels"):
        np_names = _np_aliases(m)
        graph = CallGraph(m)
        summaries = _concretizing_summaries(graph, np_names)
        for fn, static in _traced_defs(m):
            findings.extend(_scan_traced(m, fn, static, np_names, summaries))
    return findings


def _param_escapes(fn: ast.AST, param: str,
                   summaries: dict[str, tuple[int, ...]],
                   np_names: set[str]) -> bool:
    """Does a value bound to ``param`` escape to the Python side inside
    ``fn`` (branch/concretizer/host numpy), directly or through another
    summarised helper?"""
    tainted = {param}
    stmts = _all_stmts(fn)
    for _ in range(2):
        for stmt in stmts:
            if isinstance(stmt, (ast.Assign, ast.AugAssign, ast.AnnAssign)):
                if _expr_tainted(stmt.value, tainted):
                    targets = (stmt.targets if isinstance(stmt, ast.Assign)
                               else [stmt.target])
                    for t in targets:
                        tainted.update(assigned_names(t))
    for node in (n for s in stmts for n in ast.walk(s)):
        if (isinstance(node, (ast.If, ast.While))
                and _expr_tainted(node.test, tainted)):
            return True
        if not isinstance(node, ast.Call):
            continue
        seg = last_segment(node.func)
        if (seg in CONCRETIZERS and isinstance(node.func, ast.Name)
                and any(_expr_tainted(a, tainted) for a in node.args)):
            return True
        if isinstance(node.func, ast.Attribute):
            if (node.func.attr in CONCRETIZING_METHODS
                    and _expr_tainted(node.func.value, tainted)):
                return True
            if (isinstance(node.func.value, ast.Name)
                    and node.func.value.id in np_names
                    and any(_expr_tainted(a, tainted) for a in node.args)):
                return True
        key = CallGraph.callee_key(node.func)
        for i in (summaries.get(key, ()) if key else ()):
            if i < len(node.args) and _expr_tainted(node.args[i], tainted):
                return True
    return False


def _concretizing_summaries(graph: CallGraph, np_names: set[str]
                            ) -> dict[str, tuple[int, ...]]:
    """Call-arg indices through which each module-local function escapes a
    value to the Python side.  Fixpoint over helper->helper forwarding so
    a concretization two calls deep still maps back to the outermost call
    site inside traced code.  Indices are CALL-ARG positions (``self``
    excluded)."""
    summaries: dict[str, tuple[int, ...]] = {}
    changed = True
    while changed:
        changed = False
        for name, fn in graph.functions.items():
            params = [a.arg for a in fn.args.args]
            offset = 1 if params and params[0] == "self" else 0
            escaping = set(summaries.get(name, ()))
            for i, p in enumerate(params[offset:]):
                if i not in escaping and _param_escapes(fn, p, summaries,
                                                        np_names):
                    escaping.add(i)
            new = tuple(sorted(escaping))
            if new and new != summaries.get(name):
                summaries[name] = new
                changed = True
    return summaries


def _scan_traced(m: Module, fn: ast.AST, static: set[str],
                 np_names: set[str],
                 summaries: Optional[dict[str, tuple[int, ...]]] = None
                 ) -> list[Finding]:
    """Taint-and-flag over one traced function: taint starts at the traced
    params (of the function and of every nested def — nested defs trace
    too), flows through simple assignments, and is flagged wherever a
    Python-side escape consumes it."""
    if isinstance(fn, ast.Lambda):
        params = {a.arg for a in fn.args.args}
        body_nodes = list(ast.walk(fn.body))
        stmts: list[ast.stmt] = []
    else:
        params = {a.arg for a in fn.args.args} - static
        for sub in ast.walk(fn):
            if isinstance(sub, (ast.FunctionDef, ast.Lambda)) and sub is not fn:
                params |= {a.arg for a in sub.args.args}
        stmts = _all_stmts(fn)
        body_nodes = []
    tainted = set(params)
    # two passes: assignments propagate taint regardless of textual order
    for _ in range(2):
        for stmt in stmts:
            if isinstance(stmt, (ast.Assign, ast.AugAssign, ast.AnnAssign)):
                if _expr_tainted(stmt.value, tainted):
                    targets = (stmt.targets if isinstance(stmt, ast.Assign)
                               else [stmt.target])
                    for t in targets:
                        tainted.update(assigned_names(t))

    findings: list[Finding] = []
    nodes = body_nodes or [n for s in stmts for n in ast.walk(s)]
    seen: set[tuple[int, str]] = set()

    def flag(line: int, msg: str):
        if (line, msg) not in seen:
            seen.add((line, msg))
            findings.append(Finding("FL004", m.rel, line, msg))

    for node in nodes:
        if isinstance(node, (ast.If, ast.While)):
            for name in sorted(_tainted_names(node.test, tainted)):
                flag(node.lineno,
                     f"Python control flow on traced value '{name}' inside "
                     "traced code — branch on host values only, use "
                     "jnp.where/lax.cond for traced ones")
        elif isinstance(node, ast.Call):
            seg = last_segment(node.func)
            if seg in CONCRETIZERS and isinstance(node.func, ast.Name):
                for arg in node.args:
                    for name in sorted(_tainted_names(arg, tainted)):
                        flag(node.lineno,
                             f"{seg}() concretizes traced value '{name}' "
                             "inside traced code — it forces a trace-time "
                             "escape (or a device sync under jit)")
            elif (isinstance(node.func, ast.Attribute)
                  and node.func.attr in CONCRETIZING_METHODS):
                for name in sorted(
                        _tainted_names(node.func.value, tainted)):
                    flag(node.lineno,
                         f".{node.func.attr}() on traced value '{name}' "
                         "inside traced code")
            elif (isinstance(node.func, ast.Attribute)
                  and isinstance(node.func.value, ast.Name)
                  and node.func.value.id in np_names):
                for arg in node.args:
                    for name in sorted(_tainted_names(arg, tainted)):
                        flag(node.lineno,
                             "host numpy call "
                             f"{node.func.value.id}.{node.func.attr}() on "
                             f"traced value '{name}' inside traced code — "
                             "use jnp")
            if summaries:
                key = CallGraph.callee_key(node.func)
                for i in (summaries.get(key, ()) if key else ()):
                    if i >= len(node.args):
                        continue
                    for name in sorted(
                            _tainted_names(node.args[i], tainted)):
                        flag(node.lineno,
                             f"traced value '{name}' escapes through "
                             f"helper '{key}' (its argument {i} is "
                             "branched on or concretized inside) — "
                             "helpers trace with their caller")
    return findings


def _all_stmts(fn: ast.FunctionDef) -> list[ast.stmt]:
    """Every statement inside ``fn`` INCLUDING nested defs' bodies (nested
    defs inside a traced function trace with it)."""
    out: list[ast.stmt] = []
    for node in ast.walk(fn):
        if isinstance(node, ast.stmt) and node is not fn:
            out.append(node)
    return out


def _tainted_names(expr: ast.AST, tainted: set[str]) -> set[str]:
    out = set()
    for node in ast.walk(expr):
        if isinstance(node, ast.Name) and node.id in tainted:
            out.add(node.id)
    return out


def _expr_tainted(expr: ast.AST, tainted: set[str]) -> bool:
    return bool(_tainted_names(expr, tainted))


# ========================================================= FL005: recompiles
# Classes whose bodies may key on ``.tobytes()``: the staging path is the
# ONE place a plan's slot assignment legitimately becomes a cache key
# (WaveStager's per-wave LRU + prefetch boxes).
BLESSED_STAGERS = frozenset({"WaveStager"})


def check_fl005(project: Project) -> list[Finding]:
    findings: list[Finding] = []
    for m in project.in_dirs("fed", "core"):
        blessed_spans: list[tuple[int, int]] = []
        for node in ast.walk(m.tree):
            if (isinstance(node, ast.ClassDef)
                    and node.name in BLESSED_STAGERS):
                blessed_spans.append((node.lineno, node.end_lineno))

        def blessed(line: int) -> bool:
            return any(lo <= line <= hi for lo, hi in blessed_spans)

        for node in ast.walk(m.tree):
            if not isinstance(node, ast.Call):
                continue
            if (isinstance(node.func, ast.Attribute)
                    and node.func.attr == "tobytes"
                    and not blessed(node.lineno)):
                findings.append(Finding(
                    "FL005", m.rel, node.lineno,
                    ".tobytes()-keyed structure outside the blessed "
                    "staging path (fed/sharded.py WaveStager) "
                    "— ad-hoc byte keys feeding jit arguments are the "
                    "recompile bug class"))
            seg = last_segment(node.func)
            base = (dotted_name(node.func) or "").split(".")[0]
            if (seg in SHAPE_CONSTRUCTORS and base in ("jnp", "jax")
                    and node.args
                    and isinstance(node.args[0],
                                   (ast.ListComp, ast.GeneratorExp,
                                    ast.SetComp))):
                findings.append(Finding(
                    "FL005", m.rel, node.lineno,
                    f"{base}.{seg}() over a comprehension bakes a Python "
                    "collection's length into an array shape — if this "
                    "feeds a jitted program, every length change "
                    "recompiles (stage through fixed-size buffers, or "
                    "allowlist with justification)"))
    return findings


# ====================================================== FL006: lock discipline
def _class_functions(cls: ast.ClassDef) -> dict[str, ast.AST]:
    """Every def lexically inside the class, keyed by bare name — methods
    and their nested worker defs share one namespace, mirroring CallGraph."""
    out: dict[str, ast.AST] = {}
    for node in ast.walk(cls):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            out.setdefault(node.name, node)
    return out


def _thread_entries(cls: ast.ClassDef) -> set[str]:
    """Function names this class hands to another thread:
    ``Thread(target=X)`` targets and ``.submit(X, ...)`` callables, where
    X is a bare name (nested worker def) or ``self.method``."""
    out: set[str] = set()
    for node in ast.walk(cls):
        if not isinstance(node, ast.Call):
            continue
        if last_segment(node.func) == "Thread":
            for kw in node.keywords:
                if kw.arg == "target":
                    key = CallGraph.callee_key(kw.value)
                    if key:
                        out.add(key)
        elif (isinstance(node.func, ast.Attribute)
              and node.func.attr == "submit" and node.args):
            key = CallGraph.callee_key(node.args[0])
            if key:
                out.add(key)
    return out


def _reachable(entries: set[str], funcs: dict[str, ast.AST]) -> set[str]:
    """Transitive closure of ``entries`` over bare-name/``self.m`` calls
    within the class's own functions."""
    seen = {e for e in entries if e in funcs}
    stack = list(seen)
    while stack:
        for node in ast.walk(funcs[stack.pop()]):
            if isinstance(node, ast.Call):
                key = CallGraph.callee_key(node.func)
                if key in funcs and key not in seen:
                    seen.add(key)
                    stack.append(key)
    return seen


def _init_attr_types(funcs: dict[str, ast.AST]) -> dict[str, Optional[str]]:
    """``self.X = Ctor(...)`` assignments in ``__init__``: attr -> the
    constructor's last segment (the structural-blessing table)."""
    init = funcs.get("__init__")
    types: dict[str, Optional[str]] = {}
    if init is None:
        return types
    for stmt in ast.walk(init):
        if not (isinstance(stmt, (ast.Assign, ast.AnnAssign))
                and isinstance(stmt.value, ast.Call)):
            continue
        seg = last_segment(stmt.value.func)
        targets = (stmt.targets if isinstance(stmt, ast.Assign)
                   else [stmt.target])
        for t in targets:
            d = dotted_name(t)
            if d and d.startswith("self.") and d.count(".") == 1:
                types[d.split(".")[1]] = seg
    return types


def _self_attr_of(node: ast.AST) -> Optional[str]:
    """``self.X`` (exactly one level) -> ``X``."""
    d = dotted_name(node)
    if d and d.startswith("self.") and d.count(".") == 1:
        return d.split(".")[1]
    return None


def _attr_writes(fn: ast.AST, lock_attrs: set[str]
                 ) -> list[tuple[str, int, bool]]:
    """(attr, line, lock_held) for every write to ``self.<attr>`` in the
    function body: attribute (re)binds, ``self.X[...] = ...`` item stores,
    and in-place mutator calls ``self.X.append/pop/put(...)``.  Nested
    defs are skipped — they run on whichever side spawns them and are
    scanned as their own functions."""
    out: list[tuple[str, int, bool]] = []

    def collect(stmt: ast.stmt, held: bool) -> None:
        for node in _own_nodes(stmt):
            if (isinstance(node, ast.Attribute)
                    and isinstance(node.ctx, (ast.Store, ast.Del))
                    and isinstance(node.value, ast.Name)
                    and node.value.id == "self"):
                out.append((node.attr, node.lineno, held))
            elif (isinstance(node, ast.Subscript)
                  and isinstance(node.ctx, (ast.Store, ast.Del))):
                attr = _self_attr_of(node.value)
                if attr:
                    out.append((attr, node.lineno, held))
            elif (isinstance(node, ast.Call)
                  and isinstance(node.func, ast.Attribute)
                  and node.func.attr in MUTATING_METHODS):
                attr = _self_attr_of(node.func.value)
                if attr:
                    out.append((attr, node.lineno, held))

    def visit(stmts: list[ast.stmt], held: bool) -> None:
        for stmt in stmts:
            if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.ClassDef)):
                continue
            collect(stmt, held)
            inner = held
            if isinstance(stmt, (ast.With, ast.AsyncWith)):
                for item in stmt.items:
                    attr = _self_attr_of(item.context_expr)
                    if attr in lock_attrs:
                        inner = True
            for field in ("body", "orelse", "finalbody"):
                visit(getattr(stmt, field, []) or [], inner)
            for h in getattr(stmt, "handlers", []) or []:
                visit(h.body, inner)

    visit(fn.body, False)
    return out


def check_fl006(project: Project) -> list[Finding]:
    findings: list[Finding] = []
    for m in project.modules:
        for cls in ast.walk(m.tree):
            if isinstance(cls, ast.ClassDef):
                findings.extend(_scan_class_locks(m, cls))
    return findings


def _scan_class_locks(m: Module, cls: ast.ClassDef) -> list[Finding]:
    funcs = _class_functions(cls)
    thread_side = _reachable(_thread_entries(cls), funcs)
    if not thread_side:
        return []
    attr_types = _init_attr_types(funcs)
    lock_attrs = {a for a, seg in attr_types.items() if seg in LOCK_TYPES}
    blessed = ({a for a, seg in attr_types.items()
                if seg in THREAD_SAFE_TYPES}
               | set(FL006_BLESSED) | lock_attrs)
    methods = {n.name for n in cls.body
               if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))}
    # __init__ writes happen-before the thread starts; nested defs outside
    # the thread closure run inline on the main side.
    t_writes: list[tuple[str, int, bool, str]] = []
    m_writes: list[tuple[str, int, bool, str]] = []
    for name, fn in funcs.items():
        if name == "__init__":
            continue
        side = t_writes if name in thread_side else (
            m_writes if name in methods else None)
        if side is None:
            continue
        side.extend((a, ln, held, name)
                    for a, ln, held in _attr_writes(fn, lock_attrs))
    shared = ({a for a, *_ in t_writes} & {a for a, *_ in m_writes}) - blessed
    findings = []
    for writes, here, there in ((t_writes, "worker-thread", "main-thread"),
                                (m_writes, "main-thread", "worker-thread")):
        for a, ln, held, fn_name in writes:
            if a in shared and not held:
                findings.append(Finding(
                    "FL006", m.rel, ln,
                    f"'{cls.name}.{a}' is mutated here ({here} side, in "
                    f"'{fn_name}') without a held lock, and also from the "
                    f"{there} side — every write to thread-shared state "
                    "must sit under `with self.<Lock>:` or hand off "
                    "through a queue/immutable snapshot (DESIGN.md §16)"))
    return sorted(findings, key=lambda f: f.line)


# =================================================== FL007: hot-path blocking
def _is_hot_span(expr: ast.AST) -> bool:
    """``perf.span("stage"|"compute"|"aggregate", ...)`` as a with-item."""
    return (isinstance(expr, ast.Call)
            and last_segment(expr.func) == "span"
            and (dotted_name(expr.func) or "").split(".")[0] == "perf"
            and bool(expr.args)
            and isinstance(expr.args[0], ast.Constant)
            and expr.args[0].value in HOT_SPANS)


def check_fl007(project: Project) -> list[Finding]:
    findings: list[Finding] = []
    for m in project.in_dirs("fed"):
        np_names = _np_aliases(m)
        graph = CallGraph(m)
        hot_stmts: list[ast.stmt] = []
        for node in ast.walk(m.tree):
            if (isinstance(node, (ast.With, ast.AsyncWith))
                    and any(_is_hot_span(i.context_expr)
                            for i in node.items)):
                hot_stmts.extend(_flat_stmts(node.body))
        if not hot_stmts:
            continue
        # a module-local helper called from hot code is hot too; calls
        # through other objects (self.stager.stage) are the blessed
        # entry points and stop propagation
        hot_fns: set[str] = set()
        for stmt in hot_stmts:
            for node in _own_nodes(stmt):
                if isinstance(node, ast.Call):
                    key = CallGraph.callee_key(node.func)
                    if key in graph.functions:
                        hot_fns.add(key)
        for entry in sorted(hot_fns):
            hot_fns |= set(graph.transitive_callees(entry))
        for name in sorted(hot_fns):
            hot_stmts.extend(_flat_stmts(graph.functions[name].body))
        findings.extend(_blocking_findings(m, hot_stmts, np_names))
    return findings


def _blocking_findings(m: Module, stmts: list[ast.stmt],
                       np_names: set[str]) -> list[Finding]:
    findings: list[Finding] = []
    seen: set[tuple[int, str]] = set()

    def flag(line: int, msg: str) -> None:
        if (line, msg) not in seen:
            seen.add((line, msg))
            findings.append(Finding("FL007", m.rel, line, msg))

    for stmt in stmts:
        for node in _own_nodes(stmt):
            if not isinstance(node, ast.Call):
                continue
            base = (dotted_name(node.func) or "").split(".")[0]
            if base in FL007_BLESSED_BASES:
                continue           # perf/guards instrumentation is sanctioned
            if isinstance(node.func, ast.Name) and node.func.id == "open":
                flag(node.lineno,
                     "open() inside a steady-round hot span — file I/O "
                     "belongs on the async checkpoint path, outside "
                     "stage/compute/aggregate")
                continue
            if not isinstance(node.func, ast.Attribute):
                continue
            attr = node.func.attr
            if attr == "block_until_ready":
                flag(node.lineno,
                     ".block_until_ready() inside a hot span — device "
                     "syncs belong outside stage/compute/aggregate "
                     "(measure dispatch, not completion)")
            elif attr == "put" and not any(
                    kw.arg == "block"
                    and isinstance(kw.value, ast.Constant)
                    and kw.value.value is False for kw in node.keywords):
                flag(node.lineno,
                     "blocking queue .put() inside a hot span — use "
                     "put_nowait()/put(..., block=False) or move the "
                     "handoff outside the span")
            elif attr == "join" and not node.args and not node.keywords:
                flag(node.lineno,
                     "unbounded .join() inside a hot span — a no-timeout "
                     "thread join stalls the round; bound it or move it "
                     "off the hot path")
            elif attr == "sleep" and base == "time":
                flag(node.lineno, "time.sleep() inside a hot span")
            elif attr in ("write_text", "write_bytes",
                          "read_text", "read_bytes"):
                flag(node.lineno,
                     f".{attr}() file I/O inside a hot span — route it "
                     "through the blessed checkpoint writer outside the "
                     "span")
            elif (base in np_names
                  and attr in ("save", "savez", "savez_compressed",
                               "load", "savetxt", "loadtxt")):
                flag(node.lineno,
                     f"{base}.{attr}() file I/O inside a hot span")
            elif attr == "dump" and base in ("json", "pickle"):
                flag(node.lineno,
                     f"{base}.dump() file I/O inside a hot span")
    return findings


RULES: list[tuple[str, object]] = [
    ("FL000", check_fl000),
    ("FL001", check_fl001),
    ("FL002", check_fl002),
    ("FL003", check_fl003),
    ("FL004", check_fl004),
    ("FL005", check_fl005),
    ("FL006", check_fl006),
    ("FL007", check_fl007),
]

RULE_DOCS = {
    "FL000": "pragma hygiene: every '# fedlint: allow=' carries a"
             " ' -- reason' suffix (bare pragmas are findings and cannot"
             " self-allowlist)",
    "FL001": "PRNG stream discipline: registered SALT_* at entropy index 2,"
             " one tuple shape per salt",
    "FL002": "fingerprint completeness: FedConfig fields == fingerprint keys"
             " ∪ EXECUTION_ONLY",
    "FL003": "donation safety: no reads of donated bindings, no canonical"
             " state in donated positions",
    "FL004": "tracer safety: no if/float()/.item()/np.* on traced values in"
             " traced code",
    "FL005": "recompile safety: no .tobytes() keys outside the blessed"
             " stager (WaveStager), no comprehension-shaped jnp"
             " constructors",
    "FL006": "lock discipline: attributes mutated from both a worker thread"
             " and main-thread methods must be written under a held lock or"
             " be a queue/lock/event handoff",
    "FL007": "hot-path latency: no device syncs, blocking queue puts,"
             " unbounded joins, sleeps, or file I/O inside the"
             " stage/compute/aggregate spans (perf/guards entry points are"
             " blessed)",
}
