"""Per-file analysis facts: the cacheable projection every rule consumes.

fedlint v1 handed each rule the raw AST and every rule re-walked it; the
interprocedural rules (lock-order, blocking-under-lock, thread-entry) need a
WHOLE-PROGRAM view — a function/method index and a resolved call graph — and
the tier-1 gate needs warm re-runs to skip parsing entirely (the suite runs
near its timeout budget). Both land here: one extraction pass per file
produces a :class:`FileFacts` — classes, functions (methods, nested defs,
lambdas), every call site with the lock set syntactically held at it, every
``self``-attribute touch, ``with self.<lock>:`` acquisitions, thread-entry
registrations (``threading.Thread``/``Timer``/send-pool dispatch), lowering
registrations (``jax.jit`` & co.), wire-key and metric-constant sites — that
is JSON-serializable, so ``.fedlint_cache/`` can key it on
``(path, mtime, size)`` and a warm run never re-parses an unchanged file.

Extraction is config-independent by design: which calls count as blocking,
which lock names alias, which metric prefixes are canonical are all matched
at RULE time over the facts, so one cache serves every rule selection.

Lock-tracking semantics (shared with the v1 guarded-by rule): ``held`` at a
site is the set of ``self.<attr>`` locks acquired by lexically enclosing
``with`` statements INSIDE the same function body. A nested ``def`` or
``lambda`` starts with an empty held set — it runs later, on whatever thread
calls it. ``# lock-held:`` annotations are recorded but NOT folded into
``held``: they are caller-side assumptions the interprocedural rules must
check, not facts.
"""

from __future__ import annotations

import ast
import dataclasses
import re

_UPPER_RE = re.compile(r"^[A-Z][A-Z0-9_]+$")
_KEY_RE = re.compile(r"^MSG_ARG_KEY_\w+$")

# schema version of the serialized facts: bump on ANY change to the
# dataclasses below or to extraction semantics — the cache discards
# mismatched entries wholesale
FACTS_SCHEMA_VERSION = 3

# call names that register their callable arguments as THREAD ENTRIES:
# the callable runs later on another thread, with no locks held
_THREAD_CTORS = frozenset({"threading.Thread", "Thread"})
_TIMER_CTORS = frozenset({"threading.Timer", "Timer"})
# method names whose callable-bearing arguments are dispatched to worker
# threads (SendWorkerPool.run_all tasks, executor.submit)
_POOL_DISPATCH_ATTRS = frozenset({"run_all", "submit"})

# attr names that lower their first argument through a compile path
# (traced-purity scope — mirrors parallel/dispatch + jax.shard_map)
_LOWERING_ATTRS = frozenset({
    "jit", "shard_map", "lower", "jit_under_mesh", "pallas_call",
})

# builtin coercions are value plumbing, not construction (the
# overwrite-after-super seam targets real constructions)
_COERCIONS = frozenset({
    "bool", "int", "float", "str", "bytes", "tuple", "list", "dict", "set",
    "frozenset",
})


def dotted_name(func: ast.expr) -> str | None:
    """`a.b.c` -> "a.b.c" (Name/Attribute chains only)."""
    parts: list[str] = []
    node = func
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _is_jit_expr(expr: ast.expr) -> bool:
    """`jax.jit`, `jit`, `partial(jax.jit, ...)`, `functools.partial(...)`."""
    dotted = dotted_name(expr)
    if dotted in ("jax.jit", "jit"):
        return True
    if isinstance(expr, ast.Call):
        fn = dotted_name(expr.func)
        if fn in ("partial", "functools.partial") and expr.args:
            return _is_jit_expr(expr.args[0])
    return False


def _self_attr_target(node: ast.stmt) -> str | None:
    """`self.X = ...` / `self.X: T = ...` -> X (single-target only)."""
    if isinstance(node, ast.Assign) and len(node.targets) == 1:
        target = node.targets[0]
    elif isinstance(node, ast.AnnAssign):
        target = node.target
    else:
        return None
    if (isinstance(target, ast.Attribute)
            and isinstance(target.value, ast.Name)
            and target.value.id == "self"):
        return target.attr
    return None


def _is_construction(value: ast.expr | None) -> bool:
    if not isinstance(value, ast.Call):
        return False
    func = value.func
    if isinstance(func, ast.Name) and func.id in _COERCIONS:
        return False
    return True


def _is_super_init_call(node: ast.stmt) -> bool:
    """`super().__init__(...)` or `SomeClass.__init__(self, ...)`."""
    if not (isinstance(node, ast.Expr) and isinstance(node.value, ast.Call)):
        return False
    func = node.value.func
    if not (isinstance(func, ast.Attribute) and func.attr == "__init__"):
        return False
    owner = func.value
    if (isinstance(owner, ast.Call) and isinstance(owner.func, ast.Name)
            and owner.func.id == "super"):
        return True
    # explicit-base form used by the diamond tips (Buffered* variants)
    return isinstance(owner, (ast.Name, ast.Attribute))


def _base_name(expr: ast.expr) -> str | None:
    if isinstance(expr, ast.Name):
        return expr.id
    if isinstance(expr, ast.Attribute):
        return expr.attr
    return None


@dataclasses.dataclass
class CallFact:
    """One call site: where, what (dotted chain), a resolution hint, and the
    locks syntactically held around it."""

    line: int
    col: int
    dotted: str | None
    func: int          # owning FuncFact index, -1 for module scope
    target: tuple[str, str] | None   # ("self", m) | ("name", n) | None
    held: tuple[str, ...]

    def to_list(self) -> list:
        return [self.line, self.col, self.dotted, self.func,
                list(self.target) if self.target else None, list(self.held)]

    @staticmethod
    def from_list(row: list) -> "CallFact":
        return CallFact(row[0], row[1], row[2], row[3],
                        tuple(row[4]) if row[4] else None, tuple(row[5]))


@dataclasses.dataclass
class FuncFact:
    """One function-like body: method, module function, nested def, lambda."""

    index: int
    name: str
    qualname: str
    line: int
    col: int
    cls: int            # ClassFact index when a direct method, else -1
    parent: int         # enclosing FuncFact index, -1 at module/class level
    kind: str           # "def" | "async" | "lambda"
    lock_held: tuple[str, ...]          # `# lock-held:` annotation
    jit_decorated: bool
    calls: list[int] = dataclasses.field(default_factory=list)
    # (attr, line, col, held) — every `self.<attr>` touch in this body
    touches: list[tuple[str, int, int, tuple[str, ...]]] = dataclasses.field(
        default_factory=list)
    # (lock, line, held_before) — `with self.<lock>:` acquisitions
    acquires: list[tuple[str, int, tuple[str, ...]]] = dataclasses.field(
        default_factory=list)
    lowered_via: str | None = None      # lambda handed to a lowering call

    def to_dict(self) -> dict:
        return {
            "i": self.index, "n": self.name, "q": self.qualname,
            "l": self.line, "c": self.col, "k": self.cls, "p": self.parent,
            "t": self.kind, "lh": list(self.lock_held),
            "j": self.jit_decorated, "ca": self.calls,
            "to": [[a, l, c, list(h)] for a, l, c, h in self.touches],
            "aq": [[lk, l, list(h)] for lk, l, h in self.acquires],
            "lv": self.lowered_via,
        }

    @staticmethod
    def from_dict(d: dict) -> "FuncFact":
        return FuncFact(
            d["i"], d["n"], d["q"], d["l"], d["c"], d["k"], d["p"], d["t"],
            tuple(d["lh"]), d["j"], list(d["ca"]),
            [(a, l, c, tuple(h)) for a, l, c, h in d["to"]],
            [(lk, l, tuple(h)) for lk, l, h in d["aq"]],
            d["lv"],
        )


@dataclasses.dataclass
class ClassFact:
    """Per-class facts: base chain, what ``__init__`` constructs/assigns,
    concurrency annotations, and the method table."""

    index: int
    name: str
    bases: tuple[str, ...]
    line: int
    init_constructed: dict[str, int] = dataclasses.field(default_factory=dict)
    init_assigned: set[str] = dataclasses.field(default_factory=set)
    # (attr, line, col, top_stmt_line) — every self.X assignment in __init__
    init_assigns: list[tuple[str, int, int, int]] = dataclasses.field(
        default_factory=list)
    super_call_line: int | None = None
    guarded: dict[str, str] = dataclasses.field(default_factory=dict)
    guard_decl_lines: set[int] = dataclasses.field(default_factory=set)
    lock_held: dict[str, tuple[str, ...]] = dataclasses.field(
        default_factory=dict)
    methods: dict[str, int] = dataclasses.field(default_factory=dict)
    # class-level MSG_ARG_KEY_* string constants: name -> (value, line, col,
    # value_line, value_col)
    wire_defs: dict[str, tuple[str, int, int, int, int]] = dataclasses.field(
        default_factory=dict)

    def to_dict(self) -> dict:
        return {
            "i": self.index, "n": self.name, "b": list(self.bases),
            "l": self.line, "ic": self.init_constructed,
            "ia": sorted(self.init_assigned),
            "ias": [list(t) for t in self.init_assigns],
            "s": self.super_call_line, "g": self.guarded,
            "gd": sorted(self.guard_decl_lines),
            "lh": {k: list(v) for k, v in self.lock_held.items()},
            "m": self.methods,
            "w": {k: list(v) for k, v in self.wire_defs.items()},
        }

    @staticmethod
    def from_dict(d: dict) -> "ClassFact":
        return ClassFact(
            d["i"], d["n"], tuple(d["b"]), d["l"],
            dict(d["ic"]), set(d["ia"]),
            [tuple(t) for t in d["ias"]], d["s"], dict(d["g"]),
            set(d["gd"]), {k: tuple(v) for k, v in d["lh"].items()},
            dict(d["m"]), {k: tuple(v) for k, v in d["w"].items()},
        )


@dataclasses.dataclass
class WaiverFact:
    line: int
    rules: tuple[str, ...]
    reason: str | None


@dataclasses.dataclass
class FileFacts:
    """Everything the rules need to know about one module."""

    path: str
    classes: list[ClassFact] = dataclasses.field(default_factory=list)
    functions: list[FuncFact] = dataclasses.field(default_factory=list)
    calls: list[CallFact] = dataclasses.field(default_factory=list)
    # (via, ref, line, owner func index) — callables handed to thread ctors
    thread_entries: list[tuple[str, tuple[str, str], int, int]] = (
        dataclasses.field(default_factory=list))
    # function NAMES passed to a lowering call (jax.jit(f), shard_map(f, ..))
    lowered_names: list[tuple[str, str]] = dataclasses.field(
        default_factory=list)         # (name, via)
    # whitespace-free string constants: (value, line, col)
    str_consts: list[tuple[str, int, int]] = dataclasses.field(
        default_factory=list)
    # uppercase identifiers referenced anywhere (metric emission check)
    upper_refs: set[str] = dataclasses.field(default_factory=set)
    # wire-contract usage tallies (MSG_ARG_KEY_* names)
    wire_written: set[str] = dataclasses.field(default_factory=set)
    wire_read: set[str] = dataclasses.field(default_factory=set)
    # add_params("literal", ...) sites: (value, line, col)
    add_params_literals: list[tuple[str, int, int]] = dataclasses.field(
        default_factory=list)
    # value-constant positions of wire definitions (skipped by dup scan)
    wire_def_sites: set[tuple[int, int]] = dataclasses.field(
        default_factory=set)
    # module-level UPPER = "str" constants: (name, value, line, col)
    module_consts: list[tuple[str, str, int, int]] = dataclasses.field(
        default_factory=list)
    waivers: dict[int, WaiverFact] = dataclasses.field(default_factory=dict)
    standalone_comments: set[int] = dataclasses.field(default_factory=set)

    # -- waiver resolution (same grammar as SourceFile) ----------------------

    def waiver_fact_for(self, rule: str, line: int) -> WaiverFact | None:
        for candidate in (line, line - 1):
            w = self.waivers.get(candidate)
            if w is None:
                continue
            if (candidate == line - 1
                    and candidate not in self.standalone_comments):
                continue
            if rule in w.rules:
                return w
        return None

    # -- (de)serialization ---------------------------------------------------

    def to_dict(self) -> dict:
        return {
            "path": self.path,
            "classes": [c.to_dict() for c in self.classes],
            "functions": [f.to_dict() for f in self.functions],
            "calls": [c.to_list() for c in self.calls],
            "thread_entries": [[v, list(r), l, f]
                               for v, r, l, f in self.thread_entries],
            "lowered_names": [list(t) for t in self.lowered_names],
            "str_consts": [list(t) for t in self.str_consts],
            "upper_refs": sorted(self.upper_refs),
            "wire_written": sorted(self.wire_written),
            "wire_read": sorted(self.wire_read),
            "add_params_literals": [list(t) for t in self.add_params_literals],
            "wire_def_sites": [list(t) for t in sorted(self.wire_def_sites)],
            "module_consts": [list(t) for t in self.module_consts],
            "waivers": {
                str(line): [w.line, list(w.rules), w.reason]
                for line, w in self.waivers.items()
            },
            "standalone_comments": sorted(self.standalone_comments),
        }

    @staticmethod
    def from_dict(d: dict) -> "FileFacts":
        return FileFacts(
            path=d["path"],
            classes=[ClassFact.from_dict(c) for c in d["classes"]],
            functions=[FuncFact.from_dict(f) for f in d["functions"]],
            calls=[CallFact.from_list(c) for c in d["calls"]],
            thread_entries=[(v, tuple(r), l, f)
                            for v, r, l, f in d["thread_entries"]],
            lowered_names=[tuple(t) for t in d["lowered_names"]],
            str_consts=[tuple(t) for t in d["str_consts"]],
            upper_refs=set(d["upper_refs"]),
            wire_written=set(d["wire_written"]),
            wire_read=set(d["wire_read"]),
            add_params_literals=[tuple(t) for t in d["add_params_literals"]],
            wire_def_sites={tuple(t) for t in d["wire_def_sites"]},
            module_consts=[tuple(t) for t in d["module_consts"]],
            waivers={
                int(line): WaiverFact(row[0], tuple(row[1]), row[2])
                for line, row in d["waivers"].items()
            },
            standalone_comments=set(d["standalone_comments"]),
        )


class _Extractor(ast.NodeVisitor):
    """One pass over a parsed module, emitting a FileFacts."""

    def __init__(self, source_file):
        self.sf = source_file
        self.facts = FileFacts(path=source_file.path)
        self.class_stack: list[int] = []
        self.func_stack: list[int] = []
        self._func_nodes: list[ast.AST] = []  # the defs around the visitor
        self.held: tuple[str, ...] = ()
        # id(lambda node) -> via, for lambdas handed to lowering calls
        self._lambda_via: dict[int, str] = {}

    # -- helpers -------------------------------------------------------------

    def _cur_func(self) -> int:
        return self.func_stack[-1] if self.func_stack else -1

    def _qual_prefix(self) -> str:
        parts: list[str] = []
        for ci in self.class_stack:
            parts.append(self.facts.classes[ci].name)
        for fi in self.func_stack:
            parts.append(self.facts.functions[fi].name)
        return ".".join(parts)

    def _ref_of(self, expr: ast.expr) -> tuple[str, str] | None:
        """A callable reference we can resolve: self.<m> or a bare name."""
        if (isinstance(expr, ast.Attribute)
                and isinstance(expr.value, ast.Name)
                and expr.value.id == "self"):
            return ("self", expr.attr)
        if isinstance(expr, ast.Name):
            return ("name", expr.id)
        return None

    # -- classes -------------------------------------------------------------

    def visit_ClassDef(self, node: ast.ClassDef) -> None:
        cf = ClassFact(
            index=len(self.facts.classes),
            name=node.name,
            bases=tuple(b for b in map(_base_name, node.bases) if b),
            line=node.lineno,
        )
        self.facts.classes.append(cf)
        for item in node.body:
            if isinstance(item, (ast.FunctionDef, ast.AsyncFunctionDef)):
                held = self.sf.lock_held_annotation(item.lineno)
                if held:
                    cf.lock_held[item.name] = tuple(held)
                if item.name == "__init__":
                    self._index_init(cf, item)
            elif (isinstance(item, ast.Assign) and len(item.targets) == 1
                    and isinstance(item.targets[0], ast.Name)
                    and _KEY_RE.match(item.targets[0].id)
                    and isinstance(item.value, ast.Constant)
                    and isinstance(item.value.value, str)):
                cf.wire_defs.setdefault(item.targets[0].id, (
                    item.value.value, item.lineno, item.col_offset,
                    item.value.lineno, item.value.col_offset,
                ))
                self.facts.wire_def_sites.add(
                    (item.value.lineno, item.value.col_offset))
        # methods register as functions are visited (class on top of stack)
        self.class_stack.append(cf.index)
        saved_funcs, self.func_stack = self.func_stack, []
        saved_held, self.held = self.held, ()
        self.generic_visit(node)
        self.func_stack = saved_funcs
        self.held = saved_held
        self.class_stack.pop()

    def _index_init(self, cf: ClassFact, item: ast.FunctionDef) -> None:
        for stmt in item.body:
            if _is_super_init_call(stmt):
                if cf.super_call_line is None:
                    cf.super_call_line = stmt.lineno
                continue
            for sub in ast.walk(stmt):
                if not isinstance(sub, (ast.Assign, ast.AnnAssign)):
                    continue
                attr = _self_attr_target(sub)
                if attr is None:
                    continue
                cf.init_assigned.add(attr)
                cf.init_assigns.append(
                    (attr, sub.lineno, sub.col_offset, stmt.lineno))
                if _is_construction(sub.value):
                    cf.init_constructed.setdefault(attr, sub.lineno)

    # -- functions -----------------------------------------------------------

    def _enter_function(self, node, name: str, kind: str) -> FuncFact:
        direct_method = (bool(self.class_stack) and not self.func_stack)
        prefix = self._qual_prefix()
        ff = FuncFact(
            index=len(self.facts.functions),
            name=name,
            qualname=f"{prefix}.{name}" if prefix else name,
            line=node.lineno,
            col=node.col_offset,
            cls=self.class_stack[-1] if direct_method else -1,
            parent=self._cur_func(),
            kind=kind,
            lock_held=tuple(self.sf.lock_held_annotation(node.lineno)),
            jit_decorated=(
                kind != "lambda"
                and any(_is_jit_expr(d) for d in node.decorator_list)
            ),
            lowered_via=self._lambda_via.get(id(node)),
        )
        self.facts.functions.append(ff)
        if direct_method:
            self.facts.classes[ff.cls].methods.setdefault(name, ff.index)
        return ff

    def _visit_function(self, node, name: str, kind: str) -> None:
        ff = self._enter_function(node, name, kind)
        self.func_stack.append(ff.index)
        self._func_nodes.append(node)
        # the body runs later: enclosing with-blocks do NOT protect it
        saved_held, self.held = self.held, ()
        self.generic_visit(node)
        self.held = saved_held
        self._func_nodes.pop()
        self.func_stack.pop()

    def visit_FunctionDef(self, node: ast.FunctionDef) -> None:
        self._visit_function(node, node.name, "def")

    def visit_AsyncFunctionDef(self, node: ast.AsyncFunctionDef) -> None:
        self._visit_function(node, node.name, "async")

    def visit_Lambda(self, node: ast.Lambda) -> None:
        self._visit_function(node, "<lambda>", "lambda")

    # -- guarded-by declarations ---------------------------------------------

    def _note_guard_decl(self, node) -> None:
        if not self.class_stack:
            return
        attr = _self_attr_target(node)
        if attr is None:
            return
        lock = self.sf.guarded_annotation(node.lineno)
        if lock is not None:
            cf = self.facts.classes[self.class_stack[-1]]
            cf.guarded.setdefault(attr, lock)
            cf.guard_decl_lines.add(node.lineno)

    def visit_Assign(self, node: ast.Assign) -> None:
        self._note_guard_decl(node)
        self.generic_visit(node)

    def visit_AnnAssign(self, node: ast.AnnAssign) -> None:
        self._note_guard_decl(node)
        self.generic_visit(node)

    # -- lock tracking -------------------------------------------------------

    def visit_With(self, node: ast.With) -> None:
        acquired: list[str] = []
        for item in node.items:
            expr = item.context_expr
            if (isinstance(expr, ast.Attribute)
                    and isinstance(expr.value, ast.Name)
                    and expr.value.id == "self"):
                fi = self._cur_func()
                if fi >= 0:
                    self.facts.functions[fi].acquires.append(
                        (expr.attr, expr.lineno, self.held))
                if expr.attr not in self.held:
                    acquired.append(expr.attr)
            self.visit(item.context_expr)
            if item.optional_vars is not None:
                self.visit(item.optional_vars)
        saved = self.held
        self.held = tuple([*self.held, *acquired])
        for stmt in node.body:
            self.visit(stmt)
        self.held = saved

    visit_AsyncWith = visit_With

    # -- leaf facts ----------------------------------------------------------

    def visit_Attribute(self, node: ast.Attribute) -> None:
        if isinstance(node.value, ast.Name) and node.value.id == "self":
            fi = self._cur_func()
            if fi >= 0:
                self.facts.functions[fi].touches.append(
                    (node.attr, node.lineno, node.col_offset, self.held))
        if _UPPER_RE.match(node.attr):
            self.facts.upper_refs.add(node.attr)
        self.generic_visit(node)

    def visit_Name(self, node: ast.Name) -> None:
        if _UPPER_RE.match(node.id):
            self.facts.upper_refs.add(node.id)

    def visit_Constant(self, node: ast.Constant) -> None:
        v = node.value
        if (isinstance(v, str) and v and len(v) <= 200
                and not any(ch.isspace() for ch in v)):
            self.facts.str_consts.append((v, node.lineno, node.col_offset))

    # -- wire-contract marks -------------------------------------------------

    def _wire_key_name(self, node: ast.expr) -> str | None:
        if isinstance(node, ast.Attribute) and _KEY_RE.match(node.attr):
            return node.attr
        if isinstance(node, ast.Name) and _KEY_RE.match(node.id):
            return node.id
        return None

    def _wire_mark(self, node: ast.expr, read: bool = False,
                   written: bool = False) -> None:
        name = self._wire_key_name(node)
        if name is None:
            return
        if read:
            self.facts.wire_read.add(name)
        if written:
            self.facts.wire_written.add(name)

    def visit_Subscript(self, node: ast.Subscript) -> None:
        self._wire_mark(node.slice, read=True, written=True)
        self.generic_visit(node)

    def visit_Dict(self, node: ast.Dict) -> None:
        for key in node.keys:
            if key is not None:
                self._wire_mark(key, written=True)
        self.generic_visit(node)

    def visit_Compare(self, node: ast.Compare) -> None:
        for comp in [node.left, *node.comparators]:
            self._wire_mark(comp, read=True, written=True)
        self.generic_visit(node)

    # -- calls ---------------------------------------------------------------

    def visit_Call(self, node: ast.Call) -> None:
        dotted = dotted_name(node.func)
        target = self._ref_of(node.func)
        call = CallFact(
            line=node.lineno, col=node.col_offset, dotted=dotted,
            func=self._cur_func(), target=target, held=self.held,
        )
        idx = len(self.facts.calls)
        self.facts.calls.append(call)
        if call.func >= 0:
            self.facts.functions[call.func].calls.append(idx)

        # wire-contract usage marks (MyMessage.add_params(KEY, v), .get(KEY))
        if isinstance(node.func, ast.Attribute) and node.args:
            if node.func.attr == "add_params":
                self._wire_mark(node.args[0], written=True)
                arg0 = node.args[0]
                if (isinstance(arg0, ast.Constant)
                        and isinstance(arg0.value, str)):
                    self.facts.add_params_literals.append(
                        (arg0.value, arg0.lineno, arg0.col_offset))
            elif node.func.attr in ("get", "pop"):
                self._wire_mark(node.args[0], read=True)
            else:
                for arg in node.args:
                    self._wire_mark(arg, read=True, written=True)

        # thread-entry registrations
        self._note_thread_entry(node, dotted)

        # lowering registrations (traced-purity)
        is_lowering = (
            dotted in ("jax.jit", "jit")
            or (isinstance(node.func, ast.Attribute)
                and node.func.attr in _LOWERING_ATTRS)
        )
        if is_lowering and node.args:
            via = dotted or node.func.attr
            arg0 = node.args[0]
            if isinstance(arg0, ast.Name):
                self.facts.lowered_names.append((arg0.id, via))
                # a loop variable: the engine writes its programs as rows
                # of a table and lowers them through one call (``for name,
                # impl, ... in rows: displib.lower(impl, ...)``). What the
                # variable may hold is what the enclosing function puts
                # into its tuples and lists: those method handles are
                # recorded like a handle passed directly
                for handle in self._row_handles(arg0.id):
                    self.facts.lowered_names.append((handle, via))
            elif isinstance(arg0, ast.Attribute):
                # method handles lowered by reference — the engine's packed/
                # sharded program constructors pass bound methods to
                # dispatch.lower (``displib.lower(self._packed_agg_impl,
                # ...)``); record the terminal attr so traced-purity scans
                # the method body like any lowered function
                self.facts.lowered_names.append((arg0.attr, via))
            elif isinstance(arg0, ast.Lambda):
                self._lambda_via[id(arg0)] = via

        self.generic_visit(node)

    def _row_handles(self, loop_var: str) -> list[str]:
        """Terminal attrs of the ``self.<method>`` handles inside the tuple
        and list literals of the enclosing function, when ``loop_var`` is
        bound by one of its ``for`` targets (else nothing: a plain name is
        a function's own)."""
        if not self._func_nodes:
            return []
        nodes = list(ast.walk(self._func_nodes[-1]))
        targets = {n.id for loop in nodes if isinstance(loop, ast.For)
                   for n in ast.walk(loop.target) if isinstance(n, ast.Name)}
        if loop_var not in targets:
            return []
        return [elt.attr for row in nodes if isinstance(row, (ast.Tuple, ast.List))
                for elt in row.elts
                if isinstance(elt, ast.Attribute)
                and isinstance(elt.value, ast.Name) and elt.value.id == "self"]

    def _note_thread_entry(self, node: ast.Call, dotted: str | None) -> None:
        refs: list[tuple[str, tuple[str, str], int]] = []
        if dotted in _THREAD_CTORS:
            for kw in node.keywords:
                if kw.arg == "target":
                    ref = self._ref_of(kw.value)
                    if ref:
                        refs.append(("Thread", ref, kw.value.lineno))
        elif dotted in _TIMER_CTORS:
            cand = None
            if len(node.args) >= 2:
                cand = node.args[1]
            else:
                for kw in node.keywords:
                    if kw.arg == "function":
                        cand = kw.value
            if cand is not None:
                ref = self._ref_of(cand)
                if ref:
                    refs.append(("Timer", ref, cand.lineno))
        elif (isinstance(node.func, ast.Attribute)
                and node.func.attr in _POOL_DISPATCH_ATTRS):
            # pool dispatch: any resolvable callable reference anywhere in
            # the argument expressions runs later on a worker thread
            for arg in [*node.args, *[kw.value for kw in node.keywords]]:
                for sub in ast.walk(arg):
                    if isinstance(sub, (ast.Name, ast.Attribute)):
                        ref = self._ref_of(sub)
                        if ref:
                            refs.append((node.func.attr, ref, sub.lineno))
        for via, ref, line in refs:
            self.facts.thread_entries.append(
                (via, ref, line, self._cur_func()))


def extract_facts(source_file) -> FileFacts:
    """Produce the FileFacts for a parsed :class:`core.SourceFile`."""
    ex = _Extractor(source_file)
    ex.visit(source_file.tree)
    facts = ex.facts
    # module-level UPPER = "str" constants (metric-keys dead-metric check)
    for stmt in source_file.tree.body:
        if (isinstance(stmt, ast.Assign) and len(stmt.targets) == 1
                and isinstance(stmt.targets[0], ast.Name)
                and _UPPER_RE.match(stmt.targets[0].id)
                and isinstance(stmt.value, ast.Constant)
                and isinstance(stmt.value.value, str)):
            facts.module_consts.append((
                stmt.targets[0].id, stmt.value.value,
                stmt.lineno, stmt.col_offset,
            ))
    # waivers + standalone comment lines (waiver application is facts-side)
    for line, w in source_file.waivers.items():
        facts.waivers[line] = WaiverFact(w.line, w.rules, w.reason)
    facts.standalone_comments = set(source_file.standalone_comments)
    return facts
