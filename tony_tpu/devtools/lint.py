"""tonylint: AST-based invariant checker for the tony_tpu tree.

The orchestrator's whole value is that it babysits everything and never
dies with the job — and the repo's reliability bugs keep being instances
of the same few static patterns: a blocking call made while holding a
lock (the channel-plane hangs), a leaked fd (the launch leak now watched
at runtime by ``tony_task_open_fds``), a proto wire change that was not
strictly additive, a bare ``except`` that eats the evidence in a server
hot loop. This module encodes those hard-won disciplines as ~8 checkers
so every future PR inherits them for free instead of re-learning them in
review::

    python -m tony_tpu.devtools.lint [paths...]          # exit 1 on findings
    python -m tony_tpu.devtools.lint --update-wire-manifest

Checkers (table with rationale in ``docs/static-analysis.md``):

========  ==============================================================
TL001     blocking-while-locked: socket send/recv/accept/connect,
          ``time.sleep``, ``subprocess.*``, thread ``.join()``, channel
          ``send``/``send_bytes``/``recv_bytes``, frame I/O, and
          foreign ``.wait()`` lexically inside a ``with <lock>`` block.
TL002     lock-discipline: attributes a class declares guarded via a
          ``# guarded-by: _lock`` comment accessed outside a ``with``
          on that lock.
TL003     thread-hygiene: every ``threading.Thread`` gets a ``tony-``-
          prefixed ``name`` and is either ``daemon=True`` or provably
          joined in the same module.
TL004     fd-hygiene: ``socket.socket()`` / ``open()`` results bound to
          locals must be closed (``with``, ``try/finally``, a
          same-function ``.close()``) or escape ownership.
TL005     broad-except: bare ``except:`` / ``except Exception`` that
          neither re-raises, logs, nor flight-records.
TL006     proto-additivity: ``tony.proto`` diffed against the committed
          ``wire_manifest.json`` — renumbering or reusing a released
          field number is an error; adding is fine and
          ``--update-wire-manifest`` records it.
TL007     frame-exhaustiveness: every frame/op constant in
          ``serving/protocol.py`` and ``channels/channel.py`` has a
          dispatch arm somewhere under ``tony_tpu/``.
TL008     unobserved-series: every ``tony_*`` metric series, jhist
          event type, and ``tony.*`` config key appears in its docs
          table, and vice versa (the one implementation behind the
          bijection tests in ``tests/test_tracing.py`` /
          ``tests/test_config.py``).
========  ==============================================================

Suppression is a checked-in **baseline** (``devtools/lint_baseline.json``)
keyed per ``(checker, path, symbol)`` — never per line number — so the
gate is ratcheting: pre-existing findings stay suppressed, new code
cannot add any, and shrinking the baseline is always legal.

Dependency-free on purpose (stdlib ``ast`` + ``json`` + ``re`` only): it
must run on any machine that can run the tests, including inside the
tier-1 self-check (``tests/test_lint.py``).
"""

from __future__ import annotations

import argparse
import ast
import dataclasses
import json
import os
import re
import sys

#: repo root (the directory holding tony_tpu/, docs/, tests/).
REPO_ROOT = os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))

DEFAULT_BASELINE = os.path.join("tony_tpu", "devtools",
                                "lint_baseline.json")
WIRE_MANIFEST = os.path.join("tony_tpu", "rpc", "proto",
                             "wire_manifest.json")
PROTO_FILE = os.path.join("tony_tpu", "rpc", "proto", "tony.proto")

CHECKERS = ("TL001", "TL002", "TL003", "TL004",
            "TL005", "TL006", "TL007", "TL008")


@dataclasses.dataclass(frozen=True)
class Finding:
    checker: str
    path: str        # repo-relative, posix separators
    line: int
    symbol: str      # stable suppression key: qualname / constant / series
    message: str
    hint: str

    @property
    def key(self) -> tuple[str, str, str]:
        return (self.checker, self.path, self.symbol)

    def render(self) -> str:
        return (f"{self.path}:{self.line}: {self.checker} "
                f"[{self.symbol}] {self.message}  (fix: {self.hint})")


@dataclasses.dataclass
class Module:
    path: str        # repo-relative posix path (or absolute if outside)
    abspath: str
    source: str
    lines: list[str]
    tree: ast.AST


# ---------------------------------------------------------------------------
# shared AST plumbing
# ---------------------------------------------------------------------------
def _relpath(path: str) -> str:
    ap = os.path.abspath(path)
    if ap.startswith(REPO_ROOT + os.sep):
        return os.path.relpath(ap, REPO_ROOT).replace(os.sep, "/")
    return ap.replace(os.sep, "/")


def load_module(path: str) -> Module | None:
    """Parse one file; unparseable files are their own loud failure at
    import/test time, not a lint concern — skipped with a stderr note."""
    with open(path, encoding="utf-8") as f:
        source = f.read()
    try:
        tree = ast.parse(source, filename=path)
    except SyntaxError as e:
        print(f"tonylint: skipping unparseable {path}: {e}",
              file=sys.stderr)
        return None
    return Module(path=_relpath(path), abspath=os.path.abspath(path),
                  source=source, lines=source.splitlines(), tree=tree)


def scan_paths(paths: list[str]) -> list[Module]:
    files: list[str] = []
    for p in paths:
        if os.path.isdir(p):
            for dirpath, dirnames, names in os.walk(p):
                dirnames[:] = [d for d in dirnames
                               if d != "__pycache__"
                               and not d.startswith(".")]
                files.extend(os.path.join(dirpath, n)
                             for n in sorted(names) if n.endswith(".py"))
        elif p.endswith(".py"):
            files.append(p)
    mods = []
    for f in files:
        m = load_module(f)
        if m is not None:
            mods.append(m)
    return mods


def _dotted(node: ast.AST) -> str | None:
    """``a.b.c`` for a Name/Attribute chain, else None."""
    parts: list[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _last_segment(node: ast.AST) -> str | None:
    if isinstance(node, ast.Attribute):
        return node.attr
    if isinstance(node, ast.Name):
        return node.id
    return None


def _qualnames(tree: ast.AST) -> dict[ast.AST, str]:
    """Map every node to its enclosing scope's qualified name — the
    stable symbol a baseline entry suppresses by."""
    out: dict[ast.AST, str] = {}

    def walk(node: ast.AST, scope: str) -> None:
        for child in ast.iter_child_nodes(node):
            child_scope = scope
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef,
                                  ast.ClassDef)):
                child_scope = (f"{scope}.{child.name}" if scope
                               else child.name)
            out[child] = child_scope or "<module>"
            walk(child, child_scope)

    out[tree] = "<module>"
    walk(tree, "")
    return out


def _body_nodes(node: ast.AST):
    """Every node lexically under ``node`` EXCLUDING nested function /
    lambda bodies: code inside a closure is not executed where it is
    written, so lock-scope checkers must not attribute it to the
    enclosing block."""
    stack = list(ast.iter_child_nodes(node))
    while stack:
        n = stack.pop()
        yield n
        if not isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef,
                              ast.Lambda)):
            stack.extend(ast.iter_child_nodes(n))


# ---------------------------------------------------------------------------
# TL001: blocking call while holding a lock
# ---------------------------------------------------------------------------
#: a ``with`` context expression whose last segment matches this is a
#: lock (Lock, RLock, Condition — the repo's naming convention).
_LOCKISH = re.compile(r"(^|_)(lock|cv|mutex|cond|condition)$")

#: attribute calls that block on the network / another thread / a child
#: process. ``.wait()`` is special-cased (fine on the held condition,
#: a deadlock invitation on anything else) and ``.join()`` is
#: heuristically filtered from string joins below.
_BLOCKING_ATTRS = {
    "sleep", "sendall", "send", "recv", "recv_into", "accept",
    "connect", "connect_ex", "sendto", "recvfrom", "makefile",
    "getaddrinfo", "create_connection", "send_bytes", "recv_bytes",
    "drain",
}
_BLOCKING_NAMES = {"sleep", "recv_frame", "send_frame", "recv_exact",
                   "create_connection"}


def _is_string_join(call: ast.Call) -> bool:
    """``sep.join(parts)`` vs ``thread.join(timeout)``: a thread join
    takes no args or a numeric/keyword timeout; a string join takes an
    iterable. ``os.path.join`` is excluded by its receiver chain."""
    recv = call.func.value if isinstance(call.func, ast.Attribute) else None
    if isinstance(recv, ast.Constant):
        return True                      # "".join / b"".join
    if _last_segment(recv) in ("path", "os", "posixpath", "ntpath"):
        return True
    if len(call.args) > 1:
        return True
    if call.args:
        a = call.args[0]
        if not (isinstance(a, ast.Constant)
                and isinstance(a.value, (int, float))):
            return True                  # join(parts): an iterable arg
    return False


def _blocking_call_reason(call: ast.Call,
                          held_locks: list[str]) -> str | None:
    func = call.func
    if isinstance(func, ast.Name):
        if func.id in _BLOCKING_NAMES:
            return func.id
        return None
    if not isinstance(func, ast.Attribute):
        return None
    dotted = _dotted(func) or func.attr
    root = dotted.split(".", 1)[0]
    if root == "subprocess":
        return dotted
    if func.attr == "join":
        if _is_string_join(call):
            return None
        return dotted + "()"
    if func.attr == "wait":
        # waiting on the condition you hold RELEASES it (fine); waiting
        # on anything else while holding a lock is the deadlock shape.
        recv = _dotted(func.value)
        if recv is not None and recv in held_locks:
            return None
        return dotted + "()"
    if func.attr in _BLOCKING_ATTRS:
        return dotted + "()"
    return None


def check_blocking_under_lock(mod: Module) -> list[Finding]:
    findings: list[Finding] = []
    quals = _qualnames(mod.tree)
    for node in ast.walk(mod.tree):
        if not isinstance(node, (ast.With, ast.AsyncWith)):
            continue
        locks = []
        for item in node.items:
            seg = _last_segment(item.context_expr)
            if seg and _LOCKISH.search(seg):
                locks.append(_dotted(item.context_expr) or seg)
        if not locks:
            continue
        for inner in _body_nodes(node):
            if not isinstance(inner, ast.Call):
                continue
            reason = _blocking_call_reason(inner, locks)
            if reason is None:
                continue
            findings.append(Finding(
                "TL001", mod.path, inner.lineno,
                quals.get(inner, "<module>"),
                f"blocking call {reason} while holding "
                f"{' + '.join(locks)}",
                "move the blocking call outside the with-block, or "
                "snapshot state under the lock and act on it after "
                "release"))
    return findings


# ---------------------------------------------------------------------------
# TL002: guarded-by lock discipline
# ---------------------------------------------------------------------------
_GUARDED_BY = re.compile(r"#\s*guarded-by:\s*([A-Za-z_][A-Za-z0-9_]*)")


def _guarded_decls(cls: ast.ClassDef,
                   lines: list[str]) -> dict[str, tuple[str, int]]:
    """``self.X = ...  # guarded-by: _lock`` declarations anywhere in the
    class body -> {attr: (lock_attr, decl_line)}."""
    decls: dict[str, tuple[str, int]] = {}
    for node in ast.walk(cls):
        if not isinstance(node, (ast.Assign, ast.AnnAssign)):
            continue
        targets = node.targets if isinstance(node, ast.Assign) \
            else [node.target]
        for t in targets:
            if (isinstance(t, ast.Attribute)
                    and isinstance(t.value, ast.Name)
                    and t.value.id == "self"):
                m = _GUARDED_BY.search(lines[node.lineno - 1]) \
                    if node.lineno - 1 < len(lines) else None
                if m:
                    decls[t.attr] = (m.group(1), node.lineno)
    return decls


def check_lock_discipline(mod: Module) -> list[Finding]:
    findings: list[Finding] = []
    for cls in ast.walk(mod.tree):
        if not isinstance(cls, ast.ClassDef):
            continue
        decls = _guarded_decls(cls, mod.lines)
        if not decls:
            continue
        for fn in cls.body:
            if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            if fn.name == "__init__":
                continue        # construction precedes sharing
            findings.extend(_scan_guarded_fn(mod, cls, fn, decls))
    return findings


def _scan_guarded_fn(mod: Module, cls: ast.ClassDef, fn: ast.AST,
                     decls: dict[str, tuple[str, int]]) -> list[Finding]:
    findings = []
    guarded_here: list[tuple[ast.AST, set[str]]] = []

    def locks_held_at(target: ast.AST) -> set[str]:
        held: set[str] = set()
        for scope, locks in guarded_here:
            if target in scope_members[id(scope)]:
                held |= locks
        return held

    # precompute with-block membership (lexical, excluding nested defs)
    scope_members: dict[int, set[ast.AST]] = {}
    for node in ast.walk(fn):
        if isinstance(node, (ast.With, ast.AsyncWith)):
            locks = set()
            for item in node.items:
                seg = _last_segment(item.context_expr)
                if seg:
                    locks.add(seg)
            if locks:
                guarded_here.append((node, locks))
                scope_members[id(node)] = set(_body_nodes(node))
    for node in ast.walk(fn):
        if not (isinstance(node, ast.Attribute)
                and isinstance(node.value, ast.Name)
                and node.value.id == "self" and node.attr in decls):
            continue
        lock, _decl_line = decls[node.attr]
        if lock in locks_held_at(node):
            continue
        findings.append(Finding(
            "TL002", mod.path, node.lineno,
            f"{cls.name}.{node.attr}",
            f"self.{node.attr} is declared guarded-by {lock} but "
            f"accessed outside `with self.{lock}`",
            f"wrap the access in `with self.{lock}:` (or snapshot the "
            f"value under the lock)"))
    return findings


# ---------------------------------------------------------------------------
# TL003: thread hygiene
# ---------------------------------------------------------------------------
def _thread_name_ok(call: ast.Call) -> tuple[bool, str]:
    for kw in call.keywords:
        if kw.arg != "name":
            continue
        v = kw.value
        if isinstance(v, ast.Constant) and isinstance(v.value, str):
            return v.value.startswith("tony-"), repr(v.value)
        if isinstance(v, ast.JoinedStr) and v.values:
            first = v.values[0]
            if isinstance(first, ast.Constant) \
                    and isinstance(first.value, str):
                return first.value.startswith("tony-"), \
                    f"f{first.value!r}..."
        return False, "<dynamic>"
    return False, "<unnamed>"


def _module_join_receivers(tree: ast.AST) -> set[str]:
    out = set()
    for node in ast.walk(tree):
        if (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr == "join"
                and not _is_string_join(node)):
            seg = _last_segment(node.func.value)
            if seg:
                out.add(seg)
    return out


def _loop_vars_over(tree: ast.AST, container: str) -> set[str]:
    """names bound by ``for v in <container>`` loops anywhere."""
    out = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.For) \
                and _last_segment(node.iter) == container \
                and isinstance(node.target, ast.Name):
            out.add(node.target.id)
    return out


def check_thread_hygiene(mod: Module) -> list[Finding]:
    findings: list[Finding] = []
    quals = _qualnames(mod.tree)
    joins = _module_join_receivers(mod.tree)
    # map Thread-call -> the name it (or its containing listcomp) binds
    bound: dict[ast.Call, str] = {}
    for node in ast.walk(mod.tree):
        if not isinstance(node, ast.Assign) or len(node.targets) != 1:
            continue
        target_seg = _last_segment(node.targets[0])
        if not target_seg:
            continue
        value = node.value
        calls = [value] if isinstance(value, ast.Call) else \
            [value.elt] if isinstance(value, ast.ListComp) \
            and isinstance(value.elt, ast.Call) else []
        for c in calls:
            bound[c] = target_seg
    for node in ast.walk(mod.tree):
        if not (isinstance(node, ast.Call)
                and _last_segment(node.func) == "Thread"):
            continue
        sym = quals.get(node, "<module>")
        ok, shown = _thread_name_ok(node)
        if not ok:
            findings.append(Finding(
                "TL003", mod.path, node.lineno, sym,
                f"thread name {shown} is not 'tony-'-prefixed",
                "pass name='tony-<role>' so stacks, `py-spy` and "
                "flight dumps attribute the thread"))
        daemon = any(kw.arg == "daemon"
                     and isinstance(kw.value, ast.Constant)
                     and kw.value.value is True
                     for kw in node.keywords)
        if daemon:
            continue
        target = bound.get(node)
        joined = target is not None and (
            target in joins
            or bool(_loop_vars_over(mod.tree, target) & joins))
        if not joined:
            findings.append(Finding(
                "TL003", mod.path, node.lineno, sym,
                "thread is neither daemon=True nor provably joined in "
                "this module",
                "pass daemon=True, or bind the thread and .join() it "
                "on every exit path"))
    return findings


# ---------------------------------------------------------------------------
# TL004: fd hygiene
# ---------------------------------------------------------------------------
_FD_FACTORIES = {"open", "socket", "create_connection", "socketpair"}


def _is_fd_factory(call: ast.Call) -> bool:
    seg = _last_segment(call.func)
    if seg not in _FD_FACTORIES:
        return False
    if seg == "socket":
        # socket.socket(...) / socket(...) — not e.g. x.socket attribute
        root = _dotted(call.func)
        return root in ("socket", "socket.socket")
    return True


def check_fd_hygiene(mod: Module) -> list[Finding]:
    findings: list[Finding] = []
    for fn in ast.walk(mod.tree):
        if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        findings.extend(_scan_fd_fn(mod, fn))
    return findings


def _scan_fd_fn(mod: Module, fn: ast.AST) -> list[Finding]:
    quals_prefix = fn.name
    opened: dict[str, int] = {}             # var -> lineno
    for node in ast.walk(fn):
        if (isinstance(node, ast.Assign) and len(node.targets) == 1
                and isinstance(node.targets[0], ast.Name)
                and isinstance(node.value, ast.Call)
                and _is_fd_factory(node.value)):
            opened[node.targets[0].id] = node.lineno
    if not opened:
        return []
    closed: set[str] = set()
    escaped: set[str] = set()
    for node in ast.walk(fn):
        if isinstance(node, ast.Call):
            if isinstance(node.func, ast.Attribute) \
                    and node.func.attr in ("close", "detach", "shutdown") \
                    and isinstance(node.func.value, ast.Name):
                closed.add(node.func.value.id)
            # ownership transfer: the fd passed to another call
            for arg in list(node.args) + [kw.value for kw in node.keywords]:
                for sub in ast.walk(arg):
                    if isinstance(sub, ast.Name) and sub.id in opened:
                        escaped.add(sub.id)
        elif isinstance(node, (ast.Return, ast.Yield, ast.YieldFrom)) \
                and node.value is not None:
            for sub in ast.walk(node.value):
                if isinstance(sub, ast.Name) and sub.id in opened:
                    escaped.add(sub.id)
        elif isinstance(node, ast.Assign):
            # stored on self / a container: lifetime managed elsewhere
            if isinstance(node.value, (ast.Name, ast.Tuple, ast.List,
                                       ast.Dict)):
                names = {s.id for s in ast.walk(node.value)
                         if isinstance(s, ast.Name)}
                if names & set(opened):
                    for t in node.targets:
                        if not isinstance(t, ast.Name):
                            escaped |= names & set(opened)
        elif isinstance(node, (ast.With, ast.AsyncWith)):
            for item in node.items:
                for sub in ast.walk(item.context_expr):
                    if isinstance(sub, ast.Name) and sub.id in opened:
                        closed.add(sub.id)      # contextlib.closing etc.
    out = []
    for var, line in sorted(opened.items(), key=lambda kv: kv[1]):
        if var in closed or var in escaped:
            continue
        out.append(Finding(
            "TL004", mod.path, line, f"{quals_prefix}:{var}",
            f"fd-bearing local {var!r} is never closed on any path in "
            f"this function",
            "use `with`, close in a try/finally, or hand ownership to "
            "an object that closes it"))
    return out


# ---------------------------------------------------------------------------
# TL005: broad except that eats the evidence
# ---------------------------------------------------------------------------
_LOG_METHODS = {"debug", "info", "warning", "warn", "error", "exception",
                "critical", "log"}
_LOG_RECEIVERS = {"log", "logger", "logging", "warnings", "traceback"}
_FLIGHT_METHODS = {"record", "dump"}


def _is_broad(handler: ast.ExceptHandler) -> bool:
    t = handler.type
    if t is None:
        return True
    names = [t] if not isinstance(t, ast.Tuple) else list(t.elts)
    return any(_last_segment(n) in ("Exception", "BaseException")
               for n in names)


def _handler_observes(handler: ast.ExceptHandler) -> bool:
    for node in _body_nodes(handler):
        if isinstance(node, ast.Raise):
            return True
        if not isinstance(node, ast.Call):
            continue
        func = node.func
        if isinstance(func, ast.Name) and func.id in (
                "print", "_flight_incident", "fail", "perror"):
            return True
        if not isinstance(func, ast.Attribute):
            continue
        recv_node = func.value
        if isinstance(recv_node, ast.Call):     # get_flight().record(...)
            recv_node = recv_node.func
        recv = _last_segment(recv_node) or ""
        if func.attr in _LOG_METHODS and (
                recv in _LOG_RECEIVERS or recv.endswith("log")
                or recv.endswith("logger")):
            return True
        if func.attr in ("print_exc", "format_exc", "warn"):
            return True
        if func.attr in _FLIGHT_METHODS and "flight" in recv.lower():
            return True
        if func.attr == "_flight_incident":
            return True
        if func.attr == "inc" and "reject" in ast.dump(func).lower():
            return True
    return False


def check_broad_except(mod: Module) -> list[Finding]:
    findings: list[Finding] = []
    quals = _qualnames(mod.tree)
    for node in ast.walk(mod.tree):
        if not isinstance(node, ast.ExceptHandler):
            continue
        if not _is_broad(node):
            continue
        if _handler_observes(node):
            continue
        shown = "bare except" if node.type is None else \
            f"except {_last_segment(node.type) or '...'}"
        findings.append(Finding(
            "TL005", mod.path, node.lineno,
            quals.get(node, "<module>"),
            f"{shown} neither re-raises, logs, nor flight-records",
            "narrow the exception type, or log/flight-record before "
            "swallowing"))
    return findings


# ---------------------------------------------------------------------------
# TL006: proto wire additivity
# ---------------------------------------------------------------------------
_MSG_RE = re.compile(r"^\s*message\s+(\w+)\s*\{")
_FIELD_RE = re.compile(
    r"^\s*(?:repeated\s+|optional\s+)?[\w.<>, ]+?\s+(\w+)\s*=\s*(\d+)\s*;")
_RESERVED_RE = re.compile(r"^\s*reserved\s+([\d,\s]+);")


def parse_proto(path: str) -> dict[str, dict[str, int]]:
    """tony.proto -> {message: {field: number}}. A hand regex parser is
    enough: the control-plane proto is proto3 with flat messages and no
    nesting, and staying dependency-free matters more than generality."""
    messages: dict[str, dict[str, int]] = {}
    current: str | None = None
    depth = 0
    with open(path, encoding="utf-8") as f:
        for raw in f:
            line = raw.split("//", 1)[0]
            m = _MSG_RE.match(line)
            if m and depth == 0:
                current = m.group(1)
                messages[current] = {}
            depth += line.count("{") - line.count("}")
            if depth <= 0:
                current = None
                depth = 0
                continue
            if current is None:
                continue
            fm = _FIELD_RE.match(line)
            if fm and not _MSG_RE.match(line):
                messages[current][fm.group(1)] = int(fm.group(2))
    return messages


def load_wire_manifest(path: str) -> dict[str, dict[str, int]] | None:
    if not os.path.exists(path):
        return None
    with open(path, encoding="utf-8") as f:
        doc = json.load(f)
    return {msg: {k: int(v) for k, v in fields.items()}
            for msg, fields in doc.get("messages", {}).items()}


def write_wire_manifest(path: str, proto: dict[str, dict[str, int]],
                        old: dict[str, dict[str, int]] | None) -> None:
    """Merge-regenerate: new fields/messages are added; fields REMOVED
    from the proto are retained so their numbers stay released forever
    (reuse stays detectable). A renumber is refused upstream — it can
    never be laundered through regeneration."""
    merged: dict[str, dict[str, int]] = {}
    for msg in sorted(set(proto) | set(old or {})):
        fields = dict((old or {}).get(msg, {}))
        fields.update(proto.get(msg, {}))
        merged[msg] = dict(sorted(fields.items(), key=lambda kv: kv[1]))
    doc = {
        "version": 1,
        "note": "Released proto wire shape (message -> field -> number)."
                " Maintained by `python -m tony_tpu.devtools.lint"
                " --update-wire-manifest`; removed fields are retained"
                " so their numbers stay reserved. Hand-edit only to"
                " renumber a field that never shipped.",
        "messages": merged,
    }
    with open(path, "w", encoding="utf-8") as f:
        json.dump(doc, f, indent=2, sort_keys=False)
        f.write("\n")


def check_proto_additivity(root: str = REPO_ROOT) -> list[Finding]:
    proto_path = os.path.join(root, PROTO_FILE)
    manifest_path = os.path.join(root, WIRE_MANIFEST)
    rel = PROTO_FILE.replace(os.sep, "/")
    proto = parse_proto(proto_path)
    findings: list[Finding] = []
    # intra-proto: duplicate numbers are corrupt regardless of history
    for msg, fields in proto.items():
        by_num: dict[int, str] = {}
        for name, num in fields.items():
            if num in by_num:
                findings.append(Finding(
                    "TL006", rel, 1, f"{msg}.{name}",
                    f"field number {num} used by both "
                    f"{by_num[num]!r} and {name!r} in message {msg}",
                    "give the new field the next free number"))
            by_num[num] = name
    manifest = load_wire_manifest(manifest_path)
    if manifest is None:
        findings.append(Finding(
            "TL006", rel, 1, "wire_manifest",
            f"no committed wire manifest at "
            f"{WIRE_MANIFEST.replace(os.sep, '/')}",
            "run `python -m tony_tpu.devtools.lint "
            "--update-wire-manifest` and commit the result"))
        return findings
    for msg, released in manifest.items():
        live = proto.get(msg, {})
        live_by_num = {num: name for name, num in live.items()}
        for name, num in released.items():
            if name in live and live[name] != num:
                findings.append(Finding(
                    "TL006", rel, 1, f"{msg}.{name}",
                    f"released field {msg}.{name} renumbered "
                    f"{num} -> {live[name]} (breaks every shipped "
                    f"peer)",
                    "restore the released number; add a NEW field for "
                    "new semantics"))
            elif name not in live and num in live_by_num:
                findings.append(Finding(
                    "TL006", rel, 1, f"{msg}.{live_by_num[num]}",
                    f"field number {num} (released as {msg}.{name}) "
                    f"reused by new field {live_by_num[num]!r} — old "
                    f"peers will misparse it",
                    "give the new field the next free number; released "
                    "numbers are reserved forever"))
    return findings


# ---------------------------------------------------------------------------
# TL007: frame/op dispatch exhaustiveness
# ---------------------------------------------------------------------------
_FRAME_SOURCES = (
    os.path.join("tony_tpu", "serving", "protocol.py"),
    os.path.join("tony_tpu", "channels", "channel.py"),
)


def _frame_constants(root: str) -> dict[str, tuple[str, int]]:
    """{const_name: (defining relpath, lineno)}. protocol.py's set is
    the FRAME_NAMES dict's keys (authoritative); channel.py's is its
    top-level ``CH_* = <int>`` constants."""
    consts: dict[str, tuple[str, int]] = {}
    proto_mod = load_module(os.path.join(root, _FRAME_SOURCES[0]))
    if proto_mod is not None:
        for node in ast.walk(proto_mod.tree):
            if (isinstance(node, ast.Assign)
                    and any(isinstance(t, ast.Name)
                            and t.id == "FRAME_NAMES"
                            for t in node.targets)
                    and isinstance(node.value, ast.Dict)):
                for k in node.value.keys:
                    if isinstance(k, ast.Name):
                        consts[k.id] = (proto_mod.path, k.lineno)
    chan_mod = load_module(os.path.join(root, _FRAME_SOURCES[1]))
    if chan_mod is not None:
        for node in chan_mod.tree.body:
            if (isinstance(node, ast.Assign)
                    and len(node.targets) == 1
                    and isinstance(node.targets[0], ast.Name)
                    and node.targets[0].id.startswith("CH_")
                    and isinstance(node.value, ast.Constant)
                    and isinstance(node.value.value, int)):
                consts[node.targets[0].id] = (chan_mod.path, node.lineno)
    return consts


def _dispatch_uses(mod: Module, names: set[str],
                   defining: dict[str, str]) -> set[str]:
    """Constants this module DISPATCHES on: used in a comparison,
    membership test, match-case, or as a dict key (dict keys only count
    outside the defining module — FRAME_NAMES itself is a name map, not
    a dispatch)."""
    used: set[str] = set()

    def note(node: ast.AST) -> None:
        seg = _last_segment(node)
        if seg in names:
            used.add(seg)

    for node in ast.walk(mod.tree):
        if isinstance(node, ast.Compare):
            for sub in [node.left] + node.comparators:
                note(sub)
                if isinstance(sub, (ast.Tuple, ast.List, ast.Set)):
                    for e in sub.elts:
                        note(e)
        elif isinstance(node, ast.MatchValue):
            note(node.value)
        elif isinstance(node, ast.Dict):
            for k in node.keys:
                if k is None:
                    continue
                seg = _last_segment(k)
                if seg in names and defining.get(seg) != mod.path:
                    used.add(seg)
    return used


def check_frame_exhaustiveness(root: str = REPO_ROOT,
                               modules: list[Module] | None = None
                               ) -> list[Finding]:
    consts = _frame_constants(root)
    if not consts:
        return []
    if modules is None:
        modules = scan_paths([os.path.join(root, "tony_tpu")])
    names = set(consts)
    defining = {n: p for n, (p, _) in consts.items()}
    used: set[str] = set()
    for mod in modules:
        if mod.path.startswith("tony_tpu/devtools/"):
            continue
        used |= _dispatch_uses(mod, names, defining)
    findings = []
    for name in sorted(names - used):
        path, line = consts[name]
        findings.append(Finding(
            "TL007", path, line, name,
            f"frame/op constant {name} has no dispatch arm anywhere "
            f"under tony_tpu/",
            "add the handler arm (or delete the dead constant)"))
    return findings


# ---------------------------------------------------------------------------
# TL008: observability bijections (metrics / events / config <-> docs)
# ---------------------------------------------------------------------------
#: string literals matching the series shape that are NOT metric series.
NON_SERIES = {"tony_pb2", "tony_tpu", "tony_src"}

_SERIES_LIT = re.compile(r"[\"'](tony_[a-z0-9_]+)[\"']")
_SERIES_FSTR = re.compile(r"f[\"'](tony_[a-z0-9_]*)\{")
#: ``f"{prefix}_seconds_total"`` — a registered-literal prefix plus a
#: dynamic suffix (metrics.py observe_phase_times style).
_SERIES_FSUFFIX = re.compile(r"f[\"']\{\w+\}(_[a-z0-9_]+)[\"']")
_DOC_SERIES = re.compile(r"(tony_[a-z0-9_]+)")
_EVENT_DECL = re.compile(r'^([A-Z][A-Z_]*) = "([A-Z][A-Z_]*)"',
                         flags=re.MULTILINE)
_DOC_EVENT_ROW = re.compile(r"^\|\s*`([A-Z][A-Z_]+)`\s*\|",
                            flags=re.MULTILINE)


def registered_series_names(root: str = REPO_ROOT
                            ) -> tuple[set[str], set[str], set[str]]:
    """(exact literals, truncated f-string prefixes, dynamic suffixes)
    of every ``tony_*`` series registered anywhere under tony_tpu/
    (devtools excluded — the linter's own fixtures are not the metrics
    plane)."""
    exact: set[str] = set()
    prefixes: set[str] = set()
    suffixes: set[str] = set()
    base = os.path.join(root, "tony_tpu")
    for dirpath, dirnames, files in os.walk(base):
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
        if os.path.basename(dirpath) == "devtools":
            dirnames[:] = []
            continue
        for fn in sorted(files):
            if not fn.endswith(".py"):
                continue
            src = open(os.path.join(dirpath, fn), encoding="utf-8").read()
            exact.update(_SERIES_LIT.findall(src))
            prefixes.update(_SERIES_FSTR.findall(src))
            suffixes.update(_SERIES_FSUFFIX.findall(src))
    return exact - NON_SERIES, prefixes, suffixes


def declared_event_types(root: str = REPO_ROOT) -> set[str]:
    """The SCREAMING_CASE ``NAME = "NAME"`` constants in
    events/events.py — the single registration point."""
    path = os.path.join(root, "tony_tpu", "events", "events.py")
    src = open(path, encoding="utf-8").read()
    return {value for name, value in _EVENT_DECL.findall(src)
            if name == value}


def config_key_constants(root: str = REPO_ROOT) -> tuple[set[str], dict]:
    """(*_KEY constant values, DEFAULTS dict) from conf/keys.py —
    imported, not parsed: keys.py is stdlib-only by design and the
    import keeps this in exact lockstep with the runtime."""
    from tony_tpu.conf import keys as K
    declared = {getattr(K, name) for name in dir(K)
                if name.endswith("_KEY")
                and isinstance(getattr(K, name), str)}
    return declared, dict(K.DEFAULTS)


def check_observability(root: str = REPO_ROOT,
                        facets: tuple[str, ...] = ("metrics", "events",
                                                   "config")
                        ) -> list[Finding]:
    findings: list[Finding] = []
    if "metrics" in facets:
        findings.extend(_check_metrics_docs(root))
    if "events" in facets:
        findings.extend(_check_events_docs(root))
    if "config" in facets:
        findings.extend(_check_config_docs(root))
    return findings


def _check_metrics_docs(root: str) -> list[Finding]:
    doc_rel = "docs/observability.md"
    doc = open(os.path.join(root, doc_rel), encoding="utf-8").read()
    exact, prefixes, suffixes = registered_series_names(root)
    findings = []
    if not exact:
        return [Finding("TL008", doc_rel, 1, "series-scan",
                        "series scan found nothing — the scanner "
                        "regressed", "fix registered_series_names")]
    # forward: every registered series (and every truncated f-string
    # prefix, e.g. tony_startup_) must appear in the docs table
    for name in sorted(set(n for n in exact if n not in doc)
                       | set(p for p in prefixes if p and p not in doc)):
        findings.append(Finding(
            "TL008", doc_rel, 1, name,
            f"series missing from docs/observability.md: {name}",
            "add a row to the metrics table (producer + meaning)"))
    # reverse: every series-shaped token the docs mention must be
    # registered somewhere — exactly, under a truncated f-prefix, as a
    # registered-prefix + dynamic-suffix composition, or as a docs
    # wildcard (``tony_serve_phase_*`` leaves a trailing-underscore
    # token) over real series
    doc_tokens = set(_DOC_SERIES.findall(doc)) - NON_SERIES
    for tok in sorted(doc_tokens):
        if tok in exact:
            continue
        if any(tok.startswith(p) for p in prefixes if p):
            continue
        if any(tok == lit + s for lit in exact for s in suffixes):
            continue                # f"{prefix}_seconds_total" style
        if any(lit.startswith(tok) or (lit + "_").startswith(tok)
               for lit in exact):
            continue                # docs wildcard like tony_serve_phase_*
        findings.append(Finding(
            "TL008", doc_rel, 1, tok,
            f"documented series {tok} is not registered anywhere under "
            f"tony_tpu/",
            "delete the stale docs row (or register the series)"))
    return findings


def _check_events_docs(root: str) -> list[Finding]:
    doc_rel = "docs/observability.md"
    doc = open(os.path.join(root, doc_rel), encoding="utf-8").read()
    types = declared_event_types(root)
    findings = []
    for t in sorted(x for x in types if x not in doc):
        findings.append(Finding(
            "TL008", doc_rel, 1, t,
            f"event types missing from docs/observability.md: {t}",
            "add a row to the jhist event-type table"))
    for t in sorted(set(_DOC_EVENT_ROW.findall(doc)) - types):
        findings.append(Finding(
            "TL008", doc_rel, 1, t,
            f"documented event type {t} is not declared in "
            f"events/events.py",
            "delete the stale docs row (or declare the constant)"))
    return findings


def _check_config_docs(root: str) -> list[Finding]:
    doc_rel = "docs/configuration.md"
    doc = open(os.path.join(root, doc_rel), encoding="utf-8").read()
    doc = doc.replace("\\|", "|")   # markdown-escaped | in defaults
    declared, defaults = config_key_constants(root)
    keys_rel = "tony_tpu/conf/keys.py"
    findings = []
    for k in sorted(declared - set(defaults)):
        findings.append(Finding(
            "TL008", keys_rel, 1, k,
            f"keys.py *_KEY constants and DEFAULTS registry out of "
            f"sync: missing defaults={{{k!r}}}",
            "add the key to DEFAULTS"))
    for k in sorted(set(defaults) - declared):
        findings.append(Finding(
            "TL008", keys_rel, 1, k,
            f"keys.py *_KEY constants and DEFAULTS registry out of "
            f"sync: orphan defaults={{{k!r}}}",
            "declare a *_KEY constant (or delete the default)"))
    for k in sorted(x for x in defaults if x not in doc):
        findings.append(Finding(
            "TL008", doc_rel, 1, k,
            f"undocumented config keys: [{k!r}]",
            "add a row to docs/configuration.md"))
    for suffix in ("instances", "memory", "vcores", "gpus", "tpus",
                   "tpu.topology", "resources"):
        if f"tony.<job>.{suffix}" not in doc:
            findings.append(Finding(
                "TL008", doc_rel, 1, f"tony.<job>.{suffix}",
                f"dynamic key tony.<job>.{suffix} undocumented",
                "add the dynamic-key row to docs/configuration.md"))
    return findings


# ---------------------------------------------------------------------------
# baseline
# ---------------------------------------------------------------------------
def load_baseline(path: str) -> list[dict]:
    if not os.path.exists(path):
        return []
    with open(path, encoding="utf-8") as f:
        doc = json.load(f)
    return list(doc.get("suppressions", []))


def apply_baseline(findings: list[Finding], suppressions: list[dict]
                   ) -> tuple[list[Finding], int, list[dict]]:
    """-> (surviving findings, suppressed count, stale entries)."""
    keys = {(s.get("checker"), s.get("path"), s.get("symbol"))
            for s in suppressions}
    hit: set[tuple] = set()
    out = []
    for f in findings:
        if f.key in keys:
            hit.add(f.key)
        else:
            out.append(f)
    stale = [s for s in suppressions
             if (s.get("checker"), s.get("path"), s.get("symbol"))
             not in hit]
    return out, len(findings) - len(out), stale


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------
PER_FILE_CHECKERS = (check_blocking_under_lock, check_lock_discipline,
                     check_thread_hygiene, check_fd_hygiene,
                     check_broad_except)


def run_per_file_checkers(mod: Module) -> list[Finding]:
    out: list[Finding] = []
    for checker in PER_FILE_CHECKERS:
        out.extend(checker(mod))
    return out


def run(paths: list[str], *, root: str = REPO_ROOT,
        repo_checks: bool | None = None) -> list[Finding]:
    """All findings (un-baselined) for ``paths``. Repo-wide checkers
    (TL006/TL007/TL008) run when the scan covers the real tony_tpu
    package (auto), or per ``repo_checks``."""
    modules = scan_paths(paths)
    findings: list[Finding] = []
    for mod in modules:
        findings.extend(run_per_file_checkers(mod))
    if repo_checks is None:
        pkg = os.path.join(os.path.abspath(root), "tony_tpu") + os.sep
        repo_checks = any(m.abspath.startswith(pkg) for m in modules)
    if repo_checks:
        findings.extend(check_proto_additivity(root))
        findings.extend(check_frame_exhaustiveness(root, modules))
        findings.extend(check_observability(root))
    findings.sort(key=lambda f: (f.path, f.line, f.checker, f.symbol))
    return findings


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m tony_tpu.devtools.lint",
        description="tonylint: AST invariant checker "
                    "(docs/static-analysis.md)")
    ap.add_argument("paths", nargs="*",
                    help="files/dirs to lint (default: tony_tpu/)")
    ap.add_argument("--baseline",
                    default=os.path.join(REPO_ROOT, DEFAULT_BASELINE),
                    help="suppression baseline JSON")
    ap.add_argument("--no-baseline", action="store_true",
                    help="report pre-existing findings too")
    ap.add_argument("--json", action="store_true", dest="as_json",
                    help="machine-readable findings")
    ap.add_argument("--update-wire-manifest", action="store_true",
                    help="fold added proto fields into wire_manifest."
                         "json (renumbers/reuses still refuse)")
    args = ap.parse_args(argv)

    if args.update_wire_manifest:
        proto_path = os.path.join(REPO_ROOT, PROTO_FILE)
        manifest_path = os.path.join(REPO_ROOT, WIRE_MANIFEST)
        bad = [f for f in (check_proto_additivity(REPO_ROOT)
                           if os.path.exists(manifest_path) else [])
               if f.symbol != "wire_manifest"]
        if bad:
            for f in bad:
                print(f.render(), file=sys.stderr)
            print("tonylint: refusing to update the manifest over a "
                  "renumber/reuse — fix the proto first",
                  file=sys.stderr)
            return 1
        old = load_wire_manifest(manifest_path)
        write_wire_manifest(manifest_path, parse_proto(proto_path), old)
        print(f"tonylint: wire manifest updated at "
              f"{_relpath(manifest_path)}")
        return 0

    paths = args.paths or [os.path.join(REPO_ROOT, "tony_tpu")]
    findings = run(paths)
    if not args.no_baseline:
        findings, suppressed, stale = apply_baseline(
            findings, load_baseline(args.baseline))
        # an entry is only stale if its file was actually scanned —
        # linting a subset must not condemn the rest of the baseline
        scanned = [_relpath(p).rstrip("/") for p in paths]
        stale = [s for s in stale
                 if any(str(s.get("path", "")).startswith(sp)
                        for sp in scanned)]
        if stale:
            names = ", ".join(f"{s.get('checker')}:{s.get('symbol')}"
                              for s in stale[:8])
            print(f"tonylint: {len(stale)} stale baseline "
                  f"entr{'y' if len(stale) == 1 else 'ies'} no longer "
                  f"match anything ({names}) — safe to delete",
                  file=sys.stderr)
    if args.as_json:
        print(json.dumps([dataclasses.asdict(f) for f in findings],
                         indent=2))
    else:
        for f in findings:
            print(f.render())
    if findings:
        print(f"tonylint: {len(findings)} finding"
              f"{'s' if len(findings) != 1 else ''} "
              f"(suppress via {_relpath(args.baseline)} only for "
              f"pre-existing debt — the baseline only ratchets down)",
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
