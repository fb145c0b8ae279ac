"""threadmodel — whole-program thread-role propagation engine.

Every concurrency check in this repo used to grow its own call graph
(the PR-3 fast-dispatch graph, the PR-6 device-worker graph) and its
own root discovery.  This module is the shared engine: it discovers
the REAL concurrency roots of the program — the spawn sites where a
thread lane begins — assigns each a role, and propagates role sets
through the call graph, including callback-registration edges
(``call_soon``, ``add_done_callback``, ``on_commit=``) whose targets
run on a lane the registering code does not own.

Roles (one per lane the runtime actually spawns):

  loop           asyncio messenger event loop: every ``async def``,
                 ``ms_dispatch`` of fast-dispatching classes, and
                 callbacks scheduled via ``call_soon``/``call_later``/
                 ``_loop_call``
  device_worker  ``StripeBatchQueue._worker`` — the one thread that
                 talks to the device, plus ``add_done_callback``
                 closures (stripe futures resolve ON this thread)
  shard_worker   ``ShardedWorkQueue`` shard threads and the
                 ``process=`` callbacks handed to them
  fanout         the backend's ``ThreadPoolExecutor`` fan-out lane
                 (``...executor().submit(fn)``)
  commit         the store ``CommitPipeline`` group-commit thread:
                 its ``_run`` loop, the ``sync_fn`` ctor arg, and
                 every ``on_commit=`` completion it fires
  timer          tick/sweep/watchdog/heartbeat/scrub threads
  thread         any other ``threading.Thread(target=...)`` target
  main           not a spawned lane: functions reachable from no root

Spawn sites CUT propagation: ``threading.Thread(target=f)`` makes f a
fresh root of its own role — the caller's role does not leak into it
(that handoff is exactly the PR-5 fix: decode completions run on fresh
threads so neither the device worker nor the network lanes take pg
locks).  Callback registrations PROPAGATE instead: the callback runs
on the lane that invokes it, not the lane that registered it.

On top of the role map sits a per-role capability lattice (DENIED_CAPS)
the lane-shaped checks share: may-block, may-take-pg-lock, may-d2h,
may-compile.  ``no-blocking-on-loop`` is (loop, may-block),
``no-d2h-on-hot-path`` is (loop|device, may-d2h), ``lane-capability``
enforces the rest.

Known limits (deliberate, conservative): nested function defs and
lambdas are not call-graph nodes — a closure handed to a spawn site is
followed only when it resolves to an indexed function, so an
unresolvable target is silently not analyzed rather than guessed.
"""

from __future__ import annotations

import ast
import re
from typing import Dict, Iterator, List, Optional, Sequence, Set, Tuple

from ceph_tpu.analysis.framework import SourceFile, call_name, dotted

# -- roles -------------------------------------------------------------------

ROLE_LOOP = "loop"
ROLE_DEVICE = "device_worker"
ROLE_SHARD = "shard_worker"
ROLE_FANOUT = "fanout"
ROLE_COMMIT = "commit"
ROLE_TIMER = "timer"
ROLE_THREAD = "thread"
ROLE_MAIN = "main"

ALL_ROLES = (ROLE_LOOP, ROLE_DEVICE, ROLE_SHARD, ROLE_FANOUT,
             ROLE_COMMIT, ROLE_TIMER, ROLE_THREAD)

# -- capabilities ------------------------------------------------------------

CAP_BLOCK = "may-block"
CAP_PG_LOCK = "may-take-pg-lock"
CAP_D2H = "may-d2h"
CAP_COMPILE = "may-compile"

# Capabilities each role LACKS.  A role absent here may do anything.
# loop: the messenger event loop reads every peer's frames — blocking
#   it is a cluster-wide liveness hang (PR 1/2/3), d2h on it is a
#   device round trip per frame (PR 6), a pg lock on it is the PR-5 deadlock lane, and
#   an XLA compile on it is a multi-second stall (PR 10 measured 89%
#   of a workload's wall inside compiles).
# device_worker: must get straight back to coalescing — pg locks on it
#   deadlock against lanes that hold the pg lock while waiting on a
#   stripe future (PR 5); payload d2h re-introduces the round trip.
#   It MAY compile (dispatch is where compiles happen) and MAY block
#   (its whole job is draining a queue).
DENIED_CAPS: Dict[str, Tuple[str, ...]] = {
    ROLE_LOOP: (CAP_BLOCK, CAP_PG_LOCK, CAP_D2H, CAP_COMPILE),
    ROLE_DEVICE: (CAP_PG_LOCK, CAP_D2H),
}

_SCHED_ARG0 = {"call_soon", "call_soon_threadsafe", "_loop_call"}
_SCHED_ARG1 = {"call_later", "call_at"}
_TIMER_NAME_RE = re.compile(
    r"tick|sweep|watchdog|timer|heartbeat|\bhb\b|hb_loop|scrub|renew|"
    r"ticker|deadline", re.IGNORECASE)

# well-known lane entry points that exist whether or not any spawn
# site resolves statically (module-qualified so test fixtures written
# AS these modules get the same roots the real tree does)
_FIXED_ROOTS: Tuple[Tuple[str, str], ...] = (
    (ROLE_DEVICE, "ceph_tpu.tpu.queue:StripeBatchQueue._worker"),
    (ROLE_SHARD, "ceph_tpu.core.workqueue:ShardedWorkQueue._worker"),
    (ROLE_COMMIT, "ceph_tpu.store.objectstore:CommitPipeline._run"),
)


def body_walk(fn: ast.AST) -> Iterator[ast.AST]:
    """Walk a function body without descending into nested defs or
    lambdas — those only run if somebody calls them, and then the call
    site is the finding."""
    stack = list(ast.iter_child_nodes(fn))
    while stack:
        n = stack.pop()
        yield n
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef,
                          ast.Lambda)):
            continue
        stack.extend(ast.iter_child_nodes(n))


def awaited_calls(fn: ast.AST) -> Set[int]:
    return {id(n.value) for n in body_walk(fn)
            if isinstance(n, ast.Await) and isinstance(n.value, ast.Call)}


def returns_false_only(fn: ast.FunctionDef) -> bool:
    body = [st for st in fn.body
            if not (isinstance(st, ast.Expr)
                    and isinstance(st.value, ast.Constant)
                    and isinstance(st.value.value, str))]
    return (len(body) == 1 and isinstance(body[0], ast.Return)
            and isinstance(body[0].value, ast.Constant)
            and body[0].value.value is False)


# -- program index -----------------------------------------------------------

class ClassInfo:
    def __init__(self, node: ast.ClassDef) -> None:
        self.node = node
        self.bases = [dotted(b) for b in node.bases]
        self.methods: Dict[str, ast.AST] = {
            n.name: n for n in node.body
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))}


class Module:
    def __init__(self, f: SourceFile) -> None:
        self.file = f
        self.modname = f.rel[:-3].replace("/", ".")
        self.funcs: Dict[str, ast.AST] = {}       # module-level defs
        self.classes: Dict[str, ClassInfo] = {}
        self.imports: Dict[str, str] = {}          # local -> module
        self.from_imports: Dict[str, Tuple[str, str]] = {}
        for node in f.tree.body:
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                self.funcs[node.name] = node
            elif isinstance(node, ast.ClassDef):
                self.classes[node.name] = ClassInfo(node)
        for node in ast.walk(f.tree):
            if isinstance(node, ast.Import):
                for alias in node.names:
                    self.imports[alias.asname
                                 or alias.name.split(".")[0]] = alias.name
            elif isinstance(node, ast.ImportFrom) and node.module:
                for alias in node.names:
                    self.from_imports[alias.asname or alias.name] = (
                        node.module, alias.name)


class FuncInfo:
    """One analyzable function with its lexical context."""

    def __init__(self, mod: Module, cls: Optional[str],
                 name: str, node: ast.AST) -> None:
        self.mod = mod
        self.cls = cls
        self.name = name
        self.node = node

    @property
    def qual(self) -> str:
        return f"{self.mod.modname}:{self.local}"

    @property
    def local(self) -> str:
        return f"{self.cls}.{self.name}" if self.cls else self.name


# built Programs are cached by the identity of their parse trees: the
# trees live forever in the framework's AST cache, so ids are stable,
# and five lane-shaped checks per run would otherwise re-walk every
# module five times
_PROGRAM_CACHE: Dict[Tuple[int, ...], "Program"] = {}


class Program:
    """Whole-program index: modules, classes, functions, and the
    conservative call resolution every lane-shaped check shares."""

    def __init__(self, files: Sequence[SourceFile]) -> None:
        self.mods: Dict[str, Module] = {
            m.modname: m for m in (Module(f) for f in files)}
        self.index: Dict[str, FuncInfo] = {}
        for mod in self.mods.values():
            for name, node in mod.funcs.items():
                fn = FuncInfo(mod, None, name, node)
                self.index[fn.qual] = fn
            for cname, cls in mod.classes.items():
                for mname, node in cls.methods.items():
                    fn = FuncInfo(mod, cname, mname, node)
                    self.index[fn.qual] = fn

    @classmethod
    def of(cls, files: Sequence[SourceFile]) -> "Program":
        key = tuple(id(f.tree) for f in files)
        hit = _PROGRAM_CACHE.get(key)
        if hit is None:
            hit = _PROGRAM_CACHE[key] = cls(files)
        return hit

    # -- resolution (deliberately conservative: unresolvable targets
    # are not followed rather than guessed) ------------------------------
    def resolve_call(self, fn: FuncInfo, cn: str) -> Optional[FuncInfo]:
        if not cn:
            return None
        parts = cn.split(".")
        mod = fn.mod
        if parts[0] == "self" and len(parts) == 2 and fn.cls:
            return self.resolve_method(mod, fn.cls, parts[1])
        if len(parts) == 1:
            if parts[0] in mod.funcs:
                return FuncInfo(mod, None, parts[0], mod.funcs[parts[0]])
            fi = mod.from_imports.get(parts[0])
            if fi:
                src = self.mods.get(fi[0])
                if src and fi[1] in src.funcs:
                    return FuncInfo(src, None, fi[1], src.funcs[fi[1]])
            return None
        if len(parts) == 2:
            target_mod = self.mods.get(mod.imports.get(parts[0], ""))
            if target_mod is None:
                # module alias: `from pkg import mod as alias`
                fi = mod.from_imports.get(parts[0])
                if fi:
                    target_mod = self.mods.get(f"{fi[0]}.{fi[1]}")
            if target_mod and parts[1] in target_mod.funcs:
                return FuncInfo(target_mod, None, parts[1],
                                target_mod.funcs[parts[1]])
        return None

    def resolve_method(self, mod: Module, cname: str, mname: str,
                       depth: int = 0) -> Optional[FuncInfo]:
        if depth > 8:
            return None
        cls = mod.classes.get(cname)
        if cls is None:
            return None
        if mname in cls.methods:
            return FuncInfo(mod, cname, mname, cls.methods[mname])
        for base in cls.bases:
            bname = base.split(".")[-1]
            if bname in mod.classes and bname != cname:
                hit = self.resolve_method(mod, bname, mname, depth + 1)
                if hit is not None:
                    return hit
            fi = mod.from_imports.get(bname)
            if fi:
                src = self.mods.get(fi[0])
                if src and fi[1] in src.classes:
                    hit = self.resolve_method(src, fi[1], mname,
                                              depth + 1)
                    if hit is not None:
                        return hit
        return None

    def edges(self, fn: FuncInfo) -> List[FuncInfo]:
        out: List[FuncInfo] = []
        for node in body_walk(fn.node):
            if isinstance(node, ast.Call):
                target = self.resolve_call(fn, call_name(node))
                if target is not None:
                    out.append(target)
        return out


# -- the role engine ---------------------------------------------------------

_MODEL_CACHE: Dict[Tuple[int, ...], "ThreadModel"] = {}


class ThreadModel:
    """Role roots + per-role reachability with parent pointers (for
    example chains in violation messages)."""

    def __init__(self, program: Program) -> None:
        self.program = program
        # role -> root qual -> why (spawn-site description)
        self.roots: Dict[str, Dict[str, str]] = {r: {} for r in ALL_ROLES}
        self._find_roots()
        # role -> {qual: parent qual or None for roots}
        self.reach: Dict[str, Dict[str, Optional[str]]] = {}
        for role in ALL_ROLES:
            self.reach[role] = self._propagate(self.roots[role])

    @classmethod
    def of(cls, files: Sequence[SourceFile]) -> "ThreadModel":
        key = tuple(id(f.tree) for f in files)
        hit = _MODEL_CACHE.get(key)
        if hit is None:
            hit = _MODEL_CACHE[key] = cls(Program.of(files))
        return hit

    # -- queries ----------------------------------------------------------
    def roles_of(self, qual: str) -> Set[str]:
        out = {r for r in ALL_ROLES if qual in self.reach[r]}
        return out or {ROLE_MAIN}

    def chain(self, role: str, qual: str) -> List[str]:
        """Example call chain root..qual as local names."""
        parent = self.reach[role]
        names: List[str] = []
        cur: Optional[str] = qual
        while cur is not None:
            fn = self.program.index.get(cur)
            names.append(fn.local if fn is not None else cur)
            cur = parent.get(cur)
        names.reverse()
        return names

    # -- roots ------------------------------------------------------------
    def _add_root(self, role: str, qual: str, why: str) -> None:
        if qual in self.program.index:
            self.roots[role].setdefault(qual, why)

    def _find_roots(self) -> None:
        prog = self.program
        for role, qual in _FIXED_ROOTS:
            self._add_root(role, qual, "lane entry point")
        for fn in prog.index.values():
            if isinstance(fn.node, ast.AsyncFunctionDef):
                self._add_root(ROLE_LOOP, fn.qual, "async def")
        # fast-dispatching classes: their ms_dispatch runs inline on
        # the messenger event loop
        for mod in prog.mods.values():
            for cname, cls in mod.classes.items():
                can = cls.methods.get("ms_can_fast_dispatch")
                if can is None or returns_false_only(can):
                    continue
                disp = prog.resolve_method(mod, cname, "ms_dispatch")
                if disp is not None:
                    self._add_root(ROLE_LOOP, disp.qual,
                                   f"{cname}.ms_can_fast_dispatch")
        # registration sites: walk FULL bodies (lambdas and nested
        # defs included — a registration inside a closure is still a
        # registration once the closure runs)
        for fn in list(prog.index.values()):
            for node in ast.walk(fn.node):
                if isinstance(node, ast.Call):
                    self._scan_registration(fn, node)

    def _scan_registration(self, fn: FuncInfo, node: ast.Call) -> None:
        cn = call_name(node)
        base = cn.split(".")[-1]
        site = f"{fn.local}:{node.lineno}"

        def resolve(arg: Optional[ast.AST]) -> Optional[FuncInfo]:
            if arg is None:
                return None
            return self.program.resolve_call(fn, dotted(arg))

        # loop-scheduled callbacks
        arg = None
        if base in _SCHED_ARG0 and node.args:
            arg = node.args[0]
        elif base in _SCHED_ARG1 and len(node.args) > 1:
            arg = node.args[1]
        t = resolve(arg)
        if t is not None:
            self._add_root(ROLE_LOOP, t.qual, f"scheduled at {site}")
            return

        # ad-hoc threads: target= names the lane's entry
        if base == "Thread":
            target = None
            tname = ""
            for kw in node.keywords:
                if kw.arg == "target":
                    target = kw.value
                elif kw.arg == "name" and isinstance(
                        kw.value, (ast.Constant, ast.JoinedStr)):
                    tname = ast.unparse(kw.value)
            t = resolve(target)
            if t is not None:
                role = (ROLE_TIMER
                        if (_TIMER_NAME_RE.search(t.name)
                            or _TIMER_NAME_RE.search(tname))
                        else ROLE_THREAD)
                self._add_root(role, t.qual, f"Thread() at {site}")
            return

        # sharded work queue: the process callback runs on shard
        # workers; so do items enqueued via wq.queue(token, item)
        if base == "ShardedWorkQueue":
            target = None
            if len(node.args) > 2:
                target = node.args[2]
            for kw in node.keywords:
                if kw.arg == "process":
                    target = kw.value
            t = resolve(target)
            if t is not None:
                self._add_root(ROLE_SHARD, t.qual, f"process= at {site}")
            return
        if base == "queue" and len(node.args) > 1:
            owner = cn.split(".")[-2] if "." in cn else ""
            if "wq" in owner:
                t = resolve(node.args[1])
                if t is not None:
                    self._add_root(ROLE_SHARD, t.qual,
                                   f"wq.queue at {site}")
            return

        # commit pipeline: ctor sync_fn + every on_commit completion
        if base == "CommitPipeline" and node.args:
            t = resolve(node.args[0])
            if t is not None:
                self._add_root(ROLE_COMMIT, t.qual, f"sync_fn at {site}")
            return
        for kw in node.keywords:
            if kw.arg == "on_commit":
                t = resolve(kw.value)
                if t is not None:
                    self._add_root(ROLE_COMMIT, t.qual,
                                   f"on_commit= at {site}")

        # executor fan-out vs pipeline.submit(seq, cb)
        if base == "submit" and node.args:
            owner = cn.split(".")[-2] if "." in cn else ""
            if "pipeline" in owner:
                if len(node.args) > 1:
                    t = resolve(node.args[1])
                    if t is not None:
                        self._add_root(ROLE_COMMIT, t.qual,
                                       f"pipeline.submit at {site}")
            else:
                t = resolve(node.args[0])
                if t is not None:
                    self._add_root(ROLE_FANOUT, t.qual,
                                   f"submit at {site}")
            return

        # future callbacks: stripe futures resolve on the device
        # worker (set_result runs registered callbacks inline)
        if base == "add_done_callback" and node.args:
            t = resolve(node.args[0])
            if t is not None:
                self._add_root(ROLE_DEVICE, t.qual,
                               f"add_done_callback at {site}")

    # -- propagation ------------------------------------------------------
    def _propagate(self, roots: Dict[str, str]
                   ) -> Dict[str, Optional[str]]:
        prog = self.program
        parent: Dict[str, Optional[str]] = {q: None for q in roots}
        frontier = list(roots)
        while frontier:
            q = frontier.pop()
            fn = prog.index.get(q)
            if fn is None:
                continue
            for callee in prog.edges(fn):
                if callee.qual not in parent:
                    parent[callee.qual] = q
                    frontier.append(callee.qual)
        return parent
