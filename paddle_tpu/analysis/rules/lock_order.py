"""PT-LOCK — cross-module lock-acquisition graph must stay acyclic.

Ten threaded modules (pipeline, trace writer/ring, metrics registry +
reporter, the metrics HTTP endpoint, master client, stat timers, the
logger's warn-once table) now interleave under locks.  Two code paths
that acquire the same pair of locks in opposite orders are a deadlock
waiting for the right two threads — and unlike a thread leak, nothing
at runtime flags the hazard until it fires.

This rule derives the acquisition graph statically:

- **nodes** are lock identities: the literal name of a
  ``named_lock("...")`` / ``named_condition("...")`` creation
  (:mod:`paddle_tpu.analysis.lockorder` — the same node names the
  runtime checker uses), or a ``module.Class.attr`` synthetic for a raw
  ``threading.Lock()``;
- **edges** come from lexical ``with a: ... with b:`` nesting, plus
  interprocedural reach: a call made while holding ``a`` to a function
  whose transitive may-acquire set contains ``b`` adds ``a -> b``
  (may-acquire is a fixpoint over the conservatively-resolved call
  graph, so only statically certain paths contribute);
- a **cycle** in the graph is the finding, reported once per cycle
  with every witnessing site;
- holding a *module-level singleton* lock while calling a function
  that (transitively) re-acquires the same lock is reported as a
  self-deadlock (instance locks are exempt — two instances of one
  class are distinct locks under one node name).

:func:`build_lock_graph` exposes the derived graph for the CLI's
``--lock-graph`` dump — the hierarchy asserted at runtime by the
chaos/pipeline suites.
"""

from __future__ import annotations

import ast
import os
from typing import Dict, List, Optional, Set, Tuple

from ..callgraph import FunctionInfo, ModuleInfo, Project, dotted_name
from ..engine import Finding

RULE = "PT-LOCK"

_CTORS = {"Lock", "RLock", "Condition"}
_NAMED = {"named_lock", "named_condition"}


# -------------------------------------------------------- lock registry
class _Locks:
    """Every statically-known lock creation in the project."""

    def __init__(self) -> None:
        self.module: Dict[Tuple[str, str], str] = {}   # (mod, var) -> id
        self.cls: Dict[Tuple[str, str, str], str] = {}  # (mod,C,attr)->id
        self.local: Dict[Tuple[str, str, str], str] = {}  # (mod,fn,var)
        self.singletons: Set[str] = set()   # ids with exactly one
        #                                     module-level instance

    def resolve_name(self, mod: ModuleInfo, fn: Optional[FunctionInfo],
                     name: str) -> Optional[str]:
        cur = fn
        while cur is not None:
            lid = self.local.get((mod.name, cur.qualname, name))
            if lid is not None:
                return lid
            cur = mod.functions.get(cur.parent) if cur.parent else None
        return self.module.get((mod.name, name))

    def resolve_self_attr(self, mod: ModuleInfo, cls: Optional[str],
                          attr: str) -> Optional[str]:
        if cls is not None:
            lid = self.cls.get((mod.name, cls, attr))
            if lid is not None:
                return lid
        # unique definition anywhere in the module (covers inheritance
        # inside one file, e.g. subclasses using a base's self._lock)
        hits = {v for (m, _, a), v in self.cls.items()
                if m == mod.name and a == attr}
        return hits.pop() if len(hits) == 1 else None


def _ctor_lock_id(project: Project, mod: ModuleInfo,
                  node: ast.AST) -> Optional[str]:
    """Lock id for a creation expression: the literal of a
    ``named_lock``/``named_condition`` call, ``""`` (anonymous — caller
    names it from the assignment target) for a raw ``threading``
    constructor, None for anything else."""
    if not isinstance(node, ast.Call):
        return None
    chain = dotted_name(node.func)
    if chain is None:
        return None
    leaf = chain.split(".")[-1]
    if leaf in _NAMED:
        if node.args and isinstance(node.args[0], ast.Constant) \
                and isinstance(node.args[0].value, str):
            return node.args[0].value
        return ""
    if leaf in _CTORS:
        root = chain.split(".")[0]
        if root == leaf:
            return "" if mod.from_imports.get(
                leaf, ("", ""))[0] == "threading" else None
        return "" if project.names_module(mod, root, "threading") \
            else None
    return None


def _collect_locks(project: Project) -> _Locks:
    locks = _Locks()
    module_counts: Dict[str, int] = {}
    for mod in project.iter_modules():
        for node in ast.walk(mod.tree):
            value = None
            target = None
            if isinstance(node, ast.Assign) and len(node.targets) == 1:
                target, value = node.targets[0], node.value
            elif isinstance(node, ast.AnnAssign) and node.value is not None:
                target, value = node.target, node.value
            if value is None:
                continue
            # dataclass field(default_factory=<lock factory>)
            if isinstance(value, ast.Call) \
                    and dotted_name(value.func) is not None \
                    and dotted_name(value.func).split(".")[-1] == "field":
                for kw in value.keywords:
                    if kw.arg != "default_factory":
                        continue
                    factory = kw.value
                    if isinstance(factory, ast.Lambda):
                        factory = factory.body
                    lid = _ctor_lock_id(project, mod, factory)
                    if lid is None and isinstance(factory, (ast.Name,
                                                            ast.Attribute)):
                        chain = dotted_name(factory)
                        if chain and chain.split(".")[-1] in _CTORS:
                            lid = ""
                    if lid is not None:
                        value = None    # consumed; register below
                        cls = _enclosing_class_of(mod, node)
                        if cls is not None \
                                and isinstance(target, ast.Name):
                            name = lid or (f"{mod.short()}.{cls}"
                                           f".{target.id}")
                            locks.cls[(mod.name, cls, target.id)] = name
                    break
            if value is None:
                continue
            lid = _ctor_lock_id(project, mod, value)
            if lid is None:
                continue
            owner = _owner_of(mod, node)
            if isinstance(target, ast.Name):
                if owner is None:                       # module level
                    name = lid or f"{mod.short()}.{target.id}"
                    locks.module[(mod.name, target.id)] = name
                    module_counts[name] = module_counts.get(name, 0) + 1
                else:                                   # function local
                    name = lid or (f"{mod.short()}.{owner.qualname}"
                                   f".{target.id}")
                    locks.local[(mod.name, owner.qualname,
                                 target.id)] = name
            elif isinstance(target, ast.Attribute) \
                    and isinstance(target.value, ast.Name) \
                    and target.value.id == "self" \
                    and owner is not None and owner.class_name:
                cls = owner.class_name
                name = lid or f"{mod.short()}.{cls}.{target.attr}"
                locks.cls[(mod.name, cls, target.attr)] = name
    locks.singletons = {n for n, c in module_counts.items() if c == 1}
    return locks


def _enclosing_class_of(mod: ModuleInfo, node: ast.AST) -> Optional[str]:
    """Class whose body directly contains ``node`` (for dataclass
    field annotations)."""
    for n in ast.walk(mod.tree):
        if isinstance(n, ast.ClassDef) and node in n.body:
            return n.name
    return None


def _owner_of(mod: ModuleInfo, stmt: ast.AST) -> Optional[FunctionInfo]:
    """Innermost function whose body contains ``stmt`` (None = module
    level / class body)."""
    best: Optional[FunctionInfo] = None
    for fn in mod.functions.values():
        for n in ast.walk(fn.node):
            if n is stmt:
                if best is None \
                        or len(fn.qualname) > len(best.qualname):
                    best = fn
                break
    return best


# ---------------------------------------------------------- graph build
Site = Tuple[str, int]          # (abs path, line)


class LockGraph:
    def __init__(self) -> None:
        self.edges: Dict[Tuple[str, str], Site] = {}   # first witness
        self.adj: Dict[str, Set[str]] = {}
        self.self_deadlocks: List[Tuple[str, Site, str]] = []

    def add(self, src: str, dst: str, site: Site) -> None:
        if src == dst:
            return
        self.adj.setdefault(src, set()).add(dst)
        self.edges.setdefault((src, dst), site)

    def nodes(self) -> List[str]:
        out: Set[str] = set(self.adj)
        for tos in self.adj.values():
            out |= tos
        return sorted(out)

    def cycles(self) -> List[List[str]]:
        """Elementary cycles via SCC decomposition: one representative
        cycle per non-trivial strongly connected component."""
        index: Dict[str, int] = {}
        low: Dict[str, int] = {}
        on: Set[str] = set()
        stack: List[str] = []
        sccs: List[List[str]] = []
        counter = [0]

        def strong(v: str) -> None:      # iterative Tarjan
            work = [(v, iter(sorted(self.adj.get(v, ()))))]
            index[v] = low[v] = counter[0]
            counter[0] += 1
            stack.append(v)
            on.add(v)
            while work:
                node, it = work[-1]
                advanced = False
                for w in it:
                    if w not in index:
                        index[w] = low[w] = counter[0]
                        counter[0] += 1
                        stack.append(w)
                        on.add(w)
                        work.append((w, iter(sorted(
                            self.adj.get(w, ())))))
                        advanced = True
                        break
                    if w in on:
                        low[node] = min(low[node], index[w])
                if advanced:
                    continue
                work.pop()
                if work:
                    parent = work[-1][0]
                    low[parent] = min(low[parent], low[node])
                if low[node] == index[node]:
                    comp = []
                    while True:
                        w = stack.pop()
                        on.discard(w)
                        comp.append(w)
                        if w == node:
                            break
                    if len(comp) > 1:
                        sccs.append(sorted(comp))

        for v in self.nodes():
            if v not in index:
                strong(v)
        return sccs

    def topo_order(self) -> List[str]:
        """Kahn topological order of the (acyclic part of the) graph —
        the derived hierarchy: a thread may only acquire rightward."""
        indeg: Dict[str, int] = {n: 0 for n in self.nodes()}
        for (_, dst) in self.edges:
            indeg[dst] += 1
        ready = sorted(n for n, d in indeg.items() if d == 0)
        out: List[str] = []
        while ready:
            n = ready.pop(0)
            out.append(n)
            for m in sorted(self.adj.get(n, ())):
                indeg[m] -= 1
                if indeg[m] == 0:
                    ready.append(m)
            ready.sort()
        return out


def _with_lock_ids(project: Project, locks: _Locks, mod: ModuleInfo,
                   fn: FunctionInfo,
                   item: ast.withitem) -> Optional[str]:
    expr = item.context_expr
    if isinstance(expr, ast.Name):
        return locks.resolve_name(mod, fn, expr.id)
    if isinstance(expr, ast.Attribute) \
            and isinstance(expr.value, ast.Name):
        if expr.value.id == "self":
            return locks.resolve_self_attr(mod, fn.class_name, expr.attr)
        # module-level lock referenced through an import alias
        tgt = mod.from_imports.get(expr.value.id)
        if tgt is not None:
            return locks.module.get((tgt[0], expr.attr)) \
                or locks.module.get((tgt[0] + "." + tgt[1], expr.attr))
        if expr.value.id in mod.imports:
            return locks.module.get((mod.imports[expr.value.id],
                                     expr.attr))
    return None


def _analyze_function(project: Project, locks: _Locks,
                      fn: FunctionInfo, graph: LockGraph,
                      direct: Dict[FunctionInfo, Set[str]],
                      callees: Dict[FunctionInfo, Set[FunctionInfo]],
                      held_calls: List[Tuple[Tuple[str, ...],
                                             FunctionInfo, Site]]) -> None:
    mod = fn.module
    direct.setdefault(fn, set())
    callees.setdefault(fn, set())

    def walk(node: ast.AST, held: Tuple[str, ...]) -> None:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.Lambda)):
            return                  # separate function, own analysis
        if isinstance(node, (ast.With, ast.AsyncWith)):
            new_held = held
            for item in node.items:
                # the context expression evaluates while every
                # earlier-listed (and outer) lock is held — a call in
                # it (`with a, open_b():`) contributes edges too
                walk(item.context_expr, new_held)
                lid = _with_lock_ids(project, locks, mod, fn, item)
                if lid is None:
                    continue
                site = (mod.path, node.lineno)
                for h in new_held:
                    graph.add(h, lid, site)
                direct[fn].add(lid)
                new_held = new_held + (lid,)
            for child in node.body:
                walk(child, new_held)
            return
        if isinstance(node, ast.Call):
            tgt = project.resolve_call(mod, fn, node)
            if tgt is not None:
                callees[fn].add(tgt)
                if held:
                    held_calls.append(
                        (held, tgt, (mod.path, node.lineno)))
        for child in ast.iter_child_nodes(node):
            walk(child, held)

    for child in ast.iter_child_nodes(fn.node):
        walk(child, ())


def build_lock_graph(project: Project) \
        -> Tuple[LockGraph, List[Finding]]:
    locks = _collect_locks(project)
    graph = LockGraph()
    direct: Dict[FunctionInfo, Set[str]] = {}
    callees: Dict[FunctionInfo, Set[FunctionInfo]] = {}
    held_calls: List[Tuple[Tuple[str, ...], FunctionInfo, Site]] = []
    for mod in project.iter_modules():
        for fn in mod.functions.values():
            _analyze_function(project, locks, fn, graph, direct,
                              callees, held_calls)

    # transitive may-acquire fixpoint
    may: Dict[FunctionInfo, Set[str]] = {f: set(s)
                                         for f, s in direct.items()}
    changed = True
    while changed:
        changed = False
        for f, cs in callees.items():
            for c in cs:
                add = may.get(c, set()) - may[f]
                if add:
                    may[f] |= add
                    changed = True

    findings: List[Finding] = []
    for held, callee, site in held_calls:
        for lid in sorted(may.get(callee, ())):
            for h in held:
                if lid == h:
                    if lid in locks.singletons:
                        graph.self_deadlocks.append((lid, site,
                                                     callee.qualname))
                else:
                    graph.add(h, lid, site)

    seen_self: Set[Tuple[str, Site]] = set()
    for lid, site, callee in graph.self_deadlocks:
        if (lid, site) in seen_self:
            continue
        seen_self.add((lid, site))
        findings.append(Finding(
            RULE, site[0], site[1], 0,
            f"call made while holding {lid!r} reaches a re-acquire of "
            f"the same non-reentrant lock (via `{callee}`) — "
            "guaranteed self-deadlock"))

    for comp in graph.cycles():
        sites = []
        for i, a in enumerate(comp):
            b = comp[(i + 1) % len(comp)]
            s = graph.edges.get((a, b)) or graph.edges.get((b, a))
            if s:
                sites.append(f"{os.path.basename(s[0])}:{s[1]}")
        anchor = None
        for i, a in enumerate(comp):
            b = comp[(i + 1) % len(comp)]
            anchor = graph.edges.get((a, b))
            if anchor:
                break
        anchor = anchor or (next(iter(project.by_path)), 1)
        findings.append(Finding(
            RULE, anchor[0], anchor[1], 0,
            "lock-order cycle between {" + ", ".join(comp) + "} — two "
            "threads taking these locks in opposite orders deadlock; "
            f"witnesses: {', '.join(sites) or 'n/a'}"))
    return graph, findings


def run(project: Project) -> List[Finding]:
    _, findings = build_lock_graph(project)
    return findings


def render_graph(project: Project) -> str:
    """Human dump for the CLI's ``--lock-graph``: every derived edge
    with its witness site, then the topological hierarchy."""
    graph, findings = build_lock_graph(project)
    lines = ["derived lock-acquisition graph "
             f"({len(graph.edges)} edge(s)):"]
    root = os.getcwd()
    for (src, dst), (path, line) in sorted(graph.edges.items()):
        try:
            rel = os.path.relpath(path, root)
        except ValueError:          # pragma: no cover — windows drives
            rel = path
        lines.append(f"  {src} -> {dst}   ({rel}:{line})")
    if findings:
        lines.append("CYCLES / self-deadlocks:")
        lines.extend("  " + f.render() for f in findings)
    else:
        lines.append("acyclic; hierarchy (acquire left before right):")
        lines.append("  " + " < ".join(graph.topo_order()))
    return "\n".join(lines)
