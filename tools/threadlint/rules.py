"""threadlint's rules: reachability from registered roots + vocabulary.

The traversal is a worklist over ``(function, frozenset(held locks))``
states seeded at every registered thread root.  Held sets propagate
through call sites (a callee inherits the caller's held locks plus any
``with`` scope the call sits inside), so "blocking under lock" and
"lock nested inside lock" are judged on the EFFECTIVE held set, not the
lexical one.  Lock-order edges and local blocking checks additionally
run over every function regardless of reachability — a bad nesting in
main-thread-only code still poisons the global order for everyone else.
"""

from __future__ import annotations

from tools.threadlint import Finding, Registry
from tools.threadlint.engine import Program


def run_rules(program: Program, registry: Registry, check_vocab: bool,
              suppressions: dict | None = None) -> list:
    findings: list[Finding] = []
    findings += _vocab_rules(program, registry, check_vocab)
    edges, jax_hits, blocking_hits, writes = _traverse(
        program, registry, suppressions or {})
    findings += _tl001(jax_hits)
    findings += _tl002(edges, registry)
    findings += _tl003(blocking_hits, registry)
    findings += _tl004(writes, registry)
    findings += _tl005(program, registry)
    return findings


# ------------------------------------------------------------ traversal

def _traverse(program: Program, registry: Registry,
              suppressions: dict):
    edges: dict[tuple, tuple] = {}          # (held, acquired) -> site
    jax_hits: dict[tuple, set] = {}         # (path,line,label) -> roots
    blocking_hits: dict[tuple, set] = {}    # (path,line,label) -> locks
    writes: dict[str, dict] = {}            # attr -> root -> [(p,l,held)]

    def severed(path: str, line: int) -> bool:
        """A reasoned `# threadlint: disable=TL003` at a call site is a
        reviewed blocking-under-these-locks decision, so it also stops
        held-set propagation THROUGH that call — otherwise the same
        reviewed hazard re-fires at every interior blocking touch."""
        sup = suppressions.get(path, {})
        for ln in (line, line - 1):
            entry = sup.get(ln)
            if entry and "TL003" in entry[0] and entry[1]:
                return True
        return False

    # local legs (reachability-independent)
    for fi in program.functions.values():
        for t in fi.blocking:
            if t.held:
                blocking_hits.setdefault(
                    (fi.path, t.line, t.label), set()).update(t.held)
        for a in fi.acquires:
            for l1 in a.held:
                edges.setdefault((l1, a.site), (fi.path, a.line))

    # interprocedural legs
    for root in registry.roots.values():
        if root.entry not in program.functions:
            continue
        seen: set = set()
        stack: list = [(root.entry, frozenset())]
        while stack:
            qual, held = stack.pop()
            if (qual, held) in seen:
                continue
            seen.add((qual, held))
            fi = program.functions.get(qual)
            if fi is None:
                continue
            if not root.jax_ok:
                for t in fi.jax:
                    jax_hits.setdefault(
                        (fi.path, t.line, t.label), set()).add(root.name)
            for t in fi.blocking:
                eff = held | t.held
                if eff:
                    blocking_hits.setdefault(
                        (fi.path, t.line, t.label), set()).update(eff)
            for a in fi.acquires:
                for l1 in held | a.held:
                    edges.setdefault((l1, a.site), (fi.path, a.line))
            if not fi.is_init:
                for w in fi.writes:
                    writes.setdefault(w.site, {}).setdefault(
                        root.name, []).append(
                            (fi.path, w.line, held | w.held))
            for c in fi.calls:
                eff = frozenset() if severed(fi.path, c.line) \
                    else held | c.held
                for tgt in c.targets:
                    stack.append((tgt, eff))
            for tgt in registry.extra_edges.get(qual, ()):
                stack.append((tgt, held))
    return edges, jax_hits, blocking_hits, writes


# ---------------------------------------------------------------- rules

def _tl001(jax_hits: dict) -> list:
    out = []
    for (path, line, label), roots in sorted(jax_hits.items()):
        out.append(Finding(
            "TL001", path, line,
            f"JAX surface `{label}` reachable from thread root(s) "
            f"{', '.join(sorted(roots))} not marked jax_ok"))
    return out


def _lock_name(site: str, registry: Registry) -> str:
    lock = registry.locks.get(site)
    return lock.name if lock else site


def _tl002(edges: dict, registry: Registry) -> list:
    out = []
    graph: dict[str, set] = {}
    for (l1, l2), (path, line) in sorted(edges.items()):
        n1, n2 = _lock_name(l1, registry), _lock_name(l2, registry)
        if l1 == l2:
            lock = registry.locks.get(l1)
            if lock is None or not lock.reentrant:
                out.append(Finding(
                    "TL002", path, line,
                    f"lock `{n1}` re-acquired while already held "
                    "(not registered reentrant)"))
            continue
        graph.setdefault(l1, set()).add(l2)
        r1 = registry.locks.get(l1)
        r2 = registry.locks.get(l2)
        if r1 and r2 and r2.rank <= r1.rank:
            out.append(Finding(
                "TL002", path, line,
                f"lock rank inversion: `{n2}` (rank {r2.rank}) acquired "
                f"while holding `{n1}` (rank {r1.rank}); ranks must "
                "strictly increase"))
    # cycle detection (DFS, report each back edge once)
    WHITE, GRAY, BLACK = 0, 1, 2
    color = {n: WHITE for n in
             set(graph) | {m for vs in graph.values() for m in vs}}
    cycles: list[tuple] = []

    def visit(node, trail):
        color[node] = GRAY
        for nxt in sorted(graph.get(node, ())):
            if color[nxt] == GRAY:
                i = trail.index(nxt)
                cycles.append(tuple(trail[i:]) + (nxt,))
            elif color[nxt] == WHITE:
                visit(nxt, trail + [nxt])
        color[node] = BLACK

    for n in sorted(color):
        if color[n] == WHITE:
            visit(n, [n])
    for cyc in cycles:
        first = (cyc[0], cyc[1])
        path, line = edges[first]
        names = " -> ".join(_lock_name(s, registry) for s in cyc)
        out.append(Finding(
            "TL002", path, line, f"lock-order cycle: {names}"))
    return out


def _tl003(blocking_hits: dict, registry: Registry) -> list:
    out = []
    for (path, line, label), locks in sorted(blocking_hits.items()):
        names = ", ".join(sorted(
            _lock_name(s, registry) for s in locks))
        out.append(Finding(
            "TL003", path, line,
            f"blocking call ({label}) while holding lock(s) {names}"))
    return out


def _tl004(writes: dict, registry: Registry) -> list:
    out = []
    for site, per_root in sorted(writes.items()):
        if site in registry.atomic_ok or len(per_root) < 2:
            continue
        all_writes = [w for lst in per_root.values() for w in lst]
        common = set(all_writes[0][2])
        for _, _, held in all_writes[1:]:
            common &= held
        if common:
            continue
        path, line, _ = min(all_writes,
                            key=lambda w: (len(w[2]), w[0], w[1]))
        out.append(Finding(
            "TL004", path, line,
            f"attribute `{site}` written from thread roots "
            f"{', '.join(sorted(per_root))} with no common lock on "
            "every write path"))
    return out


def _tl005(program: Program, registry: Registry) -> list:
    out = []
    for fi in program.functions.values():
        if fi.path in registry.gil_wedge_home:
            continue
        for t in fi.wedge:
            out.append(Finding(
                "TL005", fi.path, t.line,
                f"GIL-wedge call `{t.label}` outside the bounded-"
                "subprocess probe (can block forever holding the GIL; "
                "run it in a killable child process)"))
    return sorted(out, key=lambda f: (f.path, f.line))


# ----------------------------------------------------------- vocabulary

def _vocab_rules(program: Program, registry: Registry,
                 check_vocab: bool) -> list:
    out = []
    # TL011: every lock creation site is registered (through aliases)
    for lc in program.lock_creations:
        site = program.canon_lock(lc.site) if lc.site else None
        if site is None or site not in registry.lock_sites:
            out.append(Finding(
                "TL011", lc.path, lc.line,
                f"unregistered {lc.kind} creation"
                + (f" at site `{lc.site}`" if lc.site else "")
                + "; add a LockDecl (name + rank) to thread_registry"))
    # TL010: thread/pool/submit/signal/handler vocabulary
    entries = set(registry.roots)
    for ts in program.thread_sites:
        if ts.entry is None:
            out.append(Finding(
                "TL010", ts.path, ts.line,
                f"cannot resolve Thread target `{ts.desc}`; threadlint "
                "needs a resolvable registered root"))
        elif ts.entry not in entries:
            out.append(Finding(
                "TL010", ts.path, ts.line,
                f"Thread target `{ts.entry}` is not a registered "
                "thread root"))
    for ps in program.pool_sites:
        if ps.prefix is None:
            out.append(Finding(
                "TL010", ps.path, ps.line,
                "ThreadPoolExecutor without thread_name_prefix= "
                "(pool threads must be attributable in stacks)"))
    for ss in program.submit_sites:
        if ss.entry is None:
            out.append(Finding(
                "TL010", ss.path, ss.line,
                f"cannot resolve pool submit target `{ss.desc}`"))
        elif ss.entry not in entries:
            out.append(Finding(
                "TL010", ss.path, ss.line,
                f"pool submit target `{ss.entry}` is not a registered "
                "thread root"))
    for sg in program.signal_sites:
        if sg.entry is None or sg.entry not in entries:
            out.append(Finding(
                "TL010", sg.path, sg.line,
                f"signal handler `{sg.entry or sg.desc}` is not a "
                "registered thread root"))
    for he in program.handler_entries:
        if he.entry not in entries:
            out.append(Finding(
                "TL010", he.path, he.line,
                f"handler entry `{he.entry}` is not a registered "
                "thread root"))
    # vocabulary drift (full-repo runs only): registered things that no
    # longer exist in the program
    if check_vocab:
        used_entries = set(program.functions)
        used_entries.update(t.entry for t in program.thread_sites
                            if t.entry)
        used_entries.update(s.entry for s in program.submit_sites
                            if s.entry)
        used_entries.update(s.entry for s in program.signal_sites
                            if s.entry)
        used_entries.update(h.entry for h in program.handler_entries)
        for entry, root in sorted(registry.roots.items()):
            if entry not in used_entries:
                out.append(Finding(
                    "TL010", "<thread_registry>", 0,
                    f"registered root `{root.name}` entry `{entry}` "
                    "not found in the program (stale registration?)"))
        created = {program.canon_lock(lc.site)
                   for lc in program.lock_creations if lc.site}
        for site, lock in sorted(registry.locks.items()):
            if site not in created:
                out.append(Finding(
                    "TL011", "<thread_registry>", 0,
                    f"registered lock `{lock.name}` site `{site}` has "
                    "no creation site in the program (stale "
                    "registration?)"))
    return out
