"""yacylint checkers — the registered rule pipeline.

Each checker is a pure function over the single-parse :class:`Repo`
(engine.py), registered with its exemption token(s).  The first five
are the concurrency/invariant rules this subsystem exists for (the bug
classes multi-pass human review kept catching by hand); the rest are
the ad-hoc hygiene scanners from tests/test_code_hygiene.py migrated
onto the engine so the repo has ONE static-analysis pass, one exemption
grammar, and one baseline.

Checker ids (and their suppression tokens):

=====================  ==================  ===================================
id                     token               catches
=====================  ==================  ===================================
``lockset``            ``unlocked-ok``     a majority-lock-guarded attribute
                                           read/written without the lock
``lock-blocking``      ``blocking-ok``     device transfers / HTTP / fsync /
                                           sleep lexically under a held lock
``tie-discipline``     ``tie-ok``          single-key sort/top-k in fusion
                                           paths (score DESC, docid ASC rule)
``counter-lock``       ``counter-ok``      a counter cohort mutated off the
                                           lock its siblings hold
``unbounded-queue``    ``unbounded-ok``    queue.Queue() with no maxsize
``jit-purity``         ``impure-ok``       time/random/set-iteration inside a
                                           jit-reachable kernel body (silent
                                           constant-folding hazards)
``broad-except``       ``broad-except-ok`` silent ``except Exception: pass``
``kernel-cost-model``  ``costmodel-ok``    jit/pallas kernel with no roofline
                                           cost model entry
``kernel-oracle``      ``oracle-ok``       serving kernel families (bp/ann)
                                           without a NumPy parity oracle, or
                                           dead oracle entries
``servlet-trace``      ``trace-ok``        wall-measuring servlet handlers
                                           outside the span spine
=====================  ==================  ===================================
"""

from __future__ import annotations

import ast

from .engine import Finding, Repo, checker

# -- shared AST helpers -------------------------------------------------------


def dotted(node: ast.AST) -> str:
    """'a.b.c' for a Name/Attribute chain ('' when not a plain chain)."""
    parts: list[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    if parts:
        # e.g. fn().method or d["k"].attr — keep the attr tail so rules
        # matching the called method name still see it
        return "." + ".".join(reversed(parts))
    return ""


def _self_attr(node: ast.AST) -> str | None:
    """attr name when node is ``self.<attr>``, else None."""
    if isinstance(node, ast.Attribute) and \
            isinstance(node.value, ast.Name) and node.value.id == "self":
        return node.attr
    return None


def _is_lockish(expr: ast.AST) -> str | None:
    """The lock's display name when a with-item context expression looks
    like a lock (attribute/name containing 'lock' or 'mutex'), else
    None.  ``with self._lock:``, ``with _reg_lock:``, chained items and
    ``lk["lk"]``-style subscripts on lock dicts all count."""
    name = ""
    if isinstance(expr, ast.Attribute):
        name = expr.attr
    elif isinstance(expr, ast.Name):
        name = expr.id
    elif isinstance(expr, ast.Subscript) and \
            isinstance(expr.slice, ast.Constant) and \
            isinstance(expr.slice.value, str) and \
            "lk" == expr.slice.value:
        return "[lk]"
    low = name.lower()
    if "lock" in low or "mutex" in low:
        return name
    return None


def iter_defs(tree: ast.AST):
    """Every (qualname, FunctionDef) in the module, depth-first."""
    def walk(node, prefix):
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                yield prefix + child.name, child
                yield from walk(child, prefix + child.name + ".")
            elif isinstance(child, ast.ClassDef):
                yield from walk(child, prefix + child.name + ".")
            else:
                yield from walk(child, prefix)
    yield from walk(tree, "")


def _decorator_is_jit(deco: ast.AST) -> bool:
    """@jax.jit / @jit / @partial(jax.jit, ...) /
    @functools.partial(jax.jit, ...) — the shapes the old hygiene regex
    recognized, now structurally."""
    d = dotted(deco)
    if d in ("jax.jit", "jit"):
        return True
    if isinstance(deco, ast.Call):
        f = dotted(deco.func)
        if f in ("jax.jit", "jit"):
            return True
        if f.endswith("partial") and deco.args:
            return dotted(deco.args[0]) in ("jax.jit", "jit")
    return False


def named_kernels(ctx) -> list[tuple[str, ast.FunctionDef]]:
    """(name, def) for every jit-decorated function plus every function
    whose body issues a ``pallas_call`` (pallas kernels are named by
    their host fn) — the engine-side replacement for the regex scanner
    the hygiene tests carried."""
    out = []
    for qual, fn in iter_defs(ctx.tree):
        if any(_decorator_is_jit(d) for d in fn.decorator_list):
            out.append((fn.name, fn))
            continue
        for node in ast.walk(fn):
            if isinstance(node, ast.Call) and \
                    dotted(node.func).split(".")[-1] == "pallas_call":
                out.append((fn.name, fn))
                break
    return out


# -- 1. lockset race detector -------------------------------------------------

# an attribute is "lock-guarded" once this many accesses hold the lock
# and that is at least GUARD_RATIO of all its non-__init__ accesses —
# below that the evidence is too thin to call the unguarded sites races
LOCKSET_MIN_GUARDED = 4
LOCKSET_GUARD_RATIO = 0.75


class _ClassLockScan(ast.NodeVisitor):
    """One class's access census: for every ``self.<attr>`` data access
    in a method body, whether a class lock was lexically held."""

    def __init__(self, lock_attrs: set[str]):
        self.lock_attrs = lock_attrs
        self.held: list[str] = []
        self.method = ""
        self.assume_held = False     # *_locked caller-holds convention
        # attr -> list[(lock_or_None, line, method, is_write)]
        self.accesses: dict[str, list] = {}
        # (attr, lock_or_None, line, method) per `self.X += ...` /
        # `self.X[...] += ...` — the counter-lock checker's census,
        # sharing this scan's lock tracking instead of duplicating it
        self.aug: list[tuple] = []

    def scan_method(self, m: ast.FunctionDef) -> None:
        self.method = m.name
        self.assume_held = m.name.endswith("_locked")
        for stmt in m.body:
            self.visit(stmt)

    # lock tracking ----------------------------------------------------------

    def visit_With(self, node: ast.With) -> None:
        got = []
        for item in node.items:
            a = _self_attr(item.context_expr)
            if a in self.lock_attrs:
                got.append(a)
        self.held.extend(got)
        for stmt in node.body:
            self.visit(stmt)
        for _ in got:
            self.held.pop()

    visit_AsyncWith = visit_With

    def visit_FunctionDef(self, node: ast.FunctionDef) -> None:
        # a nested def is a deferred body (thread target, callback): it
        # does NOT inherit the lexical lock — scan it as unlocked.
        # Lambdas are different: they overwhelmingly run inline as
        # min/sorted key= callables, so they keep the lock state.
        saved, self.held = self.held, []
        for stmt in node.body:
            self.visit(stmt)
        self.held = saved

    visit_AsyncFunctionDef = visit_FunctionDef

    # access recording -------------------------------------------------------

    def visit_Call(self, node: ast.Call) -> None:
        # `self.method(...)`: the func attribute is a call, not a data
        # access — but still walk the receiver chain and the arguments
        if _self_attr(node.func) is not None:
            pass
        else:
            self.visit(node.func)
        for a in node.args:
            self.visit(a)
        for kw in node.keywords:
            self.visit(kw.value)

    def visit_AugAssign(self, node: ast.AugAssign) -> None:
        a = _self_attr(node.target)
        if a is None and isinstance(node.target, ast.Subscript):
            a = _self_attr(node.target.value)
        if a is not None and a not in self.lock_attrs:
            lock = self.held[-1] if self.held else (
                "(caller)" if self.assume_held else None)
            self.aug.append((a, lock, node.lineno, self.method))
        self.generic_visit(node)

    def visit_Attribute(self, node: ast.Attribute) -> None:
        a = _self_attr(node)
        if a is not None and a not in self.lock_attrs:
            lock = self.held[-1] if self.held else (
                "(caller)" if self.assume_held else None)
            self.accesses.setdefault(a, []).append(
                (lock, node.lineno,
                 self.method, isinstance(node.ctx,
                                         (ast.Store, ast.Del))))
        self.generic_visit(node)


def _class_locks(cls: ast.ClassDef) -> set[str]:
    locks = set()
    for node in ast.walk(cls):
        if isinstance(node, ast.Assign) and isinstance(node.value, ast.Call):
            f = dotted(node.value.func)
            # a Condition wraps (or is) a lock: `with self._not_empty:`
            # acquires it, so it guards exactly like a Lock; the
            # ObservedLock/ObservedRLock wrappers (utils/profiling.py,
            # ISSUE 20b) ARE locks and must keep guarding, or swapping
            # a raw lock for its observed twin would silently retire
            # every lockset/counter-lock rule over the class
            if f.split(".")[-1] in ("Lock", "RLock", "Condition",
                                    "ObservedLock", "ObservedRLock"):
                for t in node.targets:
                    a = _self_attr(t)
                    if a:
                        locks.add(a)
    return locks


def _iter_classes(ctx):
    for node in ast.walk(ctx.tree):
        if isinstance(node, ast.ClassDef):
            yield node


@checker("lockset", "unlocked-ok")
def check_lockset(repo: Repo, stats: dict):
    """Infer each class's lock-guarded attribute set from the census of
    ``with self._lock:``-dominated accesses, then flag the minority of
    sites that touch such an attribute without the lock."""
    findings = []
    classes = guarded_attrs = 0
    for ctx in repo.under("yacy_search_server_tpu/"):
        for cls in _iter_classes(ctx):
            locks = _class_locks(cls)
            if not locks:
                continue
            classes += 1
            scan = _ClassLockScan(locks)
            methods = [n for n in cls.body
                       if isinstance(n, (ast.FunctionDef,
                                         ast.AsyncFunctionDef))]
            for m in methods:
                if m.name in ("__init__", "__new__"):
                    continue     # pre-publication: the object is private
                scan.scan_method(m)
            for attr, recs in sorted(scan.accesses.items()):
                locked = [r for r in recs if r[0] is not None]
                bare = [r for r in recs if r[0] is None]
                total = len(recs)
                if len(locked) < LOCKSET_MIN_GUARDED or not bare or \
                        len(locked) / total < LOCKSET_GUARD_RATIO:
                    continue
                # majority lock by census (the one to name in the fix)
                by_lock: dict[str, int] = {}
                for lk, *_ in locked:
                    by_lock[lk] = by_lock.get(lk, 0) + 1
                lock = max(sorted(by_lock), key=by_lock.get)
                guarded_attrs += 1
                seen_lines = set()
                for _lk, line, method, is_write in bare:
                    if line in seen_lines:
                        continue
                    seen_lines.add(line)
                    node_lines = [line]
                    mdef = next((m for m in methods if m.name == method),
                                None)
                    if mdef is not None:
                        node_lines.append(mdef.lineno)
                    if ctx.exempt(("unlocked-ok",), node_lines):
                        continue
                    kind = "write" if is_write else "read"
                    findings.append(Finding(
                        "lockset", ctx.rel, line,
                        f"self.{attr} is guarded by self.{lock} at "
                        f"{len(locked)}/{total} sites, but "
                        f"{cls.name}.{method} {kind}s it without the "
                        f"lock — take the lock or annotate "
                        f"`# lint: unlocked-ok(reason)`"))
    stats["classes_with_locks"] = classes
    stats["guarded_attrs"] = guarded_attrs
    return findings


# -- 2. blocking call under a held lock ---------------------------------------

_BLOCKING_EXACT = {
    "time.sleep", "os.fsync", "os.fdatasync", "socket.create_connection",
    "jax.device_put", "jax.device_get", "device_put", "device_get",
    "urllib.request.urlopen", "urlopen",
}
_BLOCKING_TAIL = {
    "block_until_ready", "copy_to_host_async", "mesh_rpc", "fsync",
}
_BLOCKING_PREFIX = ("requests.", "subprocess.", "http.client.")


def _is_blocking_call(call: ast.Call) -> str | None:
    d = dotted(call.func)
    if d in _BLOCKING_EXACT:
        return d
    tail = d.split(".")[-1]
    if tail in _BLOCKING_TAIL:
        return d or tail
    if d.startswith(_BLOCKING_PREFIX):
        return d
    return None


class _LockBodyScan(ast.NodeVisitor):
    """Collect blocking calls lexically inside a with-lock body,
    skipping nested function bodies (deferred execution)."""

    def __init__(self):
        self.hits: list[tuple[str, int]] = []

    def visit_FunctionDef(self, node):
        return
    visit_AsyncFunctionDef = visit_FunctionDef
    visit_Lambda = visit_FunctionDef

    def visit_Call(self, node: ast.Call) -> None:
        name = _is_blocking_call(node)
        if name:
            self.hits.append((name, node.lineno))
        self.generic_visit(node)


@checker("lock-blocking", "blocking-ok")
def check_lock_blocking(repo: Repo, stats: dict):
    """Flag device transfers, HTTP calls, fsync and sleeps lexically
    inside a ``with <lock>:`` body — the exact shape of the review-era
    bugs (multi-second transfers/merges stalling every other thread on
    the lock)."""
    findings = []
    regions = 0
    for ctx in repo.under("yacy_search_server_tpu/"):
        # enclosing def line per with-statement (the wider exemption
        # scope): map each with to the innermost def containing it
        encl: dict[int, int] = {}
        for qual, fn in iter_defs(ctx.tree):
            for node in ast.walk(fn):
                if isinstance(node, (ast.With, ast.AsyncWith)):
                    encl[node.lineno] = fn.lineno
        for node in ast.walk(ctx.tree):
            if not isinstance(node, (ast.With, ast.AsyncWith)):
                continue
            locks = [nm for item in node.items
                     if (nm := _is_lockish(item.context_expr))]
            if not locks:
                continue
            regions += 1
            scan = _LockBodyScan()
            for stmt in node.body:
                scan.visit(stmt)
            for name, line in scan.hits:
                scope = [line, node.lineno]
                if node.lineno in encl:
                    scope.append(encl[node.lineno])
                if ctx.exempt(("blocking-ok",), scope):
                    continue
                findings.append(Finding(
                    "lock-blocking", ctx.rel, line,
                    f"blocking call {name}() inside `with "
                    f"{locks[0]}:` — every thread contending the lock "
                    f"stalls behind it; move it outside the critical "
                    f"section or annotate `# lint: blocking-ok(reason)`"))
    stats["lock_regions"] = regions
    return findings


# -- 3. tie discipline in fusion paths ----------------------------------------

TIE_SCOPES = ("yacy_search_server_tpu/ops/",
              "yacy_search_server_tpu/parallel/",
              "yacy_search_server_tpu/search/")


def _has_two_key_sort(fn: ast.FunctionDef) -> bool:
    """A lax.sort with num_keys>=2 or a multi-key np.lexsort anywhere
    in the function: the final two-key pass that pins (score DESC,
    docid ASC) no matter what an interior top-k prefilter did."""
    for node in ast.walk(fn):
        if not isinstance(node, ast.Call):
            continue
        d = dotted(node.func)
        tail = d.split(".")[-1]
        if tail == "sort" and ("lax" in d.split(".")):
            for kw in node.keywords:
                if kw.arg == "num_keys" and \
                        isinstance(kw.value, ast.Constant) and \
                        kw.value.value >= 2:
                    return True
        if tail == "lexsort" and node.args and \
                isinstance(node.args[0], ast.Tuple) and \
                len(node.args[0].elts) >= 2:
            return True
    return False


@checker("tie-discipline", "tie-ok")
def check_tie_discipline(repo: Repo, stats: dict):
    """Every sort/top-k in the fusion paths must use the two-key form
    — (score, docid) via lax.sort num_keys>=2, a multi-key np.lexsort,
    or a kind='stable' argsort over docid-ordered rows — or carry a
    reasoned exemption (arxiv 1807.05798: unpinned ties flap rankings
    across runs, peers and cache entries)."""
    findings = []
    sites = 0
    for ctx in repo.under(*TIE_SCOPES):
        for qual, fn in iter_defs(ctx.tree):
            two_key = None      # computed lazily per function
            for node in ast.walk(fn):
                if not isinstance(node, ast.Call):
                    continue
                d = dotted(node.func)
                tail = d.split(".")[-1]
                bad = None
                if tail == "top_k":
                    sites += 1
                    if two_key is None:
                        two_key = _has_two_key_sort(fn)
                    if not two_key:
                        bad = (f"{d}() is single-key (ties break by "
                               f"input position) and {qual} has no "
                               f"two-key final sort")
                elif tail == "argsort":
                    sites += 1
                    stable = any(kw.arg == "kind"
                                 and isinstance(kw.value, ast.Constant)
                                 and kw.value.value == "stable"
                                 for kw in node.keywords)
                    if not stable:
                        bad = (f"{d}() without kind='stable' — equal "
                               f"scores order arbitrarily")
                elif tail == "sort" and "lax" in d.split("."):
                    sites += 1
                    nk = next((kw.value.value for kw in node.keywords
                               if kw.arg == "num_keys"
                               and isinstance(kw.value, ast.Constant)),
                              1)
                    if nk < 2:
                        bad = (f"{d}() with num_keys={nk} — the "
                               f"two-key (score, docid) form is the "
                               f"pinned tie discipline")
                elif tail == "lexsort":
                    sites += 1
                    if not (node.args
                            and isinstance(node.args[0], ast.Tuple)
                            and len(node.args[0].elts) >= 2):
                        bad = f"{d}() with a single key"
                if bad is None:
                    continue
                scope = ctx.node_lines(node) + [fn.lineno]
                if ctx.exempt(("tie-ok",), scope):
                    continue
                findings.append(Finding(
                    "tie-discipline", ctx.rel, node.lineno,
                    bad + " — use the two-key form or annotate "
                          "`# lint: tie-ok(reason)`"))
    stats["sort_sites"] = sites
    return findings


# -- 4a. unbounded queues -----------------------------------------------------

_QUEUE_NAMES = {"Queue", "LifoQueue", "PriorityQueue", "SimpleQueue"}


def _literal_int(node: ast.AST):
    """The int value of a (possibly negated) literal, else None —
    ``Queue(-1)`` parses as UnaryOp(USub, Constant(1)) and means
    UNbounded, exactly like 0."""
    if isinstance(node, ast.Constant) and \
            isinstance(node.value, (int, float)):
        return node.value
    if isinstance(node, ast.UnaryOp) and \
            isinstance(node.op, ast.USub) and \
            isinstance(node.operand, ast.Constant) and \
            isinstance(node.operand.value, (int, float)):
        return -node.operand.value
    return None


@checker("unbounded-queue", "unbounded-ok")
def check_unbounded_queue(repo: Repo, stats: dict):
    """Every queue construction needs a maxsize bound: an unbounded
    queue of work (or of issued-but-unfetched device buffers) is
    unbounded memory — backpressure IS the cap.  Generalizes the old
    devstore/meshstore in-flight scan to the whole package."""
    findings = []
    sites = 0
    inflight_bounded = 0
    for ctx in repo.under("yacy_search_server_tpu/"):
        parents = {id(c): p for p in ast.walk(ctx.tree)
                   for c in ast.iter_child_nodes(p)}
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            d = dotted(node.func)
            parts = d.split(".")
            if parts[-1] not in _QUEUE_NAMES:
                continue
            if len(parts) > 1 and parts[0] not in ("queue", "_queue"):
                continue    # e.g. multiprocessing.Queue — out of scope
            sites += 1
            bounded = False
            # queue semantics: maxsize <= 0 means INFINITE, so a
            # literal 0 or negative is unbounded; a dynamic expression
            # (Name, attribute) is trusted as a configured bound
            if parts[-1] != "SimpleQueue":      # never bounded
                for arg in (node.args[:1]
                            + [kw.value for kw in node.keywords
                               if kw.arg == "maxsize"]):
                    lit = _literal_int(arg)
                    bounded = lit is None or lit > 0
            # attribute the site for the anti-rot stat
            parent = parents.get(id(node))
            attr = None
            while parent is not None and attr is None:
                if isinstance(parent, ast.Assign):
                    for t in parent.targets:
                        attr = _self_attr(t) or attr
                    break
                if isinstance(parent, ast.AnnAssign):
                    attr = _self_attr(parent.target)
                    break
                parent = parents.get(id(parent))
            if attr == "_inflight" and bounded:
                inflight_bounded += 1
            if bounded:
                continue
            if ctx.exempt(("unbounded-ok",), ctx.node_lines(node)):
                continue
            findings.append(Finding(
                "unbounded-queue", ctx.rel, node.lineno,
                f"{d or parts[-1]}() without a maxsize bound — "
                f"unbounded queued work/memory; give it a bound or "
                f"annotate `# lint: unbounded-ok(reason)`"))
    stats["queue_sites"] = sites
    stats["inflight_bounded"] = inflight_bounded
    return findings


# -- 4b. counter mutated outside its cohort's lock ----------------------------

@checker("counter-lock", "counter-ok", "unlocked-ok")
def check_counter_lock(repo: Repo, stats: dict):
    """In a class whose numeric counters are incremented under a lock,
    EVERY counter increment must hold it: one counter drifting off the
    lock (the `_ms_lock` bug shape) silently corrupts the telemetry the
    health rules act on.  Unlike `lockset` this needs no per-attribute
    majority — the cohort's discipline is the evidence."""
    findings = []
    cohorts = 0
    for ctx in repo.under("yacy_search_server_tpu/"):
        for cls in _iter_classes(ctx):
            locks = _class_locks(cls)
            if not locks:
                continue
            # counters: numeric-initialized in __init__
            counters: set[str] = set()
            init = next((n for n in cls.body
                         if isinstance(n, ast.FunctionDef)
                         and n.name == "__init__"), None)
            if init is None:
                continue
            for node in ast.walk(init):
                if isinstance(node, ast.Assign) and \
                        isinstance(node.value, ast.Constant) and \
                        isinstance(node.value.value, (int, float)) and \
                        not isinstance(node.value.value, bool):
                    for t in node.targets:
                        a = _self_attr(t)
                        if a:
                            counters.add(a)
            if not counters:
                continue
            scan = _ClassLockScan(locks)
            methods = [n for n in cls.body
                       if isinstance(n, (ast.FunctionDef,
                                         ast.AsyncFunctionDef))]
            for m in methods:
                if m.name == "__init__":
                    continue
                scan.scan_method(m)
            aug = [rec for rec in scan.aug if rec[0] in counters]
            if not aug:
                continue
            if any(lk is not None for _a, lk, _l, _m in aug):
                cohorts += 1
            else:
                continue     # nothing guarded: lockset territory, not ours
            for attr, lk, line, method in aug:
                if lk is not None:
                    continue
                mdef = next((m for m in methods if m.name == method),
                            None)
                scope = [line] + ([mdef.lineno] if mdef else [])
                if ctx.exempt(("counter-ok", "unlocked-ok"), scope):
                    continue
                findings.append(Finding(
                    "counter-lock", ctx.rel, line,
                    f"counter self.{attr} incremented outside the "
                    f"lock its {cls.name} siblings hold — the "
                    f"unsynchronized += loses updates; take the lock "
                    f"or annotate `# lint: counter-ok(reason)`"))
    stats["counter_cohorts"] = cohorts
    return findings


# -- 5. jit purity ------------------------------------------------------------

_IMPURE_EXACT = {
    "time.time", "time.monotonic", "time.perf_counter", "time.time_ns",
    "datetime.datetime.now", "datetime.now", "datetime.datetime.utcnow",
}


def _impure_call(call: ast.Call) -> str | None:
    d = dotted(call.func)
    if d in _IMPURE_EXACT:
        return d
    if d.startswith(("np.random.", "numpy.random.", "random.")):
        return d
    return None


@checker("jit-purity", "impure-ok")
def check_jit_purity(repo: Repo, stats: dict):
    """Inside a jit-reachable kernel body, wall clocks, host RNGs and
    set-iteration are silent constant-folding hazards: the value is
    baked at trace time and never moves again.  Reachability is the
    jit-decorated defs plus module-local functions they call,
    transitively."""
    findings = []
    roots = 0
    for ctx in repo.under("yacy_search_server_tpu/"):
        defs = dict(iter_defs(ctx.tree))
        by_name: dict[str, list[str]] = {}
        for qual, fn in defs.items():
            by_name.setdefault(fn.name, []).append(qual)
        jit_roots = [qual for qual, fn in defs.items()
                     if any(_decorator_is_jit(d)
                            for d in fn.decorator_list)]
        roots += len(jit_roots)
        # module-local transitive closure over plain-name calls
        reach: set[str] = set()
        work = list(jit_roots)
        while work:
            qual = work.pop()
            if qual in reach:
                continue
            reach.add(qual)
            for node in ast.walk(defs[qual]):
                if isinstance(node, ast.Call) and \
                        isinstance(node.func, ast.Name):
                    for q in by_name.get(node.func.id, ()):
                        if q not in reach:
                            work.append(q)
        for qual in sorted(reach):
            fn = defs[qual]
            for node in ast.walk(fn):
                bad = None
                if isinstance(node, ast.Call):
                    name = _impure_call(node)
                    if name:
                        bad = (f"{name}() inside jit-reachable "
                               f"{qual} — traced once, constant "
                               f"forever")
                elif isinstance(node, ast.For) and isinstance(
                        node.iter, (ast.Set, ast.SetComp)):
                    bad = (f"iteration over a set literal inside "
                           f"jit-reachable {qual} — hash order is "
                           f"not a program invariant")
                if bad is None:
                    continue
                line = node.lineno
                scope = [line, fn.lineno]
                if ctx.exempt(("impure-ok",), scope):
                    continue
                findings.append(Finding(
                    "jit-purity", ctx.rel, line,
                    bad + "; hoist it to the host caller or annotate "
                          "`# lint: impure-ok(reason)`"))
    stats["jit_roots"] = roots
    return findings


# -- 6. silent broad excepts (migrated from test_code_hygiene) ----------------

@checker("broad-except", "broad-except-ok")
def check_broad_except(repo: Repo, stats: dict):
    """``except Exception: pass`` hides index-hygiene and serving
    failures the operator needs to see — each handler must log or
    narrow the type (the reference logs every swallowed exception
    through ConcurrentLog)."""
    findings = []
    handlers = 0
    for ctx in repo.under("yacy_search_server_tpu/"):
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.ExceptHandler):
                continue
            names = []
            if isinstance(node.type, ast.Tuple):
                names = [dotted(e) for e in node.type.elts]
            elif node.type is not None:
                names = [dotted(node.type)]
            if not any(n in ("Exception", "BaseException")
                       for n in names):
                continue
            handlers += 1
            if not (len(node.body) == 1
                    and isinstance(node.body[0], ast.Pass)):
                continue
            scope = [node.lineno, node.body[0].lineno]
            if ctx.exempt(("broad-except-ok",), scope):
                continue
            findings.append(Finding(
                "broad-except", ctx.rel, node.lineno,
                "silent `except Exception: pass` — log the failure or "
                "narrow the exception type (or annotate "
                "`# lint: broad-except-ok(reason)`)"))
    stats["broad_handlers"] = handlers
    return findings


# -- 7. kernel cost models (migrated) -----------------------------------------

ROOFLINE_REL = "yacy_search_server_tpu/ops/roofline.py"
KERNEL_SCOPES = ("yacy_search_server_tpu/ops/",
                 "yacy_search_server_tpu/ingest/")
KERNEL_FILES = ("yacy_search_server_tpu/index/devstore.py",)


def roofline_registry(repo: Repo) -> tuple[set[str], set[str]]:
    """(KERNELS keys, EXEMPT keys) read statically off ops/roofline.py
    — no jax import, same single-parse pass as everything else."""
    return (repo.dict_literal_keys(ROOFLINE_REL, "KERNELS"),
            repo.dict_literal_keys(ROOFLINE_REL, "EXEMPT"))


def kernel_contexts(repo: Repo):
    seen = set()
    for ctx in repo.under(*KERNEL_SCOPES) + \
            [c for r in KERNEL_FILES if (c := repo.get(r))]:
        if ctx.rel not in seen:
            seen.add(ctx.rel)
            yield ctx


@checker("kernel-cost-model", "costmodel-ok")
def check_kernel_cost_model(repo: Repo, stats: dict):
    """Every named device kernel (jit- or pallas-compiled) in ops/,
    ingest/ and index/devstore.py must carry a roofline cost-model
    entry — a kernel without one is invisible to the silicon
    accounting, so its perf claims cannot be stated against the
    hardware.  Exemption: `# lint: costmodel-ok(reason)` on the def
    (non-serving maintenance kernels)."""
    findings = []
    kernels, exempt = roofline_registry(repo)
    seen = []
    for ctx in kernel_contexts(repo):
        for name, fn in named_kernels(ctx):
            seen.append(name)
            if name in kernels or name in exempt:
                continue
            scope = [fn.lineno,
                     min(d.lineno for d in fn.decorator_list)
                     if fn.decorator_list else fn.lineno]
            if ctx.exempt(("costmodel-ok",), scope):
                continue
            findings.append(Finding(
                "kernel-cost-model", ctx.rel, fn.lineno,
                f"device kernel {name} has no roofline cost model — "
                f"register it in ops/roofline.KERNELS or annotate the "
                f"def `# lint: costmodel-ok(reason)`"))
    stats["kernels_seen"] = len(seen)
    stats["kernel_names"] = sorted(set(seen))
    stats["registry_kernels"] = len(kernels)
    return findings


# -- 8. serving-kernel parity oracles (migrated) ------------------------------

@checker("kernel-oracle", "oracle-ok")
def check_kernel_oracle(repo: Repo, stats: dict):
    """Serving-kernel families whose bit-identity contract rests on a
    NumPy oracle: every ``*_bp_kernel`` needs ops/packed.BP_ORACLES and
    every ``_ann_*`` kernel needs ops/ann.ANN_ORACLES (the oracle
    doubles as the host/device-loss fallback).  For these families a
    roofline EXEMPT entry is NOT acceptable — registration must be BY
    NAME.  Dead oracle entries (no kernel behind them) also flag."""
    findings = []
    kernels_reg, _exempt = roofline_registry(repo)
    bp_oracles = repo.dict_literal_keys(
        "yacy_search_server_tpu/ops/packed.py", "BP_ORACLES")
    ann_oracles = repo.dict_literal_keys(
        "yacy_search_server_tpu/ops/ann.py", "ANN_ORACLES")
    bp, annk = [], []
    dev = repo.get("yacy_search_server_tpu/index/devstore.py")
    if dev is not None:
        bp = [(n, f) for n, f in named_kernels(dev)
              if n.endswith("_bp_kernel")]
    annctx = repo.get("yacy_search_server_tpu/ops/ann.py")
    if annctx is not None:
        annk = [(n, f) for n, f in named_kernels(annctx)
                if n.startswith("_ann_")]
    for fam, found, oracles, oname in (
            ("*_bp_kernel", bp, bp_oracles, "ops/packed.BP_ORACLES"),
            ("_ann_*", annk, ann_oracles, "ops/ann.ANN_ORACLES")):
        for name, fn in found:
            ctx = dev if fam == "*_bp_kernel" else annctx
            scope = [fn.lineno,
                     min(d.lineno for d in fn.decorator_list)
                     if fn.decorator_list else fn.lineno]
            if ctx.exempt(("oracle-ok",), scope):
                continue
            if name not in oracles:
                findings.append(Finding(
                    "kernel-oracle", ctx.rel, fn.lineno,
                    f"serving kernel {name} has no NumPy oracle — "
                    f"register the parity anchor in {oname}"))
            if name not in kernels_reg:
                findings.append(Finding(
                    "kernel-oracle", ctx.rel, fn.lineno,
                    f"serving kernel {name} must be registered BY "
                    f"NAME in ops/roofline.KERNELS (an exemption is "
                    f"not acceptable for a serving kernel)"))
    # dead oracle entries: a renamed kernel must not leave one behind
    live_ann = {n for n, _ in annk}
    for dead in sorted(ann_oracles - live_ann):
        findings.append(Finding(
            "kernel-oracle", "yacy_search_server_tpu/ops/ann.py", 1,
            f"ANN_ORACLES entry {dead!r} names no live _ann_* kernel "
            f"— delete the dead oracle"))
    live_bp = {n for n, _ in bp}
    for dead in sorted(bp_oracles - live_bp):
        findings.append(Finding(
            "kernel-oracle", "yacy_search_server_tpu/ops/packed.py", 1,
            f"BP_ORACLES entry {dead!r} names no live *_bp_kernel — "
            f"delete the dead oracle"))
    stats["bp_kernels"] = sorted(live_bp)
    stats["ann_kernels"] = sorted(live_ann)
    return findings


# -- 9. wall-measuring servlets open spans (migrated) -------------------------

@checker("servlet-trace", "trace-ok")
def check_servlet_trace(repo: Repo, stats: dict):
    """Every @servlet handler that measures a wall (a t0 it later
    subtracts) or touches the roofline PROFILER must open a tracing
    span — or carry `# lint: trace-ok(reason)` on the def.  An endpoint
    that times itself outside the span spine silently drops out of the
    waterfall Performance_Trace_p renders."""
    findings = []
    handlers = 0
    for ctx in repo.under("yacy_search_server_tpu/server/servlets/"):
        for qual, fn in iter_defs(ctx.tree):
            is_servlet = any(
                isinstance(d, ast.Call) and dotted(d.func) == "servlet"
                for d in fn.decorator_list)
            if not is_servlet:
                continue
            handlers += 1
            measures = traced = False
            for node in ast.walk(fn):
                if isinstance(node, ast.Assign) and \
                        isinstance(node.value, ast.Call) and \
                        dotted(node.value.func) in (
                            "time.time", "time.monotonic",
                            "time.perf_counter"):
                    if any(isinstance(t, ast.Name)
                           and t.id.startswith("t0")
                           for t in node.targets):
                        measures = True
                if isinstance(node, ast.Name) and node.id == "PROFILER":
                    measures = True
                if isinstance(node, ast.Call) and dotted(node.func) in (
                        "tracing.trace", "tracing.span",
                        "tracing.span_in", "tracing.begin"):
                    traced = True
            if not measures or traced:
                continue
            deco_line = min((d.lineno for d in fn.decorator_list),
                            default=fn.lineno)
            if ctx.exempt(("trace-ok",),
                          [deco_line, fn.lineno]):
                continue
            findings.append(Finding(
                "servlet-trace", ctx.rel, fn.lineno,
                f"servlet handler {fn.name} measures a wall without "
                f"opening a tracing span — wrap it in tracing.trace() "
                f"or annotate `# lint: trace-ok(reason)`"))
    stats["servlet_handlers"] = handlers
    return findings


# -- 11. tail-classifier reachability (ISSUE 15) ------------------------------

TAILATTR_REL = "yacy_search_server_tpu/utils/tailattr.py"


def tail_classifier_families(repo: Repo) -> set[str]:
    """The histogram families the tail classifier consumes or gates on,
    read statically off utils/tailattr.CLASSIFIER_FAMILIES (a
    frozenset literal whose elements may be the module's own MARKER_*
    string constants) — no import, same single-parse pass as the
    roofline registry reads."""
    ctx = repo.get(TAILATTR_REL)
    if ctx is None:
        return set()
    consts: dict[str, str] = {}
    fams: set[str] = set()
    for node in ctx.tree.body:
        if not isinstance(node, ast.Assign):
            continue
        for t in node.targets:
            if not isinstance(t, ast.Name):
                continue
            if isinstance(node.value, ast.Constant) and \
                    isinstance(node.value.value, str):
                consts[t.id] = node.value.value
            elif t.id == "CLASSIFIER_FAMILIES" and \
                    isinstance(node.value, ast.Call) and \
                    node.value.args and \
                    isinstance(node.value.args[0], ast.Set):
                for el in node.value.args[0].elts:
                    if isinstance(el, ast.Constant) and \
                            isinstance(el.value, str):
                        fams.add(el.value)
                    elif isinstance(el, ast.Name):
                        fams.add(("__name__", el.id))
    return {consts.get(f[1], "") if isinstance(f, tuple) else f
            for f in fams} - {""}


@checker("tail-reach", "tail-ok")
def check_tail_reach(repo: Repo, stats: dict):
    """Every histogram family a servlet wall observes directly (a
    literal family name handed to ``histogram.observe`` or to one of
    the spine's always-measured walls — ``tracing.envelope`` (both of
    its families), ``tracing.timed``, ``tracing.record`` — anywhere
    under server/) must be reachable by the tail classifier — listed in
    utils/tailattr.CLASSIFIER_FAMILIES — or carry a reasoned
    ``# lint: tail-ok(reason)``.  A serving wall the classifier cannot
    see is a p99 bucket nothing can ever explain: it fills the SLO
    histogram but every over-threshold query it measures would
    classify blind."""
    findings = []
    fams = tail_classifier_families(repo)
    observed = 0
    walls = {"histogram.observe": 1, "tracing.envelope": 2,
             "tracing.timed": 1, "tracing.record": 1}
    for ctx in repo.under("yacy_search_server_tpu/server/"):
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            for arg in node.args[:walls.get(dotted(node.func), 0)]:
                if not (isinstance(arg, ast.Constant)
                        and isinstance(arg.value, str)):
                    continue
                observed += 1
                fam = arg.value
                if fam in fams:
                    continue
                if ctx.exempt(("tail-ok",), ctx.node_lines(node)):
                    continue
                findings.append(Finding(
                    "tail-reach", ctx.rel, node.lineno,
                    f"servlet wall observes histogram family {fam!r} "
                    f"the tail classifier cannot reach — add it to "
                    f"utils/tailattr.CLASSIFIER_FAMILIES (and teach the "
                    f"classifier) or annotate `# lint: tail-ok(reason)`"))
    stats["servlet_observed_families"] = observed
    stats["classifier_families"] = len(fams)
    return findings


# -- 12. raw lock on the instrumented-lock census (ISSUE 20b) -----------------


@checker("raw-hot-lock", "rawlock-ok")
def check_raw_hot_lock(repo: Repo, stats: dict):
    """Police the lock-wait observatory's census: every
    ``file::Class::attr`` key of ``HOT_LOCK_CENSUS``
    (utils/profiling.py) must be constructed as
    ``ObservedLock``/``ObservedRLock`` in that class — a raw
    ``threading.Lock/RLock`` on a census name is a hot lock whose
    wait/hold walls silently vanish from ``yacy_lock_wait_*`` and from
    the tail classifier's lock-wait markers.  A census entry matching
    NOTHING is also a finding (the census cannot rot as code moves).
    Escape hatch: ``# lint: rawlock-ok(reason)`` on the assignment."""
    findings = []
    census: dict[str, str] = {}     # key -> rel of the census literal
    for ctx in repo.under("yacy_search_server_tpu/"):
        for node in ast.walk(ctx.tree):
            if not (isinstance(node, ast.Assign)
                    and isinstance(node.value, ast.Dict)):
                continue
            if not any(isinstance(t, ast.Name)
                       and t.id == "HOT_LOCK_CENSUS"
                       for t in node.targets):
                continue
            for k in node.value.keys:
                if isinstance(k, ast.Constant) \
                        and isinstance(k.value, str):
                    census[k.value] = ctx.rel
    observed = raw = 0
    for key, src in sorted(census.items()):
        parts = key.split("::")
        if len(parts) != 3:
            findings.append(Finding(
                "raw-hot-lock", src, 1,
                f"malformed HOT_LOCK_CENSUS key {key!r} "
                f"(want 'file::Class::attr')"))
            continue
        rel, clsname, attr = parts
        ctx = repo.get(rel)
        cls = None
        if ctx is not None:
            cls = next((n for n in ast.walk(ctx.tree)
                        if isinstance(n, ast.ClassDef)
                        and n.name == clsname), None)
        if cls is None:
            findings.append(Finding(
                "raw-hot-lock", src, 1,
                f"HOT_LOCK_CENSUS entry {key!r} matches no class — "
                f"the census rotted; update or remove the entry"))
            continue
        assigns = [n for n in ast.walk(cls)
                   if isinstance(n, ast.Assign)
                   and isinstance(n.value, ast.Call)
                   and any(_self_attr(t) == attr for t in n.targets)]
        if not assigns:
            findings.append(Finding(
                "raw-hot-lock", src, 1,
                f"HOT_LOCK_CENSUS entry {key!r} matches no "
                f"constructor assignment in {clsname} — the census "
                f"rotted; update or remove the entry"))
            continue
        for node in assigns:
            tail = dotted(node.value.func).split(".")[-1]
            if tail in ("ObservedLock", "ObservedRLock"):
                observed += 1
                continue
            if tail not in ("Lock", "RLock"):
                continue        # some other factory: not this rule's call
            if ctx.exempt(("rawlock-ok",), [node.lineno, cls.lineno]):
                continue
            raw += 1
            findings.append(Finding(
                "raw-hot-lock", ctx.rel, node.lineno,
                f"{clsname}.{attr} is on the instrumented-lock census "
                f"but is a raw threading.{tail} — use "
                f"profiling.ObservedLock/ObservedRLock so its "
                f"wait/hold walls record, or annotate "
                f"`# lint: rawlock-ok(reason)`"))
    stats["census_entries"] = len(census)
    stats["observed_locks"] = observed
    return findings
