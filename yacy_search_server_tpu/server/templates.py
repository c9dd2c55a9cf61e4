"""Template engine — #[x]#, #(alt)#, #{loop}#, #%include%#.

Capability equivalent of the reference's template grammar (reference:
source/net/yacy/server/http/TemplateEngine.java:84-146):

- ``#[key]#``                      → value of ``key`` in the pattern map
- ``#(key)#a::b::c#(/key)#``       → alternative selected by int(key)
  (out-of-range or non-numeric selects alternative 0)
- ``#{key}#body#{/key}#``          → body repeated int(key) times; inside
  iteration i, ``#[field]#`` resolves ``key_i_field`` first (the
  serverObjects loop-row convention), and nested alternatives resolve the
  same prefixed keys
- ``#%path%#``                     → include of another template file,
  resolved against the template root

The reference streams byte-wise and re-reads the grammar on every
request. How a source splits into tags depends on the source alone,
never on the property map, so here a source is PARSED once into a tree
(literal, field, alternative with its split alternatives, loop with its
body) and a render walks the tree. A template FILE keeps its tree, with
its includes expanded inside it, for as long as the files it was made
from keep their path, mtime and size; they are looked at again at most
once every ``REVALIDATE_S``, so an operator's edit under DATA/HTDOCS
shows within that time and a request in between touches no file.
"""

from __future__ import annotations

import os
import re
import threading
import time

from .objects import ServerObjects

_INCLUDE_RE = re.compile(r"#%([A-Za-z0-9_./-]+)%#")


# how long a template file's tree is served before the files it was
# made from are stat-ed again
REVALIDATE_S = 1.0

# node kinds of a parsed template: (kind, key-or-text, children)
_LIT, _FIELD, _ALT, _LOOP = range(4)

# renders of template files by what `lookup` found: a tree it held
# ("hit") or one it had to compile first (/metrics
# yacy_template_renders_total; the `template` attr of servlet.render)
_renders = {"hit": 0, "compiled": 0}
_renders_lock = threading.Lock()


def render_counts() -> dict[str, int]:
    with _renders_lock:
        return dict(_renders)


def _count(how: str) -> str:
    with _renders_lock:
        _renders[how] += 1
    return how


class _Compiled:
    """One template file's tree and the files it was made from: name ->
    (resolved path or None, mtime_ns, size) of the template and of every
    include the expansion asked for."""

    __slots__ = ("tree", "deps", "checked")

    def __init__(self, tree: tuple, deps: dict, checked: float):
        self.tree, self.deps, self.checked = tree, deps, checked


class TemplateEngine:
    def __init__(self, roots: list[str] | None = None):
        # template search path: later roots are fallbacks (the reference
        # overlays DATA/HTDOCS over htroot the same way)
        self.roots = list(roots or [])
        # (name, None) or (name, translation table, section) ->
        # _Compiled; written under the lock, read without it
        self._trees: dict[tuple, _Compiled] = {}
        self._compile_lock = threading.Lock()

    def resolve(self, name: str) -> str | None:
        for root in self.roots:
            p = os.path.join(root, name)
            if os.path.isfile(p):
                return p
        return None

    def render_file(self, name: str, props: ServerObjects) -> str:
        got = self.lookup(name)
        if got is None:
            raise FileNotFoundError(name)
        return self.render_tree(got[0], props)

    def render(self, template: str, props: ServerObjects) -> str:
        template = self._expand_includes(template, depth=0)
        return self.render_tree(_parse(template), props)

    @staticmethod
    def render_tree(tree: tuple, props: ServerObjects) -> str:
        out: list[str] = []
        m = props._map if isinstance(props, ServerObjects) else props
        _walk(tree, m.get, "", out)
        return "".join(out)

    # lint: unlocked-ok(the first look is one dict read, atomic under
    # the interpreter lock; whoever compiles or revalidates holds
    # _compile_lock and looks again)
    def lookup(self, name: str, i18n=None,
               section: str | None = None) -> tuple[tuple, str] | None:
        """(tree, "hit" | "compiled") of the template file `name`, None
        where no root has it. `i18n` (a TranslationTable) marks an .html
        page: its includes expand FIRST so the shared chrome translates
        too, the table rewrites the source under `section` (the file's
        name unless given: the generic admin page translates under the
        servlet's), and include tags the rewrite left expand after it.
        Properties substitute at render, so crawled content is never
        rewritten. A page under two tables, or two sections of one, has
        two trees."""
        key = (name, None) if i18n is None or i18n.is_empty() \
            else (name, i18n, section or name)
        now = time.monotonic()
        ent = self._trees.get(key)
        if ent is not None and now - ent.checked < REVALIDATE_S:
            return ent.tree, _count("hit")
        with self._compile_lock:
            ent = self._trees.get(key)
            if ent is not None and (now - ent.checked < REVALIDATE_S
                                    or self._unchanged(ent.deps)):
                ent.checked = now
                return ent.tree, _count("hit")
            deps: dict = {}
            source = self._read(name, deps)
            if source is None:
                self._trees.pop(key, None)
                return None
            source = self._expand_includes(source, 0, deps)
            if i18n is not None:
                if key[1] is not None:
                    source = i18n.translate(source, key[2])
                source = self._expand_includes(source, 0, deps)
            tree = _parse(source)
            self._trees[key] = _Compiled(tree, deps, now)
            return tree, _count("compiled")

    # -- internals -----------------------------------------------------------

    def _read(self, name: str, deps: dict | None) -> str | None:
        """Source of the file `name`; what was looked at goes to `deps`
        (stat BEFORE the read: an edit in between reads as a change at
        the next look)."""
        path = self.resolve(name)
        if path is None:
            if deps is not None:
                deps[name] = (None, 0, 0)
            return None
        if deps is not None:
            st = os.stat(path)
            deps[name] = (path, st.st_mtime_ns, st.st_size)
        with open(path, encoding="utf-8") as f:
            return f.read()

    def _unchanged(self, deps: dict) -> bool:
        try:
            for name, (path, mtime_ns, size) in deps.items():
                now = self.resolve(name)
                if now != path:
                    return False        # another root answers now
                if now is not None:
                    st = os.stat(now)
                    if (st.st_mtime_ns, st.st_size) != (mtime_ns, size):
                        return False
        except OSError:
            return False
        return True

    def _expand_includes(self, text: str, depth: int,
                         deps: dict | None = None) -> str:
        if depth > 8:
            return text

        def repl(m: re.Match) -> str:
            source = self._read(m.group(1), deps)
            if source is None:
                return ""
            return self._expand_includes(source, depth + 1, deps)

        return _INCLUDE_RE.sub(repl, text)


def _walk(nodes: tuple, get, prefix: str, out: list[str]) -> None:
    """Render parsed `nodes` into `out`. A key resolves under the loop
    row's prefix first, then bare."""
    for kind, key, sub in nodes:
        if kind == _LIT:
            out.append(key)
            continue
        v = get(prefix + key) if prefix else None
        if v is None:
            v = get(key)
        if kind == _FIELD:
            if v is not None:
                out.append(v)
            continue
        try:
            n = int(v or "0")
        except ValueError:
            n = 0
        if kind == _ALT:
            _walk(sub[n] if 0 <= n < len(sub) else sub[0], get, prefix, out)
        else:
            for it in range(n):
                _walk(sub, get, f"{prefix}{key}_{it}_", out)


def _parse(text: str) -> tuple:
    """One recursive-descent pass over a source with its includes
    expanded; an unterminated tag is literal text to the end."""
    nodes: list[tuple] = []
    lit: list[str] = []

    def node(kind: int, key: str, sub) -> None:
        if lit:
            nodes.append((_LIT, "".join(lit), None))
            lit.clear()
        nodes.append((kind, key, sub))

    i = 0
    n = len(text)
    while i < n:
        j = text.find("#", i)
        if j < 0 or j + 1 >= n:
            lit.append(text[i:])
            break
        lit.append(text[i:j])
        tag = text[j + 1]
        if tag == "[":
            end = text.find("]#", j + 2)
            if end < 0:
                lit.append(text[j:])
                break
            node(_FIELD, text[j + 2:end], None)
            i = end + 2
        elif tag == "(":
            end = text.find(")#", j + 2)
            if end < 0:
                lit.append(text[j:])
                break
            key = text[j + 2:end]
            close = f"#(/{key})#"
            k = text.find(close, end + 2)
            if k < 0:
                lit.append(text[j:])
                break
            node(_ALT, key, tuple(
                _parse(a) for a in _split_alternatives(text[end + 2:k])))
            i = k + len(close)
        elif tag == "{":
            end = text.find("}#", j + 2)
            if end < 0:
                lit.append(text[j:])
                break
            key = text[j + 2:end]
            k = _find_matching_loop_close(text, end + 2, key)
            if k < 0:
                lit.append(text[j:])
                break
            node(_LOOP, key, _parse(text[end + 2:k]))
            i = k + len(f"#{{/{key}}}#")
        else:
            lit.append("#")
            i = j + 1
    if lit:
        nodes.append((_LIT, "".join(lit), None))
    return tuple(nodes)


def _split_alternatives(body: str) -> list[str]:
    """Split on :: at nesting depth 0 (alternatives may nest tags)."""
    alts, cur, depth, i, n = [], [], 0, 0, len(body)
    while i < n:
        if body.startswith("#(", i) and not body.startswith("#(/", i):
            depth += 1
            cur.append(body[i:i + 2]); i += 2
        elif body.startswith("#(/", i):
            depth -= 1
            cur.append(body[i:i + 3]); i += 3
        elif depth == 0 and body.startswith("::", i):
            alts.append("".join(cur)); cur = []; i += 2
        else:
            cur.append(body[i]); i += 1
    alts.append("".join(cur))
    return alts


def _find_matching_loop_close(text: str, start: int, key: str) -> int:
    """Index of the #{/key}# matching the loop opened before `start`,
    honoring nested loops with the same key."""
    open_tag = f"#{{{key}}}#"
    close_tag = f"#{{/{key}}}#"
    depth = 1
    i = start
    while True:
        c = text.find(close_tag, i)
        if c < 0:
            return -1
        o = text.find(open_tag, i)
        if 0 <= o < c:
            depth += 1
            i = o + len(open_tag)
            continue
        depth -= 1
        if depth == 0:
            return c
        i = c + len(close_tag)
