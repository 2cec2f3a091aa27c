"""The plain reference: a serial parse forest computed on the host.

This module is the benchmark's own copy of the parser's semantics and
imports nothing of the program.  It builds the segment automaton of the
paper (arXiv:2503.06763, Sect. 2.2-2.4) from the pattern string and runs the
serial matrix parser of Fig. 10 over it: one forward pass from the initial
segments, one backward pass over the reversed arcs from the final segments,
and their intersection per text position.  The result is the clean SLPF as
an ``(n + 1, ℓ)`` Boolean matrix, column ``r`` holding the segments that lie
on some accepting path at boundary ``r``.

The automaton's construction follows the program's numbering exactly
(preorder operator numbering, Glushkov follow sets, byte classes in order of
first appearance, segments enumerated left to right from every anchor), so
columns compare index for index.

A state set is a Python integer used as a bitset, and a step ORs the
successor sets of the set bits a byte-chunk at a time through precomputed
tables: exact, serial, and about a microsecond per character for a small
automaton (ℓ = 37) and a few for ℓ = 257.
"""

from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import numpy as np

# ------------------------------------------------------------------- syntax
# AST nodes are tuples: ("lit", byte) ("class", ranges) ("eps",)
# ("cat", items) ("alt", items) ("star", item) ("plus", item) ("opt", item)
# ("rep", item, lo, hi) ("group", item)

_WILDCARD = ((0, 9), (11, 255))


def _char_class(ranges, negated=False):
    merged: List[List[int]] = []
    for lo, hi in sorted(ranges):
        if merged and lo <= merged[-1][1] + 1:
            merged[-1][1] = max(merged[-1][1], hi)
        else:
            merged.append([lo, hi])
    if negated:
        out, prev = [], 0
        for lo, hi in merged:
            if lo > prev:
                out.append([prev, lo - 1])
            prev = max(prev, hi + 1)
        if prev <= 255:
            out.append([prev, 255])
        merged = out
    return ("class", tuple((lo, hi) for lo, hi in merged))


class _Syntax:
    _ESC = {"n": 10, "t": 9, "r": 13, "0": 0}

    def __init__(self, src: str):
        self.src, self.pos = src, 0

    def peek(self):
        return self.src[self.pos] if self.pos < len(self.src) else None

    def take(self):
        self.pos += 1
        return self.src[self.pos - 1]

    def fail(self, msg):
        raise ValueError(f"{msg} at {self.pos} in {self.src!r}")

    def alt(self):
        items = [self.cat()]
        while self.peek() == "|":
            self.take()
            items.append(self.cat())
        return items[0] if len(items) == 1 else ("alt", tuple(items))

    def cat(self):
        items = []
        while self.peek() is not None and self.peek() not in "|)":
            items.append(self.repeat())
        if not items:
            return ("eps",)
        return items[0] if len(items) == 1 else ("cat", tuple(items))

    def repeat(self):
        node = self.atom()
        while True:
            c = self.peek()
            if c in ("*", "+", "?"):
                self.take()
                node = ({"*": "star", "+": "plus", "?": "opt"}[c], node)
            elif c == "{":
                self.take()
                node = self.bound(node)
            else:
                return node

    def number(self):
        digits = ""
        while self.peek() is not None and self.peek().isdigit():
            digits += self.take()
        return digits

    def bound(self, node):
        lo = self.number()
        if not lo:
            self.fail("expected a digit")
        hi = lo
        if self.peek() == ",":
            self.take()
            hi = self.number() or None
        if self.peek() != "}":
            self.fail("unterminated repetition")
        self.take()
        lo, hi = int(lo), (int(hi) if hi is not None else None)
        if hi is not None and hi < lo:
            self.fail("bad repetition bounds")
        return ("rep", node, lo, hi)

    def atom(self):
        c = self.peek()
        if c is None:
            self.fail("unexpected end")
        if c == "(":
            self.take()
            inner = self.alt()
            if self.peek() != ")":
                self.fail("unbalanced parenthesis")
            self.take()
            return ("group", inner)
        if c == "[":
            return self.klass()
        if c == ".":
            self.take()
            return ("class", _WILDCARD)
        if c == "\\":
            self.take()
            e = self.peek()
            if e is None:
                self.fail("dangling escape")
            self.take()
            if e == "e":
                return ("eps",)
            if e in self._ESC:
                return ("lit", self._ESC[e])
            if e == "d":
                return _char_class([(48, 57)])
            if e == "w":
                return _char_class([(48, 57), (65, 90), (97, 122), (95, 95)])
            if e == "s":
                return _char_class([(9, 13), (32, 32)])
            return ("lit", ord(e))
        if c in "|)*+?{}":
            self.fail(f"unexpected {c!r}")
        self.take()
        return ("lit", ord(c))

    def class_char(self):
        c = self.take()
        if c == "\\":
            e = self.take()
            return self._ESC.get(e, ord(e))
        return ord(c)

    def klass(self):
        self.take()
        negated = self.peek() == "^"
        if negated:
            self.take()
        ranges, first = [], True
        while True:
            c = self.peek()
            if c is None:
                self.fail("unterminated class")
            if c == "]" and not first:
                self.take()
                break
            first = False
            lo = self.class_char()
            if self.peek() == "-" and self.pos + 1 < len(self.src) and self.src[self.pos + 1] != "]":
                self.take()
                hi = self.class_char()
                ranges.append((lo, hi))
            else:
                ranges.append((lo, lo))
        return _char_class(ranges, negated)


def parse_pattern(pattern: str):
    p = _Syntax(pattern)
    node = p.alt()
    if p.pos != len(pattern):
        p.fail("trailing input")
    return node


def _nullable(n) -> bool:
    k = n[0]
    if k == "eps":
        return True
    if k in ("lit", "class"):
        return False
    if k == "cat":
        return all(_nullable(i) for i in n[1])
    if k == "alt":
        return any(_nullable(i) for i in n[1])
    if k in ("star", "opt"):
        return True
    if k == "rep":
        return n[2] == 0 or _nullable(n[1])
    return _nullable(n[1])                      # plus, group


def _inf_ambiguous(n) -> bool:
    k = n[0]
    if k in ("lit", "class", "eps"):
        return False
    if k in ("cat", "alt"):
        return any(_inf_ambiguous(i) for i in n[1])
    if k in ("star", "plus"):
        return _nullable(n[1]) or _inf_ambiguous(n[1])
    if k == "rep" and n[3] is None and _nullable(n[1]):
        return True
    return _inf_ambiguous(n[1])


# ---------------------------------------------------------------- numbering
# Symbols: list of (kind, ranges); kinds "term" "eps" "open" "close" "end".
# The linear RE is a tree over symbol ids: ("sym", sid) and the AST kinds.


class _Numbering:
    def __init__(self):
        self.syms: List[Tuple[str, tuple]] = []

    def new(self, kind, ranges=None) -> int:
        self.syms.append((kind, ranges))
        return len(self.syms) - 1

    def pair(self, inner):
        o, c = self.new("open"), self.new("close")
        return ("cat", (("sym", o), inner, ("sym", c)))

    def go(self, n):
        k = n[0]
        if k == "lit":
            return ("sym", self.new("term", ((n[1], n[1]),)))
        if k == "class":
            return ("sym", self.new("term", n[1]))
        if k == "eps":
            return ("sym", self.new("eps"))
        if k == "cat":
            o, c = self.new("open"), self.new("close")
            return ("cat", (("sym", o),) + tuple(self.go(i) for i in n[1]) + (("sym", c),))
        if k == "alt":
            o, c = self.new("open"), self.new("close")
            return ("cat", (("sym", o), ("alt", tuple(self.go(i) for i in n[1])), ("sym", c)))
        if k in ("star", "plus", "opt"):
            o, c = self.new("open"), self.new("close")
            return ("cat", (("sym", o), (k, self.go(n[1])), ("sym", c)))
        if k == "group":
            o, c = self.new("open"), self.new("close")
            return ("cat", (("sym", o), self.go(n[1]), ("sym", c)))
        if k == "rep":
            item, lo, hi = n[1], n[2], n[3]
            o, c = self.new("open"), self.new("close")
            mandatory = [self.go(item) for _ in range(lo)]
            if hi is None:
                tail = ("star", self.go(item))
            else:
                tail = None
                for _ in range(hi - lo):
                    inner = self.go(item)
                    tail = ("opt", inner if tail is None else ("cat", (inner, tail)))
            parts = tuple(mandatory) + ((tail,) if tail is not None else ())
            body = ("cat", ()) if not parts else parts[0] if len(parts) == 1 else ("cat", parts)
            return ("cat", (("sym", o), body, ("sym", c)))
        raise TypeError(n)


def _glushkov(n, follow: Dict[int, set]):
    k = n[0]
    if k == "sym":
        return False, {n[1]}, {n[1]}
    if k == "cat":
        nullable, first, last = True, set(), set()
        for item in n[1]:
            nl, f, l = _glushkov(item, follow)
            for p in last:
                follow.setdefault(p, set()).update(f)
            if nullable:
                first |= f
            last = last | l if nl else set(l)
            nullable = nullable and nl
        return nullable, first, last
    if k == "alt":
        nullable, first, last = False, set(), set()
        for item in n[1]:
            nl, f, l = _glushkov(item, follow)
            nullable, first, last = nullable or nl, first | f, last | l
        return nullable, first, last
    nl, f, l = _glushkov(n[1], follow)
    if k in ("star", "plus"):
        for p in l:
            follow.setdefault(p, set()).update(f)
    return (True if k in ("star", "opt") else nl), f, l


class Automaton:
    """The segment automaton of one pattern: ℓ segments, byte classes, arcs."""

    def __init__(self, pattern: str, inf_limit: int = 2):
        ast = parse_pattern(pattern)
        num = _Numbering()
        linear = num.go(ast)
        syms = num.syms
        end = len(syms)
        syms.append(("end", None))
        follow: Dict[int, set] = {}
        nullable, first, last = _glushkov(linear, follow)
        for p in last:
            follow.setdefault(p, set()).add(end)
        if nullable:
            first = set(first) | {end}
        # the copy fixes each set's iteration order, which orders the walk
        follow = {k: set(v) for k, v in follow.items()}

        # byte classes: bytes matched by the same terminals share a class;
        # class 0 is the dead class, the others in order of first byte
        terms = [i for i, s in enumerate(syms) if s[0] == "term"]
        sig_class: Dict[frozenset, int] = {}
        self.byte_class = np.zeros(256, dtype=np.int64)
        term_classes: Dict[int, set] = {t: set() for t in terms}
        for b in range(256):
            sig = frozenset(t for t in terms if any(lo <= b <= hi for lo, hi in syms[t][1]))
            if sig:
                cid = sig_class.setdefault(sig, len(sig_class) + 1)
                self.byte_class[b] = cid
                for t in sig:
                    term_classes[t].add(cid)
        self.n_classes = len(sig_class) + 1

        # segments: walk Fol from every anchor through metasymbols to the
        # next end-letter, each metasymbol at most ``limit`` times per walk
        limit = inf_limit if _inf_ambiguous(ast) else 1
        index: Dict[tuple, int] = {}
        segs: List[tuple] = []
        initial: List[bool] = []

        def walk(start, is_initial):
            stack = [((start,), {start: 1})]
            while stack:
                path, counts = stack.pop()
                if syms[path[-1]][0] in ("term", "end"):
                    if path in index:
                        initial[index[path]] |= is_initial
                    else:
                        index[path] = len(segs)
                        segs.append(path)
                        initial.append(is_initial)
                    continue
                for nxt in follow.get(path[-1], ()):
                    c = counts.get(nxt, 0)
                    if c < limit:
                        stack.append((path + (nxt,), {**counts, nxt: c + 1}))

        for s in sorted(first):
            walk(s, True)
        for t in terms:
            for s in sorted(follow.get(t, ())):
                walk(s, False)

        self.ell = len(segs)
        by_first: Dict[int, List[int]] = {}
        for i, seg in enumerate(segs):
            by_first.setdefault(seg[0], []).append(i)
        # succ[c][col]: bitset of the segments that follow ``col`` on class c
        self.succ = [[0] * self.ell for _ in range(self.n_classes)]
        self.pred = [[0] * self.ell for _ in range(self.n_classes)]
        for col, seg in enumerate(segs):
            el = seg[-1]
            if el == end:
                continue
            rows = sorted({r for s in follow.get(el, ()) for r in by_first.get(s, ())})
            for c in term_classes[el]:
                for r in rows:
                    self.succ[c][col] |= 1 << r
                    self.pred[c][r] |= 1 << col
        self.initial = sum(1 << i for i, v in enumerate(initial) if v)
        self.final = sum(1 << i for i, seg in enumerate(segs) if seg[-1] == end)


# ------------------------------------------------------------------ parsing

_CHUNK = 8


def _step_tables(arcs: List[List[int]]) -> List[List[List[int]]]:
    """tables[c][j][v]: OR of arcs[c][8j + b] over the set bits b of v."""
    out = []
    for per_col in arcs:
        ell = len(per_col)
        tabs = []
        for j in range(-(-ell // _CHUNK)):
            t = [0] * (1 << _CHUNK)
            for v in range(1, 1 << _CHUNK):
                low = (v & -v).bit_length() - 1
                col = j * _CHUNK + low
                t[v] = t[v & (v - 1)] | (per_col[col] if col < ell else 0)
            tabs.append(t)
        out.append(tabs)
    return out


def _run(tables, start: int, classes: Sequence[int]) -> List[int]:
    mask = (1 << _CHUNK) - 1
    out = [start]
    s = start
    for c in classes:
        tabs = tables[c]
        nxt, j = 0, 0
        while s:
            v = s & mask
            if v:
                nxt |= tabs[j][v]
            s >>= _CHUNK
            j += 1
        s = nxt
        out.append(s)
    return out


class Reference:
    """Serial SLPF of texts for one pattern (the benchmark's plain reference)."""

    def __init__(self, pattern: str):
        self.auto = Automaton(pattern)
        self.ell = self.auto.ell
        self._fwd = _step_tables(self.auto.succ)
        self._bwd = _step_tables(self.auto.pred)
        self.n_bytes = -(-self.ell // 8)

    def packed_columns(self, text: bytes, *, clean: bool = True) -> np.ndarray:
        """(n + 1, ⌈ℓ/8⌉) uint8: column r's segment bits, little-endian bit
        order (segment i is bit i % 8 of byte i // 8).

        ``clean=False`` skips the backward pass: each column then holds every
        segment reachable from the start, on an accepting path or not.  That
        breaks the configuration's guarantee of a clean forest, and is the
        benchmark's control (``harness/control.py``)."""
        cls = self.auto.byte_class[np.frombuffer(text, dtype=np.uint8)].tolist()
        fwd = _run(self._fwd, self.auto.initial, cls)
        nb = self.n_bytes
        if clean:
            bwd = _run(self._bwd, self.auto.final, cls[::-1])[::-1]
            raw = b"".join((f & b).to_bytes(nb, "little") for f, b in zip(fwd, bwd))
        else:
            raw = b"".join(f.to_bytes(nb, "little") for f in fwd)
        return np.frombuffer(raw, dtype=np.uint8).reshape(len(fwd), nb)


def unpack_columns(packed: np.ndarray, ell: int) -> np.ndarray:
    """Inverse of ``pack_columns``: (n + 1, ℓ) bool."""
    return np.unpackbits(packed, axis=1, count=ell, bitorder="little").astype(bool)


def pack_columns(columns: np.ndarray) -> np.ndarray:
    """A Boolean (n + 1, ℓ) column matrix in ``Reference.packed_columns``'s
    layout."""
    return np.packbits(np.asarray(columns, dtype=bool), axis=1, bitorder="little")
