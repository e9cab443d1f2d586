"""PQL lexer + recursive-descent parser.

Reference: pql/scanner.go (token rules) and pql/parser.go (grammar):

    query    := call*
    call     := IDENT '(' children? args? ')'
    children := call (',' call)*         # children come before args
    args     := key '=' value (',' ...)  # keys unique
    value    := IDENT(true|false|null|other) | STRING | INTEGER | FLOAT | list
    list     := '[' value (',' value)* ']'

Token rules match the reference scanner exactly: idents start with a letter
and continue with [A-Za-z0-9_\\-.]; numbers allow one leading '-' and one
'.'; strings are single- or double-quoted with \\n, \\\\, \\", \\' escapes.

``parse`` runs that grammar once per statement SHAPE (``pql.shape``: the
text with its integer argument values lifted out), not once per request:
one compiled-regex pass over the text yields the shape key and the
integer literals, a bounded memo maps the key to the parsed template,
and a request gets a fresh call tree with its own values bound
(``_parse_shaped``). Parameters are integers in argument-value position
only (``rowID=3``, list members, condition operands); identifiers,
strings, floats, timestamps, ``true/false/null`` and operators stay in
the key verbatim. Whatever the pass cannot prove it read exactly as
``Scanner`` would — a backslash anywhere, an integer past 18 digits, a
text no template verifies for, every error — goes through
``Parser(text).parse()`` unchanged, so each message and position is the
full grammar's. The memo is a pure function of the text: bounded, never
invalidated.
"""

from __future__ import annotations

import re
import threading

from ..errors import PilosaError
from . import shape
from .ast import Call, Condition, Query

EOF = "EOF"
WS = "WS"
IDENT = "IDENT"
STRING = "STRING"
BADSTRING = "BADSTRING"
INTEGER = "INTEGER"
FLOAT = "FLOAT"
EQ = "EQ"
COND = "COND"  # comparison operator of a BSI field condition
COMMA = "COMMA"
LPAREN = "LPAREN"
RPAREN = "RPAREN"
LBRACK = "LBRACK"
RBRACK = "RBRACK"
ILLEGAL = "ILLEGAL"


class ParseError(PilosaError):
    def __init__(self, pos, message):
        self.pos = pos
        super().__init__(f"{message} occurred at line {pos[0]}, char {pos[1]}")


# Token regexes (compiled once; the scanner was the query hot path's
# biggest cost as a char-at-a-time loop — PQL parse was ~55% of SetBit
# service time). Each preserves the reference scanner's rules exactly:
# idents start with a letter and continue [A-Za-z0-9_\-.]; numbers take
# an optional leading '-' and at most one '.'; strings are single- or
# double-quoted with \n \\ \" \' escapes and may not span lines.
_IDENT_RE = re.compile(r"[A-Za-z][A-Za-z0-9_\-.]*")
# [0-9] not \d: the reference's isDigit is ASCII-only, and \d would
# admit Unicode digits that int() then silently converts.
_NUMBER_RE = re.compile(
    r"-(?:[0-9]+(?:\.[0-9]*)?|\.[0-9]*)?|[0-9]+(?:\.[0-9]*)?")
_STRING_RE = re.compile(r"(['\"])((?:\\[n\\\"']|[^\\\n])*?)\1")
_ESCAPE_RE = re.compile(r"\\(.)")
_ESCAPES = {"n": "\n", "\\": "\\", '"': '"', "'": "'"}
_SIMPLE_TOKENS = {"=": EQ, ",": COMMA, "(": LPAREN, ")": RPAREN,
                  "[": LBRACK, "]": RBRACK}


class Scanner:
    def __init__(self, text: str):
        self._s = text
        self._i = 0
        self._line = 0
        self._char = 0

    def _advance(self, j: int) -> None:
        """Consume self._s[self._i:j], updating (line, char)."""
        s, i = self._s, self._i
        nl = s.count("\n", i, j)
        if nl:
            self._line += nl
            self._char = j - (s.rindex("\n", i, j) + 1)
        else:
            self._char += j - i
        self._i = j

    def scan(self):
        s, i = self._s, self._i
        pos = (self._line, self._char)
        if i >= len(s):
            self._i += 1
            return EOF, pos, ""
        ch = s[i]
        if ch.isspace():
            j, n = i + 1, len(s)
            while j < n and s[j].isspace():
                j += 1
            lit = s[i:j]
            self._advance(j)
            # WS positions here are exact even across newlines (the
            # reference's unread() lost the column there); harmless
            # divergence — WS is dropped before parsing.
            return WS, pos, lit
        if "a" <= ch <= "z" or "A" <= ch <= "Z":
            m = _IDENT_RE.match(s, i)
            self._advance(m.end())
            return IDENT, pos, m.group()
        if "0" <= ch <= "9" or ch == "-":
            m = _NUMBER_RE.match(s, i)
            lit = m.group()
            self._advance(m.end())
            return (FLOAT if "." in lit else INTEGER), pos, lit
        if ch == '"' or ch == "'":
            m = _STRING_RE.match(s, i)
            if m is None:  # unterminated / newline / bad escape
                return self._scan_badstring(pos)
            body = m.group(2)
            self._advance(m.end())
            if "\\" in body:
                body = _ESCAPE_RE.sub(
                    lambda mm: _ESCAPES[mm.group(1)], body)
            return STRING, pos, body
        if ch in "<>!=":
            # Comparison operators of the BSI condition syntax
            # (``age >= 20``): two-char forms first, then the single-
            # char ones; '=' alone stays the assignment token.
            two = s[i:i + 2]
            if two in ("==", "!=", "<=", ">=", "><"):
                self._advance(i + 2)
                return COND, pos, two
            if ch in "<>":
                self._advance(i + 1)
                return COND, pos, ch
        self._advance(i + 1)
        return _SIMPLE_TOKENS.get(ch, ILLEGAL), pos, ch

    def _scan_badstring(self, pos):
        """Failure path of the string rule: unterminated input, embedded
        newline, or invalid escape ⇒ BADSTRING with the partial body
        (same consumption as the reference's char loop)."""
        s, n = self._s, len(self._s)
        ending = s[self._i]
        j = self._i + 1
        buf = []
        while True:
            if j >= n:
                self._advance(n)
                self._i = n + 1  # past-EOF bump, as a char read would
                return BADSTRING, pos, "".join(buf)
            ch = s[j]
            if ch == ending:
                # The char loop accepts exactly what _STRING_RE does, so
                # a terminated string can't reach this fallback; if the
                # regex and loop ever diverge, fail loudly.
                raise AssertionError(
                    "string regex / badstring loop divergence")
            if ch == "\n":
                self._advance(j + 1)
                return BADSTRING, pos, "".join(buf)
            if ch == "\\":
                if j + 1 >= n:
                    self._advance(n)
                    self._i = n + 1
                    return BADSTRING, pos, "".join(buf)
                nxt = s[j + 1]
                if nxt in _ESCAPES:
                    buf.append(_ESCAPES[nxt])
                    j += 2
                    continue
                self._advance(j + 2)
                return BADSTRING, pos, "".join(buf)
            buf.append(ch)
            j += 1


class Parser:
    """Recursive-descent parser over a pre-tokenized stream.

    The reference scans lazily with an 8-token unread ring
    (scanner.go:216-263); tokenizing the whole query up front with WS
    dropped gives the same stream semantics while unread becomes an
    index decrement — the token plumbing was the parse hot path's
    remaining cost once the scanner went regex."""

    def __init__(self, text: str):
        sc = Scanner(text)
        toks: list[tuple] = []
        while True:
            item = sc.scan()
            if item[0] == WS:
                continue
            toks.append(item)
            if item[0] == EOF:
                break
        self._toks = toks
        self._pos = 0

    # -- token stream helpers

    def _scan(self):
        p = self._pos
        self._pos = p + 1
        toks = self._toks
        return toks[p] if p < len(toks) else toks[-1]  # EOF repeats

    def _unscan(self, n: int = 1):
        self._pos -= n

    # WS never enters the stream, so the skip forms are the plain ones.
    _scan_skip_ws = _scan
    _unscan_skip_ws = _unscan

    # -- grammar

    def parse(self) -> Query:
        query = Query()
        while True:
            tok, pos, lit = self._scan_skip_ws()
            if tok == EOF:
                return query
            if tok != IDENT:
                raise ParseError(pos, f"expected identifier, found {lit!r}")
            self._unscan()
            query.calls.append(self._parse_call())

    def _parse_call(self) -> Call:
        call = Call()
        tok, pos, lit = self._scan_skip_ws()
        if tok != IDENT:
            raise ParseError(pos, f"expected identifier, found {lit!r}")
        call.name = lit
        tok, pos, lit = self._scan_skip_ws()
        if tok != LPAREN:
            raise ParseError(pos, f"expected left paren, found {lit!r}")
        call.children = self._parse_children()
        call.args = self._parse_args()
        tok, pos, lit = self._scan_skip_ws()
        if tok != RPAREN:
            raise ParseError(pos, f"expected right paren, found {lit!r}")
        return call

    def _parse_children(self) -> list[Call]:
        children = []
        while True:
            tok, pos, lit = self._scan_skip_ws()
            if tok != IDENT:
                self._unscan_skip_ws(1)
                return children
            tok2, pos2, _ = self._scan()
            # A child call needs LPAREN ADJACENT to the ident — the
            # reference checks it with a raw (non-WS-skipping) scan
            # (parser.go:119-126), so "Bitmap (" falls through to args.
            # The WS-free stream keeps that rule via token positions.
            if tok2 != LPAREN or pos2 != (pos[0], pos[1] + len(lit)):
                self._unscan()            # the non-LPAREN token
                self._unscan_skip_ws(1)   # the IDENT
                return children
            self._unscan(2)
            children.append(self._parse_call())
            tok, pos, lit = self._scan_skip_ws()
            if tok == RPAREN:
                self._unscan()
                return children
            if tok != COMMA:
                raise ParseError(
                    pos, f"expected comma or right paren, found {lit!r}")

    def _parse_args(self) -> dict:
        args: dict = {}
        while True:
            tok, pos, lit = self._scan_skip_ws()
            if tok == RPAREN:
                self._unscan()
                return args
            if tok != IDENT:
                raise ParseError(pos, f"expected argument key, found {lit!r}")
            key = lit
            tok, pos, lit = self._scan_skip_ws()
            if tok == COND:
                value = self._parse_condition(lit, pos)
            elif tok == EQ:
                value = self._parse_value()
            else:
                raise ParseError(pos, f"expected equals sign, found {lit!r}")
            if key in args:
                raise ParseError(pos, f"argument key already used: {key}")
            args[key] = value
            tok, pos, lit = self._scan_skip_ws()
            if tok == RPAREN:
                self._unscan()
                return args
            if tok != COMMA:
                raise ParseError(
                    pos, f"expected comma or right paren, found {lit!r}")

    def _parse_condition(self, op: str, pos) -> Condition:
        """``field OP value``: the value must be an integer, except
        ``><`` (between), which takes a two-int [low, high] list."""
        value = self._parse_value()
        if op == "><":
            if (not isinstance(value, list) or len(value) != 2
                    or not all(isinstance(v, int)
                               and not isinstance(v, bool)
                               for v in value)):
                raise ParseError(
                    pos, "between requires a two-integer list")
        elif isinstance(value, bool) or not isinstance(value, int):
            raise ParseError(
                pos, f"condition value must be an integer: {value!r}")
        return Condition(op, value)

    def _parse_value(self, in_list: bool = False):
        tok, pos, lit = self._scan_skip_ws()
        if tok == IDENT:
            if lit == "true":
                return True
            if lit == "false":
                return False
            if lit == "null" and not in_list:
                return None
            return lit
        if tok == STRING:
            return lit
        if tok == INTEGER:
            try:
                v = int(lit)
            except ValueError:
                raise ParseError(pos, f"invalid integer: {lit!r}")
            # int64 bounds, like the reference's strconv.ParseInt(lit,
            # 10, 64) (parser.go:186,243) — larger ids are unparseable
            # there, and letting them through would let one stray
            # SetBit push max_slice past 2^43 and explode every later
            # query's slice enumeration.
            if not -(1 << 63) <= v < 1 << 63:
                raise ParseError(pos, f"invalid integer: {lit!r}")
            return v
        if tok == FLOAT and not in_list:
            try:
                return float(lit)
            except ValueError:
                raise ParseError(pos, f"invalid float: {lit!r}")
        if tok == LBRACK and not in_list:
            return self._parse_list()
        kind = "list" if in_list else "argument"
        raise ParseError(pos, f"invalid {kind} value: {lit!r}")

    def _parse_list(self) -> list:
        values = []
        while True:
            values.append(self._parse_value(in_list=True))
            tok, pos, lit = self._scan_skip_ws()
            if tok == RBRACK:
                return values
            if tok != COMMA:
                raise ParseError(pos, f"expected comma, found {lit!r}")


# Fast path for flat call lists — the serving hot shapes
# (SetBit/ClearBit/Bitmap/TopN streams of key=value args, no children,
# no escapes): one anchored regex per call instead of ~17 scanner
# tokens. Strings are restricted to charset-safe bodies (no quotes,
# escapes, or separators) so the arg split is unambiguous; ANY mismatch
# falls back to the full parser, which keeps exact reference error
# semantics (pql/parser.go:66-260).
_FAST_ARG = (r"[A-Za-z][A-Za-z0-9_\-.]*\s*=\s*"
             r"(?:-?[0-9]+(?![0-9.])|\"[A-Za-z0-9 _\-.:]*\""
             r"|'[A-Za-z0-9 _\-.:]*'"
             r"|\[\s*-?[0-9]+\s*(?:,\s*-?[0-9]+\s*)*\])")
_FAST_CALL_RE = re.compile(
    r"\s*([A-Za-z][A-Za-z0-9_\-.]*)\(\s*(?:(" + _FAST_ARG
    + r"(?:\s*,\s*" + _FAST_ARG + r")*))?\s*\)\s*")
_FAST_ARG_RE = re.compile(
    r"([A-Za-z][A-Za-z0-9_\-.]*)\s*=\s*"
    r"(?:(-?[0-9]+)(?![0-9.])|\"([A-Za-z0-9 _\-.:]*)\""
    r"|'([A-Za-z0-9 _\-.:]*)'"
    r"|\[\s*(-?[0-9]+\s*(?:,\s*-?[0-9]+\s*)*)\])")


# The single point-mutation wire shape — `SetBit(frame="x", rowID=N,
# columnID=M)` with the default labels in canonical order — gets one
# anchored regex and a direct Call build: at production per-op write
# rates the generic fast path's finditer + groups split was a measured
# slice of per-op latency (ISSUE 8). Digit counts bounded so int() is
# always < 2^63; any other shape (custom labels, timestamp, view,
# reordered args) falls through unchanged.
_POINT_MUTATE_RE = re.compile(
    r'\s*(SetBit|ClearBit)\(\s*frame\s*=\s*"([A-Za-z0-9 _\-.:]*)"\s*,'
    r'\s*rowID\s*=\s*([0-9]{1,18})\s*,'
    r'\s*columnID\s*=\s*([0-9]{1,18})\s*\)\s*$')


def _parse_fast(text: str):
    """Query for a flat call list, or None when any call needs the full
    grammar (children, non-integer lists, floats, escapes, bool/null
    idents). Integer lists — the TopN exact-phase forwarding shape —
    stay on the fast path."""
    m = _POINT_MUTATE_RE.match(text)
    if m is not None:
        call = Call(m.group(1), {"frame": m.group(2),
                                 "rowID": int(m.group(3)),
                                 "columnID": int(m.group(4))})
        q = Query()
        q.calls.append(call)
        return q
    query = Query()
    i = 0
    n = len(text)
    while i < n:
        m = _FAST_CALL_RE.match(text, i)
        if m is None:
            return None if text[i:].strip() else query
        call = Call()
        call.name = m.group(1)
        body = m.group(2)
        if body:
            args = call.args
            count = 0
            for am in _FAST_ARG_RE.finditer(body):
                key, intv, dq, sq, lst = am.groups()
                if intv is not None:
                    v = int(intv)
                    if not -(1 << 63) <= v < 1 << 63:
                        return None  # full parser raises the bound error
                    args[key] = v
                elif lst is not None:
                    # Empty lists are a grammar error (the full parser
                    # requires >=1 value), so the regex requires one.
                    vals = [int(x) for x in lst.split(",")]
                    if any(not -(1 << 63) <= v < 1 << 63
                           for v in vals):
                        return None
                    args[key] = vals
                else:
                    args[key] = dq if dq is not None else sq
                count += 1
            if len(args) != count:
                return None  # duplicate key: full parser raises
        query.calls.append(call)
        i = m.end()
    return query


# The shape pass. Both patterns start on one character set (the search
# skips to the next quote or value delimiter) and read the same two
# things. A quoted string with no backslash in it is taken whole, so
# digits inside it are never seen. An integer of at most 18 digits
# (always inside int64) that follows a value delimiter — the `=` `[` `,`
# `<` `>` that end EQ, LBRACK, COMMA and every COND token — and blanks,
# and is not the head of a float, is a parameter: `_LIFT_KEY_RE` keeps
# group 1 (the string, or the delimiter and blanks) and writes `?` for
# the digits (and after a string: a key is only compared),
# `_LIFT_INTS_RE` captures the digits. Digits inside identifiers (`f1`,
# `a-5`) follow an identifier character, never a delimiter. Every other
# character — blanks, names, floats, longer integers, stray quotes,
# illegal characters — stays in the key as written, so two texts with
# one key and one count of literals differ only in their integers'
# digits, and `Scanner` reads them alike (a literal `?` is an ILLEGAL
# token: a text with one never parses, so never becomes a template;
# `_parse_shaped` holds a template to the count AND the values of the
# integers its own full parse found). Backslashes are not modelled:
# such a text takes the full parser.
_LIFT_KEY_RE = re.compile(
    r"""(["'=\[,<>](?:(?<=")[^"\n]*"|(?<=')[^'\n]*'"""
    r"""|(?<=[=\[,<>])\s*(?=-?[0-9]{1,18}(?![0-9.]))))"""
    r"""(?:(?<!["'])-?[0-9]{1,18})?""")
_LIFT_INTS_RE = re.compile(
    r"""["'=\[,<>](?:(?<=")[^"\n]*"|(?<=')[^'\n]*'"""
    r"""|(?<=[=\[,<>])\s*(-?[0-9]{1,18})(?![0-9.]))""")

_SHAPE_TEXT_MAX = 4096   # longer bodies are write batches: _parse_fast's
_SHAPE_ENTRIES = 512
# shape key -> (call specs, parameter count); insertion-ordered, the
# oldest entry leaves first. Read without the lock (one dict look-up).
_shapes: dict[str, tuple] = {}
_shapes_mu = threading.Lock()
# Texts the shape pass could not parameterise (the `full` of
# /debug/vars.planShapes, with the planner's own): a plain bump.
shape_stats = {"full": 0}


def _parse_shaped(text: str) -> Query:
    """``Parser(text).parse()`` through the shape memo (module
    docstring): a known shape costs the regex pass and a bind."""
    if len(text) > _SHAPE_TEXT_MAX or "\\" in text:
        shape_stats["full"] += 1
        return Parser(text).parse()
    key = _LIFT_KEY_RE.sub(r"\1?", text)
    vals = [int(x) for x in _LIFT_INTS_RE.findall(text) if x]
    ent = _shapes.get(key)
    if ent is not None and ent[1] == len(vals):
        return Query([shape.bind_call(spec, vals) for spec in ent[0]])
    query = Parser(text).parse()
    found: list = []
    specs = []
    n = 0
    for call in query.calls:
        shape.ints_of(call, found)
        spec, n = shape.spec_of(call, n)
        specs.append(spec)
    if found != vals:
        # The pass and the grammar disagree on this text's integers (one
        # of 19 digits, say): it is parsed in full every time.
        shape_stats["full"] += 1
        return query
    with _shapes_mu:
        if len(_shapes) >= _SHAPE_ENTRIES:
            _shapes.pop(next(iter(_shapes)), None)
        _shapes[key] = (tuple(specs), n)
    return query


def parse(text: str) -> Query:
    fast = _parse_fast(text)
    if fast is not None:
        return fast
    return _parse_shaped(text)
