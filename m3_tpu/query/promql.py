"""PromQL parser — precedence-climbing over the production grammar.

The reference wraps the upstream Prometheus parser
(ref: src/query/parser/promql/parse.go); this is a from-scratch parser
for the surface the engine executes:

    selector:       metric{l1="v", l2!="v", l3=~"re", l4!~"re"}[range]
                    ... offset <dur>
    subqueries:     expr[range:step]
    temporal fns:   rate increase delta irate idelta deriv
                    predict_linear holt_winters changes resets
                    avg|sum|min|max|count|last|stddev|stdvar|quantile|
                    present|absent _over_time
    functions:      abs ceil floor round exp ln log2 log10 sqrt sgn
                    clamp clamp_min clamp_max scalar vector time
                    timestamp histogram_quantile absent
                    label_replace label_join sort sort_desc
                    minute hour day_of_week day_of_month days_in_month
                    month year
    aggregations:   sum avg min max count stddev stdvar group
                    topk bottomk quantile count_values
                    [by (...) | without (...)]
    literals:       strings ("..." / '...')
    binary ops:     ^  * / %  + -  == != > < >= <= [bool]  and unless  or
                    with on/ignoring label matching and
                    group_left/group_right (many-to-one)
    literals:       numbers, durations (ms s m h d w)
"""

from __future__ import annotations

import dataclasses
import re

DUR_RE = re.compile(r"(\d+)(ms|s|m|h|d|w)")
_UNITS = {"ms": 10**6, "s": 10**9, "m": 60 * 10**9, "h": 3600 * 10**9,
          "d": 86400 * 10**9, "w": 7 * 86400 * 10**9}

TEMPORAL_FNS = {
    "rate", "increase", "delta", "irate", "idelta", "deriv",
    "predict_linear", "holt_winters", "changes", "resets",
    "avg_over_time", "sum_over_time", "min_over_time", "max_over_time",
    "count_over_time", "last_over_time", "stddev_over_time",
    "stdvar_over_time", "quantile_over_time", "present_over_time",
}
SCALAR_FNS = {
    "abs", "ceil", "floor", "round", "exp", "ln", "log2", "log10",
    "sqrt", "sgn", "clamp", "clamp_min", "clamp_max", "timestamp",
}
SPECIAL_FNS = {"scalar", "vector", "time", "histogram_quantile", "absent",
               "absent_over_time", "label_replace", "label_join",
               "sort", "sort_desc"}
CALENDAR_FNS = {"minute", "hour", "day_of_week", "day_of_month",
                "days_in_month", "month", "year"}
AGG_OPS = {
    "sum", "avg", "min", "max", "count", "stddev", "stdvar", "group",
    "topk", "bottomk", "quantile", "count_values",
}
PARAM_AGGS = {"topk", "bottomk", "quantile", "count_values"}

COMPARISONS = {"==", "!=", ">", "<", ">=", "<="}
SET_OPS = {"and", "or", "unless"}

# precedence, low -> high (prometheus: or < and/unless < cmp < +- < */% < ^)
_PRECEDENCE = [
    {"or"},
    {"and", "unless"},
    COMPARISONS,
    {"+", "-"},
    {"*", "/", "%"},
    {"^"},
]


@dataclasses.dataclass
class Selector:
    matchers: list  # [(kind, name, value)] kind in eq/neq/re/nre
    range_nanos: int = 0
    offset_nanos: int = 0
    # @ modifier: None, unix-nanos int, or "start"/"end" (resolved
    # against the OUTER query range, upstream semantics)
    at_nanos: object = None


@dataclasses.dataclass
class Subquery:
    expr: object
    range_nanos: int
    step_nanos: int  # 0 = default engine step
    offset_nanos: int = 0
    at_nanos: object = None


@dataclasses.dataclass
class Call:
    fn: str
    args: list


@dataclasses.dataclass
class StringLit:
    value: str


@dataclasses.dataclass
class Agg:
    op: str
    expr: object
    grouping: list[str]
    without: bool
    param: object = None  # scalar expr for topk/bottomk/quantile


@dataclasses.dataclass
class VectorMatch:
    on: bool = False  # True = on(...), False = ignoring(...) / none
    labels: tuple = ()
    group: str = ""  # "", "left", "right"
    include: tuple = ()  # group_left(extra_labels)


@dataclasses.dataclass
class BinOp:
    op: str
    lhs: object
    rhs: object
    bool_mod: bool = False
    matching: VectorMatch | None = None


@dataclasses.dataclass
class Scalar:
    value: float


_ESCAPES = {"n": "\n", "t": "\t", "r": "\r", "\\": "\\", '"': '"', "'": "'"}


def _unquote(s: str) -> str:
    """Backslash escapes processed on the unicode text directly — an
    encode/decode('unicode_escape') round trip would mojibake non-ASCII
    (UTF-8 bytes re-read with latin-1 semantics)."""
    return re.sub(
        r"\\(.)", lambda m: _ESCAPES.get(m.group(1), "\\" + m.group(1)), s
    )


def parse_duration(s: str) -> int:
    total = 0
    pos = 0
    for m in DUR_RE.finditer(s):
        if m.start() != pos:
            raise ValueError(f"bad duration {s!r}")
        total += int(m.group(1)) * _UNITS[m.group(2)]
        pos = m.end()
    if pos != len(s) or total == 0:
        raise ValueError(f"bad duration {s!r}")
    return total


TOKEN_RE = re.compile(
    r"""\s*(?:
        (?P<duration>\d+(?:ms|[smhdw])(?:\d+(?:ms|[smhdw]))*(?![a-zA-Z0-9_]))
      | (?P<number>0x[0-9a-fA-F]+|\d+\.\d+(?:e[+-]?\d+)?|\d+\.|\.\d+|\d+(?:e[+-]?\d+)?)
      | (?P<ident>[a-zA-Z_][a-zA-Z0-9_:]*(?:\.[a-zA-Z0-9_:]+)*)
      | (?P<string>"(?:[^"\\]|\\.)*"|'(?:[^'\\]|\\.)*')
      | (?P<op>=~|!~|!=|==|>=|<=|[{}()\[\],=+\-*/%^><:@])
    )""",
    re.VERBOSE,
)


def tokenize(q: str):
    # token text keeps its original case: keywords are recognized
    # case-insensitively AT KEYWORD POSITIONS only (Parser.peek_kw) —
    # lowercasing in the lexer would corrupt case-sensitive label or
    # metric names that happen to spell a keyword ({On="x"}, by (By))
    pos = 0
    out = []
    while pos < len(q):
        m = TOKEN_RE.match(q, pos)
        if not m or m.end() == pos:
            if q[pos:].strip() == "":
                break
            raise ValueError(f"parse error at {q[pos:pos+20]!r}")
        pos = m.end()
        kind = m.lastgroup
        out.append((kind, m.group(kind)))
    return out


class Parser:
    def __init__(self, query: str):
        self.toks = tokenize(query)
        self.pos = 0

    def peek(self, ahead: int = 0):
        i = self.pos + ahead
        return self.toks[i] if i < len(self.toks) else (None, None)

    def peek_kw(self, ahead: int = 0) -> str | None:
        """Token text lowercased for KEYWORD comparisons (PromQL
        keywords are case-insensitive; label/metric names are not —
        callers that consume names must use peek()/next() raw)."""
        v = self.peek(ahead)[1]
        return v.lower() if isinstance(v, str) else v

    def next(self):
        tok = self.peek()
        self.pos += 1
        return tok

    def expect(self, value):
        kind, v = self.next()
        if v != value:
            raise ValueError(f"expected {value!r}, got {v!r}")

    def parse(self):
        expr = self.parse_binary(0)
        if self.pos != len(self.toks):
            raise ValueError(f"trailing input at {self.peek()[1]!r}")
        return expr

    # --- binary expressions with precedence climbing ---

    def parse_binary(self, level: int):
        if level >= len(_PRECEDENCE):
            return self.parse_postfix()
        ops = _PRECEDENCE[level]
        right_assoc = ops == {"^"}
        lhs = self.parse_binary(level + 1)
        while self.peek_kw() in ops:
            op = self.next()[1].lower()
            bool_mod = False
            if self.peek_kw() == "bool":
                if op not in COMPARISONS:
                    raise ValueError("bool modifier on non-comparison")
                self.next()
                bool_mod = True
            matching = self.parse_matching()
            rhs = self.parse_binary(level if right_assoc else level + 1)
            lhs = BinOp(op, lhs, rhs, bool_mod=bool_mod, matching=matching)
        return lhs

    def parse_matching(self) -> VectorMatch | None:
        if self.peek_kw() not in ("on", "ignoring"):
            return None
        on = self.next()[1].lower() == "on"
        self.expect("(")
        labels = []
        while self.peek()[1] != ")":
            labels.append(self.next()[1])
            if self.peek()[1] == ",":
                self.next()
        self.expect(")")
        group, include = "", []
        if self.peek_kw() in ("group_left", "group_right"):
            group = self.next()[1].lower().removeprefix("group_")
            if self.peek()[1] == "(":
                self.next()
                while self.peek()[1] != ")":
                    include.append(self.next()[1])
                    if self.peek()[1] == ",":
                        self.next()
                self.expect(")")
        return VectorMatch(on, tuple(labels), group, tuple(include))

    # --- postfix: [range], [range:step] subquery, offset ---

    def parse_postfix(self):
        expr = self.parse_unary()
        while True:
            nxt = self.peek_kw()
            if nxt == "[":
                self.next()
                kind, dur = self.next()
                if kind != "duration":
                    raise ValueError(f"bad range {dur!r}")
                rng = parse_duration(dur)
                if self.peek()[1] == ":":
                    self.next()
                    step = 0
                    if self.peek()[1] != "]":
                        kind, sdur = self.next()
                        if kind != "duration":
                            raise ValueError(f"bad subquery step {sdur!r}")
                        step = parse_duration(sdur)
                    self.expect("]")
                    expr = Subquery(expr, rng, step)
                else:
                    self.expect("]")
                    if not isinstance(expr, Selector) or expr.range_nanos:
                        raise ValueError("range on non-selector (use [r:s])")
                    expr.range_nanos = rng
            elif nxt == "offset":
                self.next()
                kind, dur = self.next()
                if kind != "duration":
                    raise ValueError(f"bad offset {dur!r}")
                off = parse_duration(dur)
                if isinstance(expr, (Selector, Subquery)):
                    expr.offset_nanos = off
                else:
                    raise ValueError("offset on non-selector")
            elif nxt == "@":
                self.next()
                at = self._parse_at()
                if isinstance(expr, (Selector, Subquery)):
                    expr.at_nanos = at
                else:
                    raise ValueError("@ on non-selector")
            else:
                return expr

    def _parse_at(self):
        """`@ <unix seconds>` | `@ start()` | `@ end()` (upstream: the
        preprocessor pins the selector's evaluation timestamp)."""
        kind, v = self.next()
        sign = 1
        if v == "-":
            sign = -1
            kind, v = self.next()
        if kind == "number":
            return sign * int(float(v) * 1e9)
        if kind == "ident" and v in ("start", "end") and sign == 1:
            self.expect("(")
            self.expect(")")
            return v
        raise ValueError(f"bad @ timestamp {v!r}")

    def parse_unary(self):
        kind, v = self.peek()
        if v == "-":
            # prometheus: '^' binds tighter than unary minus (-2^2 == -4)
            self.next()
            return BinOp("-", Scalar(0.0), self.parse_binary(len(_PRECEDENCE) - 1))
        if v == "+":
            self.next()
            return self.parse_binary(len(_PRECEDENCE) - 1)
        if v == "(":
            self.next()
            expr = self.parse_binary(0)
            self.expect(")")
            return expr
        if kind == "number":
            self.next()
            return Scalar(float(int(v, 16)) if v.startswith("0x") else float(v))
        if kind == "string":
            self.next()
            return StringLit(_unquote(v[1:-1]))
        if kind == "duration":
            # bare durations only appear as function args (predict_linear
            # takes seconds as a number in real promql; keep strict here)
            raise ValueError(f"unexpected duration {v!r}")
        if kind == "ident":
            if v.lower() == "inf":
                self.next()
                return Scalar(float("inf"))
            if v.lower() == "nan":
                self.next()
                return Scalar(float("nan"))
            return self.parse_ident()
        if v == "{":
            return self.parse_selector(None)
        raise ValueError(f"unexpected token {v!r}")

    def parse_ident(self):
        _, name = self.next()
        nxt = self.peek()[1]
        # aggregation keywords are case-insensitive in PromQL
        if name.lower() in AGG_OPS and (nxt or "").lower() in ("(", "by", "without"):
            name = name.lower()
            return self.parse_agg(name)
        if (name in TEMPORAL_FNS or name in SCALAR_FNS
                or name in SPECIAL_FNS or name in CALENDAR_FNS) and nxt == "(":
            self.next()
            args = []
            if self.peek()[1] != ")":
                args.append(self.parse_binary(0))
                while self.peek()[1] == ",":
                    self.next()
                    args.append(self.parse_binary(0))
            self.expect(")")
            if name in TEMPORAL_FNS:
                # range arg position varies: quantile_over_time(phi, v[r])
                rv = next(
                    (a for a in args
                     if (isinstance(a, Selector) and a.range_nanos)
                     or isinstance(a, Subquery)),
                    None,
                )
                if rv is None:
                    raise ValueError(f"{name}() requires a range vector")
            return Call(name, args)
        return self.parse_selector(name)

    def parse_agg(self, op):
        grouping: list[str] = []
        without = False

        def read_grouping():
            nonlocal without
            without = self.next()[1].lower() == "without"
            self.expect("(")
            while self.peek()[1] != ")":
                grouping.append(self.next()[1])
                if self.peek()[1] == ",":
                    self.next()
            self.expect(")")

        if (self.peek()[1] or "").lower() in ("by", "without"):
            read_grouping()
        self.expect("(")
        args = [self.parse_binary(0)]
        while self.peek()[1] == ",":
            self.next()
            args.append(self.parse_binary(0))
        self.expect(")")
        if (self.peek()[1] or "").lower() in ("by", "without"):  # trailing grouping form
            read_grouping()
        param = None
        if op in PARAM_AGGS:
            if len(args) != 2:
                raise ValueError(f"{op} requires (param, vector)")
            param, expr = args
        else:
            if len(args) != 1:
                raise ValueError(f"{op} takes one argument")
            expr = args[0]
        return Agg(op, expr, grouping, without, param)

    def parse_selector(self, metric_name):
        matchers = []
        if metric_name is not None:
            matchers.append(("eq", b"__name__", metric_name.encode()))
        if self.peek()[1] == "{":
            self.next()
            while self.peek()[1] != "}":
                _, label = self.next()
                kind_map = {"=": "eq", "!=": "neq", "=~": "re", "!~": "nre"}
                _, opv = self.next()
                if opv not in kind_map:
                    raise ValueError(f"bad matcher op {opv!r}")
                skind, sval = self.next()
                if skind != "string":
                    raise ValueError("matcher value must be a string")
                value = sval[1:-1].encode().decode("unicode_escape").encode()
                matchers.append((kind_map[opv], label.encode(), value))
                if self.peek()[1] == ",":
                    self.next()
            self.expect("}")
        return Selector(matchers)


def parse(query: str):
    return Parser(query).parse()


def ast_size(node) -> int:
    """Count AST nodes — the slow-query log's device-vs-host node
    split is (fused nodes served) / (total - fused)."""
    if isinstance(node, Call):
        return 1 + sum(ast_size(a) for a in node.args)
    if isinstance(node, Agg):
        n = 1 + ast_size(node.expr)
        return n + (ast_size(node.param) if node.param is not None
                    else 0)
    if isinstance(node, BinOp):
        return 1 + ast_size(node.lhs) + ast_size(node.rhs)
    if isinstance(node, Subquery):
        return 1 + ast_size(node.expr)
    return 1
