"""Typed expression IR + JAX lowering.

Reference parity: the row-expression layer that presto-main compiles to JVM
bytecode per query (``ExpressionCompiler`` / ``PageProcessor`` /
``CursorProcessor`` — SURVEY.md §2.1 "Expression JIT"). TPU-first redesign
(SURVEY.md §7 step 2): instead of emitting bytecode, expressions *lower to
jaxprs* — ``eval_expr`` is called at trace time inside the fragment's
``jax.jit``, so XLA is the codegen and fuses the whole expression tree into
the surrounding kernel. There is no interpreter at runtime.

Null semantics are SQL three-valued logic, carried as (data, valid) pairs
where ``valid=None`` statically means "no nulls" so XLA never materialises
masks for null-free columns.

String expressions never touch string bytes on device: dictionary columns
are int32 ids with an order-preserving host dictionary (presto_tpu.page),
so =/< compare ids against host-resolved literal ids, and LIKE & friends
evaluate host-side over the dictionary into a boolean LUT that the device
gathers (SURVEY.md §7 "Strings on TPU"). Dictionaries are static pytree
metadata, so all of that folds at trace time.

Decimal semantics (exact, scaled int64):
  a ± b   -> rescale to max(scale)        (exact)
  a * b   -> scale_a + scale_b            (exact; raises if scale > 18)
  a / b   -> DOUBLE                       (documented deviation: the
             reference returns decimal; int128 division lands later)
"""

from __future__ import annotations

import dataclasses
import datetime
import re
from typing import Any, List, Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from presto_tpu import types as T
from presto_tpu.page import Page


# --------------------------------------------------------------------------
# IR nodes (analyzer output; see SURVEY.md §2.1 "Analyzer")
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Expr:
    """Base expression; ``dtype`` is resolved at analysis time."""

    def children(self) -> Sequence["Expr"]:
        return ()

    @property
    def dtype(self) -> T.DataType:
        raise NotImplementedError


@dataclasses.dataclass(frozen=True)
class ColumnRef(Expr):
    name: str
    _dtype: T.DataType

    @property
    def dtype(self):
        return self._dtype

    def __str__(self):
        return self.name


@dataclasses.dataclass(frozen=True)
class Literal(Expr):
    """A constant. Decimal literals carry their *unscaled* int value;
    date literals carry epoch days; string literals carry the python str
    (resolved against the column dictionary at lowering time)."""

    value: Any
    _dtype: T.DataType

    @property
    def dtype(self):
        return self._dtype

    def __str__(self):
        return repr(self.value)

    @classmethod
    def of(cls, value: Any) -> "Literal":
        """Infer a literal from a python value (analyzer convenience)."""
        if value is None:
            return cls(None, T.BIGINT)
        if isinstance(value, bool):
            return cls(value, T.BOOLEAN)
        if isinstance(value, int):
            return cls(value, T.BIGINT)
        if isinstance(value, float):
            return cls(value, T.DOUBLE)
        if isinstance(value, str):
            return cls(value, T.VARCHAR)
        if isinstance(value, datetime.date):
            days = (value - datetime.date(1970, 1, 1)).days
            return cls(days, T.DATE)
        raise TypeError(f"cannot infer literal type for {value!r}")


@dataclasses.dataclass(frozen=True)
class Arithmetic(Expr):
    op: str  # + - * / %
    left: Expr
    right: Expr
    _dtype: T.DataType

    def children(self):
        return (self.left, self.right)

    @property
    def dtype(self):
        return self._dtype


@dataclasses.dataclass(frozen=True)
class Negate(Expr):
    arg: Expr

    def children(self):
        return (self.arg,)

    @property
    def dtype(self):
        return self.arg.dtype


@dataclasses.dataclass(frozen=True)
class Compare(Expr):
    op: str  # = <> < <= > >=
    left: Expr
    right: Expr

    def children(self):
        return (self.left, self.right)

    @property
    def dtype(self):
        return T.BOOLEAN


@dataclasses.dataclass(frozen=True)
class And(Expr):
    terms: Tuple[Expr, ...]

    def children(self):
        return self.terms

    @property
    def dtype(self):
        return T.BOOLEAN


@dataclasses.dataclass(frozen=True)
class Or(Expr):
    terms: Tuple[Expr, ...]

    def children(self):
        return self.terms

    @property
    def dtype(self):
        return T.BOOLEAN


@dataclasses.dataclass(frozen=True)
class Not(Expr):
    arg: Expr

    def children(self):
        return (self.arg,)

    @property
    def dtype(self):
        return T.BOOLEAN


@dataclasses.dataclass(frozen=True)
class IsNull(Expr):
    arg: Expr
    negate: bool = False

    def children(self):
        return (self.arg,)

    @property
    def dtype(self):
        return T.BOOLEAN


@dataclasses.dataclass(frozen=True)
class Case(Expr):
    """Searched CASE: WHEN cond THEN value ... ELSE default."""

    whens: Tuple[Tuple[Expr, Expr], ...]
    default: Optional[Expr]
    _dtype: T.DataType

    def children(self):
        out: List[Expr] = []
        for c, v in self.whens:
            out += [c, v]
        if self.default is not None:
            out.append(self.default)
        return tuple(out)

    @property
    def dtype(self):
        return self._dtype


@dataclasses.dataclass(frozen=True)
class Cast(Expr):
    arg: Expr
    to: T.DataType

    def children(self):
        return (self.arg,)

    @property
    def dtype(self):
        return self.to


@dataclasses.dataclass(frozen=True)
class MathFunc(Expr):
    """Scalar math over one numeric argument (reference: the scalar
    function registry's math builtins — SURVEY.md §2.1 "Function
    registry"). abs/sign/round/truncate preserve the argument type,
    floor/ceil return BIGINT, the rest return DOUBLE; sqrt/ln of
    out-of-domain values return NULL (SQL-adjacent; the reference
    raises — documented deviation, keeps the kernel branch-free)."""

    func: str
    arg: Expr

    def children(self):
        return (self.arg,)

    @property
    def dtype(self):
        if self.func == "sign" and self.arg.dtype.is_decimal:
            # ±1/0 is an integer; keeping the decimal type would read
            # the bare sign as an unscaled value (off by 10^-scale)
            return T.BIGINT
        if self.func in ("abs", "sign", "round", "truncate"):
            return self.arg.dtype
        if self.func in ("floor", "ceil"):
            return T.BIGINT
        return T.DOUBLE


@dataclasses.dataclass(frozen=True)
class MathFunc2(Expr):
    """Two-argument scalar math: power | atan2 | log(base, x) |
    round(x, digits) | truncate(x, digits). round/truncate preserve the
    first argument's type; the rest return DOUBLE."""

    func: str
    left: Expr
    right: Expr

    def children(self):
        return (self.left, self.right)

    @property
    def dtype(self):
        if self.func in ("round", "truncate"):
            return self.left.dtype
        return T.DOUBLE


@dataclasses.dataclass(frozen=True)
class DateTrunc(Expr):
    """date_trunc(unit, x) over date (epoch days) or timestamp (epoch
    microseconds): unit in year|quarter|month|week|day (+ hour|minute|
    second for timestamps). Branch-free civil-calendar integer math on
    device (see _civil_from_days / _days_from_civil)."""

    unit: str
    arg: Expr

    def children(self):
        return (self.arg,)

    @property
    def dtype(self):
        return self.arg.dtype


@dataclasses.dataclass(frozen=True)
class Between(Expr):
    arg: Expr
    low: Expr
    high: Expr
    negate: bool = False

    def children(self):
        return (self.arg, self.low, self.high)

    @property
    def dtype(self):
        return T.BOOLEAN


@dataclasses.dataclass(frozen=True)
class InList(Expr):
    arg: Expr
    values: Tuple[Expr, ...]  # literals
    negate: bool = False

    def children(self):
        return (self.arg,) + self.values

    @property
    def dtype(self):
        return T.BOOLEAN


@dataclasses.dataclass(frozen=True)
class Like(Expr):
    """LIKE with a literal pattern — evaluated host-side over the
    dictionary into a boolean LUT, gathered on device."""

    arg: Expr
    pattern: str
    negate: bool = False

    def children(self):
        return (self.arg,)

    @property
    def dtype(self):
        return T.BOOLEAN


@dataclasses.dataclass(frozen=True)
class Extract(Expr):
    """EXTRACT(field FROM date) — field in year/month/day/quarter."""

    field: str
    arg: Expr

    def children(self):
        return (self.arg,)

    @property
    def dtype(self):
        return T.BIGINT


@dataclasses.dataclass(frozen=True)
class Coalesce(Expr):
    args: Tuple[Expr, ...]
    _dtype: T.DataType

    def children(self):
        return self.args

    @property
    def dtype(self):
        return self._dtype


@dataclasses.dataclass(frozen=True)
class Param(Expr):
    """A scalar placeholder bound before fragment compilation (used for
    uncorrelated scalar subqueries: the executor runs the subplan, then
    substitutes the resulting Literal — reference analogue: the planner's
    ApplyNode for scalar subqueries, resolved at runtime)."""

    param_id: int
    _dtype: T.DataType

    @property
    def dtype(self):
        return self._dtype


@dataclasses.dataclass(frozen=True)
class RuntimeParam(Expr):
    """A hoisted literal that enters the compiled program as a RUNTIME
    argument (device input) instead of a trace-time constant — the
    parameterized-plan-cache leaf (plan/canonical.py). Two structurally
    identical plans whose literals differ only in value normalize to
    one canonical form over RuntimeParams, so they share ONE jitted
    program; the values ride in as a parameter vector per execution.

    ``index`` is the slot in that vector. Construction is owned by
    plan/canonical.py (and the planner's one BoundParam lowering site)
    — enforced by tools/check_plan_params.py: an ad-hoc RuntimeParam
    bypasses the dtype/structure eligibility rules (strings resolve
    against trace-time dictionaries, long decimals take the
    literal-introspection fast path) and silently miscompiles."""

    index: int
    _dtype: T.DataType

    @property
    def dtype(self):
        return self._dtype

    def __str__(self):
        return f"?p{self.index}"


@dataclasses.dataclass(frozen=True)
class DictTransform(Expr):
    """String-valued function of a dictionary column, evaluated host-side
    over the dictionary entries (substring, lower, ...). On device it is
    an int32 LUT gather old-id -> new-id; the result column carries the
    transformed (re-sorted) dictionary. ``fn`` maps str -> str."""

    arg: Expr  # string-typed
    fn_key: str
    fn: object = dataclasses.field(hash=False, compare=False)

    def children(self):
        return (self.arg,)

    @property
    def dtype(self):
        return T.VARCHAR


@dataclasses.dataclass(frozen=True)
class DictCombine(Expr):
    """String-valued function of TWO dictionary columns (a || b): the
    combined dictionary is the host-side cross product of both inputs'
    values (bounded — names/labels, not free text), and the device id
    is id_left * |right| + id_right gathered through one int32 LUT.
    ``fn`` maps (str, str) -> str, rebuilt from ``fn_key``."""

    left: Expr  # string-typed
    right: Expr  # string-typed
    fn_key: str
    fn: object = dataclasses.field(hash=False, compare=False)

    def children(self):
        return (self.left, self.right)

    @property
    def dtype(self):
        return T.VARCHAR


@dataclasses.dataclass(frozen=True)
class IntToDict(Expr):
    """String-valued function of a BOUNDED integer column (dates as
    epoch days -> formatted strings): the dictionary is a host-side
    LUT over [lo, hi] (the date domain is a few tens of thousands of
    values), the device gathers ``lut[clip(x - lo)]``. ``fn`` maps
    int -> str, rebuilt from ``fn_key``."""

    arg: Expr  # integer/date-typed
    fn_key: str
    lo: int
    hi: int
    fn: object = dataclasses.field(hash=False, compare=False)

    def children(self):
        return (self.arg,)

    @property
    def dtype(self):
        return T.VARCHAR


def dict_transform_fn(fn_key: str):
    """Rebuild a dictionary-function host callable from its key.

    The key is the canonical (wire-safe) identity of the function —
    the coordinator->worker protocol ships only ``fn_key`` and rebuilds
    the callable here, so every producer of DictTransform /
    DictPredicate / DictIntFunc nodes must construct ``fn`` through
    this factory. Parameterized keys carry their arguments
    JSON-encoded after the first colon (colon-safe)."""
    import json

    if fn_key.startswith("date_format:"):
        import datetime

        (fmt,) = json.loads(fn_key.partition(":")[2])

        def _df(days, _f=fmt):
            d = datetime.date(1970, 1, 1) + datetime.timedelta(
                days=int(days)
            )
            return d.strftime(_f)

        return _df
    if fn_key.startswith("concat2:"):
        import json as _json

        pre, mid, suf = _json.loads(fn_key.partition(":")[2])
        return lambda a, b: pre + a + mid + b + suf
    if fn_key == "initcap":
        return lambda s: " ".join(
            w[:1].upper() + w[1:].lower() for w in s.split(" ")
        )
    if fn_key == "md5":
        import hashlib

        return lambda s: hashlib.md5(s.encode()).hexdigest()
    if fn_key == "sha256":
        import hashlib

        return lambda s: hashlib.sha256(s.encode()).hexdigest()
    if fn_key == "crc32":
        import zlib

        return lambda s: zlib.crc32(s.encode())
    if fn_key == "codepoint":
        return lambda s: ord(s[0]) if s else 0
    if fn_key.startswith("repeat:"):
        (n_,) = json.loads(fn_key.partition(":")[2])
        return lambda s: s * n_
    if fn_key.startswith("translate:"):
        src, dst = json.loads(fn_key.partition(":")[2])
        table = str.maketrans(src, dst)
        return lambda s: s.translate(table)
    if fn_key.startswith("levenshtein:"):
        (other,) = json.loads(fn_key.partition(":")[2])

        def _lev(s, _o=other):
            prev = list(range(len(_o) + 1))
            for i, ca in enumerate(s, 1):
                cur = [i]
                for j, cb in enumerate(_o, 1):
                    cur.append(min(
                        prev[j] + 1, cur[-1] + 1,
                        prev[j - 1] + (ca != cb),
                    ))
                prev = cur
            return prev[-1]

        return _lev
    if fn_key == "lower":
        return str.lower
    if fn_key == "upper":
        return str.upper
    if fn_key == "trim":
        return str.strip
    if fn_key == "ltrim":
        return lambda s: s.lstrip()
    if fn_key == "rtrim":
        return lambda s: s.rstrip()
    if fn_key == "reverse":
        return lambda s: s[::-1]
    if fn_key == "length":
        return len
    if fn_key.startswith("substring:"):
        _, st, ln = fn_key.split(":")
        start = int(st)
        length = None if ln == "None" else int(ln)
        if length is None:
            return lambda s: s[start - 1:]
        return lambda s: s[start - 1: start - 1 + length]
    kind, _, payload = fn_key.partition(":")
    if kind == "replace":
        old, new = json.loads(payload)
        return lambda s: s.replace(old, new)
    if kind == "concat":
        prefix, suffix = json.loads(payload)
        return lambda s: prefix + s + suffix
    if kind == "lpad":
        size, pad = json.loads(payload)
        return lambda s: (
            s[:size]
            if len(s) >= size
            else ((pad * size)[: size - len(s)] + s if pad else s)
        )
    if kind == "rpad":
        size, pad = json.loads(payload)
        return lambda s: (
            s[:size]
            if len(s) >= size
            else (s + (pad * size)[: size - len(s)] if pad else s)
        )
    if kind == "split_part":
        delim, index = json.loads(payload)
        def _split_part(s, _d=delim, _i=index):
            parts = s.split(_d) if _d else [s]
            return parts[_i - 1] if 1 <= _i <= len(parts) else ""
        return _split_part
    if kind == "strpos":
        (sub,) = json.loads(payload)
        return lambda s: s.find(sub) + 1
    if kind == "regexp_like":
        (pat,) = json.loads(payload)
        rx = re.compile(pat)
        return lambda s: rx.search(s) is not None
    if kind == "starts_with":
        (prefix,) = json.loads(payload)
        return lambda s: s.startswith(prefix)
    if kind == "ends_with":
        (suffix,) = json.loads(payload)
        return lambda s: s.endswith(suffix)
    raise TypeError(f"unknown dictionary-function key {fn_key!r}")


@dataclasses.dataclass(frozen=True)
class ArrayLength(Expr):
    """cardinality(arr) over a physical array column -> BIGINT
    (offsets difference; NULL rows stay NULL)."""

    arg: Expr  # ColumnRef to an array column

    def children(self):
        return (self.arg,)

    @property
    def dtype(self):
        return T.BIGINT


@dataclasses.dataclass(frozen=True)
class ArraySubscript(Expr):
    """arr[i] / element_at(arr, i) over a physical array column: a
    bounds-checked gather ``values[offsets[row] + i - 1]``;
    out-of-range (or negative-from-the-end out-of-range) -> NULL
    (Presto element_at semantics; the reference's subscript raises —
    documented deviation keeps the kernel branch-free)."""

    arg: Expr  # ColumnRef to an array column
    index: Expr  # 1-based; negative = from the end

    def children(self):
        return (self.arg, self.index)

    @property
    def dtype(self):
        return self.arg.dtype.element


@dataclasses.dataclass(frozen=True)
class MapSubscript(Expr):
    """m[k] / element_at(m, k) over a physical map column: a flat
    segment scan — the matching entry's flat position per row is a
    segmented running max over ``match ? j : -1`` read at each row's
    segment end (branch-free, one pass over the values axis, no
    scatter). Missing key -> NULL (Presto element_at; the reference's
    subscript raises — same documented deviation as ArraySubscript)."""

    arg: Expr  # ColumnRef to a map column
    key: Expr

    def children(self):
        return (self.arg, self.key)

    @property
    def dtype(self):
        return self.arg.dtype.value


@dataclasses.dataclass(frozen=True)
class RowFieldAccess(Expr):
    """r.f over a physical row (struct) column: zero-copy select of the
    field's child block; row-NULL propagates into the field."""

    arg: Expr  # ColumnRef to a row column
    field: str
    field_type: T.DataType

    def children(self):
        return (self.arg,)

    @property
    def dtype(self):
        return self.field_type


@dataclasses.dataclass(frozen=True)
class DateAdd(Expr):
    """date_add(unit, n, x): shift a date/timestamp by n units (unit in
    day|week|month|year). Month/year shifts clamp the day-of-month to
    the target month's length (SQL semantics), computed branch-free via
    civil-calendar math on device."""

    unit: str
    n: Expr  # integer count (may be a column)
    arg: Expr

    def children(self):
        return (self.n, self.arg)

    @property
    def dtype(self):
        return self.arg.dtype


@dataclasses.dataclass(frozen=True)
class ValueHash(Expr):
    """checksum() support: an order-insensitive per-value hash.

    Maps any column to a 32-bit avalanche hash zero-extended into
    BIGINT, with NULL contributing a fixed non-zero constant — so a
    wrapping-free int64 SUM over the hashes (exact below 2^31 rows) is
    an order- and partitioning-insensitive set digest. Reference parity:
    the ``checksum()`` aggregate's per-value XXHash64 step (SURVEY.md
    §2.1 "Function registry"); deviation: 32-bit mix + BIGINT result
    (the reference emits varbinary), values hash their physical device
    image (dictionary ids for strings), so checksums compare equal only
    within one engine — the reference makes the same single-engine
    assumption for its own hash seed.

    The output has no validity lane (NULLs are folded INTO the hash),
    which is what lets the SUM state see every live row."""

    arg: Expr

    def children(self):
        return (self.arg,)

    @property
    def dtype(self):
        return T.BIGINT


@dataclasses.dataclass(frozen=True)
class DictIntFunc(Expr):
    """Integer-valued function of a dictionary column (length, strpos),
    evaluated host-side per dictionary entry into an int64 LUT that the
    device gathers (SURVEY.md §7 "Strings on TPU"). ``fn`` maps
    str -> int and is rebuilt from ``fn_key`` via dict_transform_fn."""

    arg: Expr  # string-typed
    fn_key: str
    fn: object = dataclasses.field(hash=False, compare=False)

    def children(self):
        return (self.arg,)

    @property
    def dtype(self):
        return T.BIGINT


@dataclasses.dataclass(frozen=True)
class DictPredicate(Expr):
    """Boolean predicate over a dictionary column evaluated *host-side*
    per dictionary entry (e.g. predicates over substring()/lower()): the
    device just gathers the LUT (SURVEY.md §7 "Strings on TPU").
    ``fn_key`` keeps the node hashable; ``fn`` maps str -> bool."""

    arg: Expr  # ColumnRef to a varchar column
    fn_key: str
    fn: object = dataclasses.field(hash=False, compare=False)

    def children(self):
        return (self.arg,)

    @property
    def dtype(self):
        return T.BOOLEAN


# --- analyzer-facing constructors (type inference for binary ops) ---------


def arith(op: str, left: Expr, right: Expr) -> Arithmetic:
    lt, rt = left.dtype, right.dtype
    if (lt.is_decimal or rt.is_decimal) and (
        lt.name in ("double", "real") or rt.name in ("double", "real")
    ):
        out = T.DOUBLE  # decimal op double -> double (reference semantics)
    elif op == "/" and (lt.is_decimal or rt.is_decimal):
        out = T.DOUBLE  # documented deviation: int128 division later
    elif lt.is_decimal or rt.is_decimal:
        a = lt if lt.is_decimal else T.decimal(18, 0)
        b = rt if rt.is_decimal else T.decimal(18, 0)
        long = a.is_long_decimal or b.is_long_decimal
        if op == "*":
            scale = a.scale + b.scale
            if scale > 18:
                raise NotImplementedError(
                    f"decimal multiply scale {scale} > 18"
                )
            out = T.decimal(38 if long else 18, scale)
        else:
            out = T.decimal(38 if long else 18, max(a.scale, b.scale))
    else:
        out = T.common_super_type(lt, rt)
    return Arithmetic(op, left, right, out)


# --------------------------------------------------------------------------
# Lowering: eval_expr(expr, page) -> (data, valid|None), traced under jit
# --------------------------------------------------------------------------

def like_to_regex(pattern: str, escape: Optional[str] = None) -> re.Pattern:
    out, i = [], 0
    while i < len(pattern):
        c = pattern[i]
        if escape and c == escape and i + 1 < len(pattern):
            out.append(re.escape(pattern[i + 1]))
            i += 2
            continue
        if c == "%":
            out.append(".*")
        elif c == "_":
            out.append(".")
        else:
            out.append(re.escape(c))
        i += 1
    return re.compile("^" + "".join(out) + "$", re.DOTALL)


def _rescale(data, from_scale: int, to_scale: int):
    if to_scale > from_scale:
        return data * (10 ** (to_scale - from_scale))
    if to_scale < from_scale:
        # SQL half-up rounding away from zero (matches ingest in page.py)
        factor = 10 ** (from_scale - to_scale)
        half = factor // 2
        q = (jnp.abs(data) + half) // factor
        return jnp.sign(data) * q
    return data


def _and_valid(a, b):
    if a is None:
        return b
    if b is None:
        return a
    return a & b


def _numeric_pair(left: Expr, right: Expr, ld, rd):
    """Align two numeric operands to a common device representation.
    Returns (l, r, kind) where kind is 'decimal:<scale>' | 'float' | 'int'."""
    lt, rt = left.dtype, right.dtype
    if lt.is_decimal or rt.is_decimal:
        if lt.name == "double" or rt.name == "double" or lt.name == "real" or rt.name == "real":
            ls = 10.0 ** -(lt.scale if lt.is_decimal else 0)
            rs = 10.0 ** -(rt.scale if rt.is_decimal else 0)
            return (
                ld.astype(jnp.float64) * (ls if lt.is_decimal else 1.0),
                rd.astype(jnp.float64) * (rs if rt.is_decimal else 1.0),
                "float",
            )
        scale = max(
            lt.scale if lt.is_decimal else 0,
            rt.scale if rt.is_decimal else 0,
        )
        l = _rescale(ld.astype(jnp.int64), lt.scale if lt.is_decimal else 0, scale)
        r = _rescale(rd.astype(jnp.int64), rt.scale if rt.is_decimal else 0, scale)
        return l, r, f"decimal:{scale}"
    if lt.name in ("double", "real") or rt.name in ("double", "real"):
        return ld.astype(jnp.float64), rd.astype(jnp.float64), "float"
    return ld.astype(jnp.int64), rd.astype(jnp.int64), "int"


def _civil_from_days(z):
    """Epoch days -> (year, month, day), branch-free integer math on device
    (Howard Hinnant's civil_from_days; operands kept non-negative)."""
    z = z.astype(jnp.int64) + 719468
    era = jnp.floor_divide(jnp.where(z >= 0, z, z - 146096), 146097)
    doe = z - era * 146097
    yoe = jnp.floor_divide(
        doe - doe // 1460 + doe // 36524 - doe // 146096, 365
    )
    y = yoe + era * 400
    doy = doe - (365 * yoe + yoe // 4 - yoe // 100)
    mp = jnp.floor_divide(5 * doy + 2, 153)
    d = doy - jnp.floor_divide(153 * mp + 2, 5) + 1
    m = mp + jnp.where(mp < 10, 3, -9)
    y = y + (m <= 2)
    return y, m, d


def _days_from_civil(y, m, d):
    """(year, month, day) -> epoch days; inverse of _civil_from_days
    (Howard Hinnant's days_from_civil), branch-free on device."""
    y = y - (m <= 2)
    era = jnp.floor_divide(jnp.where(y >= 0, y, y - 399), 400)
    yoe = y - era * 400
    doy = jnp.floor_divide(
        153 * (m + jnp.where(m > 2, -3, 9)) + 2, 5
    ) + d - 1
    doe = yoe * 365 + yoe // 4 - yoe // 100 + doy
    return era * 146097 + doe - 719468


class ExprLowerer:
    """Lowers an Expr tree over one Page at trace time.

    One instance per fragment compilation; results are (data, valid) with
    valid=None meaning statically null-free.
    """

    def __init__(self, page: Page):
        self.page = page
        self._transform_cache = {}

    def dictionary_of(self, expr: Expr):
        """Host dictionary of a string-typed expression's result."""
        if isinstance(expr, ColumnRef):
            return self.page.block(expr.name).dictionary
        if isinstance(expr, DictTransform):
            return self._transform(expr)[0]
        if isinstance(expr, DictCombine):
            return self._combine(expr)[0]
        if isinstance(expr, Coalesce) and expr.dtype.is_string:
            return self._coalesce_dict(expr)[0]
        if isinstance(expr, Case) and expr.dtype.is_string:
            return self._case_dicts(expr)[0][0]
        if isinstance(expr, IntToDict):
            return self._int_to_dict(expr)[0]
        if isinstance(expr, Literal):
            from presto_tpu.page import Dictionary

            vals = [] if expr.value is None else [str(expr.value)]
            return Dictionary(np.asarray(vals, object))
        if isinstance(expr, ArraySubscript):
            # elements share the array block's dictionary
            return self._array_block(expr.arg).dictionary
        if isinstance(expr, MapSubscript):
            return self._map_block(expr.arg).children[1].dictionary
        if isinstance(expr, RowFieldAccess):
            blk = self.page.block(expr.arg.name)
            return blk.children[blk.dtype.field_index(expr.field)].dictionary
        raise NotImplementedError(
            f"no dictionary for string expression {type(expr).__name__}"
        )

    def _combine(self, e: "DictCombine"):
        """(new_dictionary, pair-id -> new-id LUT) for a two-dictionary
        combine, cached. pair id = id_left * |right| + id_right."""
        ld = self.dictionary_of(e.left)
        rd = self.dictionary_of(e.right)
        key = (e.fn_key, ld, rd)
        if key not in self._transform_cache:
            from presto_tpu.page import Dictionary

            nl, nr = len(ld.values), len(rd.values)
            if nl * nr > (1 << 20):
                raise NotImplementedError(
                    f"combined dictionary too large ({nl}x{nr}); "
                    "two-column string functions are bounded to 2^20 "
                    "combinations (names/labels, not free text)"
                )
            combined = np.asarray(
                [
                    str(e.fn(a, b))
                    for a in ld.values
                    for b in rd.values
                ],
                dtype=object,
            )
            if len(combined):
                uniq = np.unique(combined.astype(str))
                lut = np.searchsorted(
                    uniq, combined.astype(str)
                ).astype(np.int32)
            else:
                uniq = np.array([], dtype=object)
                lut = np.zeros(0, np.int32)
            new_dict = Dictionary(np.asarray(uniq, dtype=object))
            self._transform_cache[key] = (new_dict, lut)
        return self._transform_cache[key]

    def _eval_dictcombine(self, e: "DictCombine"):
        dl, vl = self.eval(e.left)
        dr, vr = self.eval(e.right)
        rd = self.dictionary_of(e.right)
        _, lut = self._combine(e)
        nr = max(len(rd.values), 1)
        if len(lut) == 0:
            return jnp.zeros((self.page.capacity,), jnp.int32), _and_valid(vl, vr)
        pair = (
            jnp.clip(dl, 0, (len(lut) // nr) - 1) * nr
            + jnp.clip(dr, 0, nr - 1)
        )
        mapped = jnp.asarray(lut)[pair]
        return mapped, _and_valid(vl, vr)

    def _transform(self, e: DictTransform):
        """(new_dictionary, old-id -> new-id LUT), cached per node."""
        src = self.dictionary_of(e.arg)
        key = (e.fn_key, src)
        if key not in self._transform_cache:
            from presto_tpu.page import Dictionary

            transformed = np.asarray(
                [str(e.fn(v)) for v in src.values], dtype=object
            )
            uniq = np.unique(transformed.astype(str)) if len(transformed) else np.array([], dtype=object)
            new_dict = Dictionary(np.asarray(uniq, dtype=object))
            lut = (
                np.searchsorted(uniq, transformed.astype(str)).astype(np.int32)
                if len(transformed)
                else np.zeros(0, np.int32)
            )
            self._transform_cache[key] = (new_dict, lut)
        return self._transform_cache[key]

    def eval(self, expr: Expr):
        method = getattr(self, "_eval_" + type(expr).__name__.lower(), None)
        if method is None:
            raise NotImplementedError(
                f"no lowering for {type(expr).__name__}"
            )
        return method(expr)

    # -- leaves ------------------------------------------------------------

    def _eval_columnref(self, e: ColumnRef):
        blk = self.page.block(e.name)
        return blk.data, blk.valid

    def _eval_literal(self, e: Literal):
        if e.value is None:
            shape = (
                (self.page.capacity, 2)
                if e.dtype.is_long_decimal
                else (self.page.capacity,)
            )
            zero = jnp.zeros(shape, dtype=e.dtype.jnp_dtype)
            return zero, jnp.zeros((self.page.capacity,), dtype=jnp.bool_)
        if e.dtype.is_string:
            # one-entry dictionary, all ids 0 (dictionary_of pairs it)
            return jnp.zeros((self.page.capacity,), jnp.int32), None
        if e.dtype.is_long_decimal:
            # (1, 2) limb row: broadcasts against both (cap, 2) columns
            # (elementwise limb ops) and (cap, 2) projection shapes
            return jnp.asarray(T.int128_limbs([e.value])), None
        v = e.value
        return jnp.asarray(v, dtype=e.dtype.jnp_dtype), None

    # -- arithmetic --------------------------------------------------------

    def _eval_arithmetic(self, e: Arithmetic):
        ld, lv = self.eval(e.left)
        rd, rv = self.eval(e.right)
        valid = _and_valid(lv, rv)
        lt, rt = e.left.dtype, e.right.dtype
        if lt.is_long_decimal or rt.is_long_decimal:
            return self._long_decimal_arith(e, ld, rd, valid)
        if e.op == "/" and (lt.is_decimal or rt.is_decimal):
            ls = 10.0 ** -(lt.scale if lt.is_decimal else 0)
            rs = 10.0 ** -(rt.scale if rt.is_decimal else 0)
            lf = ld.astype(jnp.float64) * ls
            rf = rd.astype(jnp.float64) * rs
            return lf / jnp.where(rf == 0, 1.0, rf), (
                valid
                if not _maybe_zero(e.right)
                else _and_valid(valid, rf != 0)
            )
        if e.op == "*" and lt.is_decimal and rt.is_decimal:
            # exact: unscaled product, scale adds
            return ld.astype(jnp.int64) * rd.astype(jnp.int64), valid
        if e.op == "*" and (lt.is_decimal or rt.is_decimal):
            dec, other = (ld, rd) if lt.is_decimal else (rd, ld)
            ot = rt if lt.is_decimal else lt
            if ot.is_integer:
                # exact: unscaled decimal * integer keeps the scale
                return dec.astype(jnp.int64) * other.astype(jnp.int64), valid
            # decimal * double falls through: _numeric_pair descales
        l, r, kind = _numeric_pair(e.left, e.right, ld, rd)
        if e.op == "+":
            return l + r, valid
        if e.op == "-":
            return l - r, valid
        if e.op == "*":
            return l * r, valid
        if e.op == "/":
            if kind == "float":
                return l / jnp.where(r == 0, 1.0, r), _and_valid(valid, r != 0)
            # SQL integer division truncates toward zero
            q = jnp.sign(l) * jnp.sign(r) * (jnp.abs(l) // jnp.maximum(jnp.abs(r), 1))
            return q.astype(jnp.int64), _and_valid(valid, r != 0)
        if e.op == "%":
            r_safe = jnp.where(r == 0, 1, r)
            m = l - (jnp.sign(l) * jnp.sign(r) * (jnp.abs(l) // jnp.abs(r_safe))) * r
            return m, _and_valid(valid, r != 0)
        raise ValueError(f"unknown arithmetic op {e.op}")

    def _eval_negate(self, e: Negate):
        d, v = self.eval(e.arg)
        if e.arg.dtype.is_long_decimal:
            from presto_tpu import int128

            h, l = int128.neg(d[..., 0], d[..., 1])
            return jnp.stack([h, l], axis=-1), v
        return -d, v

    # -- long decimal (int128 limb pairs; presto_tpu.int128) ---------------

    def _long_limbs(self, expr: Expr, data, to_scale: int):
        """Any numeric operand -> (hi, lo) limbs at ``to_scale``."""
        from presto_tpu import int128

        t = expr.dtype
        if t.is_long_decimal:
            h, l = data[..., 0], data[..., 1]
            from_scale = t.scale
        else:
            h, l = int128.from_i64(data.astype(jnp.int64))
            from_scale = t.scale if t.is_decimal else 0
        if to_scale < from_scale:  # pragma: no cover - planner upscales
            raise NotImplementedError(
                "long-decimal downscale requires int128 division"
            )
        return int128.mul_pow10(h, l, to_scale - from_scale)

    def _long_decimal_arith(self, e: Arithmetic, ld, rd, valid):
        from presto_tpu import int128

        lt, rt = e.left.dtype, e.right.dtype
        if e.dtype.name in ("double", "real"):
            # long decimal op double -> double (arith() typed it so)
            lf = self._long_f64(e.left, ld)
            rf = self._long_f64(e.right, rd)
            if e.op == "+":
                return lf + rf, valid
            if e.op == "-":
                return lf - rf, valid
            if e.op == "*":
                return lf * rf, valid
            if e.op == "/":
                return lf / jnp.where(rf == 0, 1.0, rf), (
                    valid
                    if not _maybe_zero(e.right)
                    else _and_valid(valid, rf != 0)
                )
        if e.op in ("+", "-"):
            scale = e.dtype.scale
            lh, ll = self._long_limbs(e.left, ld, scale)
            rh, rl = self._long_limbs(e.right, rd, scale)
            fn = int128.add if e.op == "+" else int128.sub
            h, l = fn(lh, ll, rh, rl)
            return jnp.stack([h, l], axis=-1), valid
        if e.op == "*" and not (lt.is_long_decimal and rt.is_long_decimal):
            # long * small integer literal: exact via limb multiply
            lit = e.right if rt.is_integer else e.left
            if (
                isinstance(lit, Literal)
                and lit.value is not None
                and 0 <= int(lit.value) < (1 << 31)
            ):
                big, bt = (ld, lt) if lt.is_long_decimal else (rd, rt)
                h, l = int128.mul_u32(
                    big[..., 0], big[..., 1], int(lit.value)
                )
                return jnp.stack([h, l], axis=-1), valid
        if e.op == "/":
            # like short-decimal /: falls to DOUBLE (documented deviation)
            lf = self._long_f64(e.left, ld)
            rf = self._long_f64(e.right, rd)
            return lf / jnp.where(rf == 0, 1.0, rf), (
                valid
                if not _maybe_zero(e.right)
                else _and_valid(valid, rf != 0)
            )
        raise NotImplementedError(
            f"long-decimal {e.op} between {lt} and {rt} (supported: "
            "+, -, negate, compare, / (->double), * by a small integer "
            "literal; full 128x128 multiply is a documented deviation)"
        )

    def _long_f64(self, expr: Expr, data):
        from presto_tpu import int128

        t = expr.dtype
        if t.is_long_decimal:
            return int128.to_f64(data[..., 0], data[..., 1]) * (
                10.0 ** -t.scale
            )
        if t.is_decimal:
            return data.astype(jnp.float64) * (10.0 ** -t.scale)
        return data.astype(jnp.float64)

    # -- comparisons -------------------------------------------------------

    def _cmp(self, op: str, l, r):
        if op == "=":
            return l == r
        if op in ("<>", "!="):
            return l != r
        if op == "<":
            return l < r
        if op == "<=":
            return l <= r
        if op == ">":
            return l > r
        if op == ">=":
            return l >= r
        raise ValueError(f"unknown comparison {op}")

    def _string_literal_compare(self, op: str, col: Expr, lit):
        """Compare a dictionary-typed expression against a string literal
        by id — folds to an int32 compare (order-preserving dictionary)."""
        ids, valid = self.eval(col)
        if lit is None:  # NULL literal (e.g. empty scalar subquery)
            zeros = jnp.zeros(jnp.shape(ids), jnp.bool_)
            return zeros, zeros
        d = self.dictionary_of(col)
        if op == "=":
            i = d.id_of(lit)
            res = (ids == i) if i >= 0 else jnp.zeros(ids.shape, jnp.bool_)
        elif op in ("<>", "!="):
            i = d.id_of(lit)
            res = (ids != i) if i >= 0 else jnp.ones(ids.shape, jnp.bool_)
        elif op == "<":
            res = ids < d.searchsorted(lit, "left")
        elif op == "<=":
            res = ids < d.searchsorted(lit, "right")
        elif op == ">":
            res = ids >= d.searchsorted(lit, "right")
        elif op == ">=":
            res = ids >= d.searchsorted(lit, "left")
        else:
            raise ValueError(op)
        return res, valid

    def _eval_compare(self, e: Compare):
        lt, rt = e.left.dtype, e.right.dtype
        if lt.is_string and isinstance(e.right, Literal):
            return self._string_literal_compare(e.op, e.left, e.right.value)
        if rt.is_string and isinstance(e.left, Literal):
            flip = {
                "<": ">", "<=": ">=", ">": "<", ">=": "<=",
                "=": "=", "<>": "<>", "!=": "!=",
            }
            return self._string_literal_compare(
                flip[e.op], e.right, e.left.value
            )
        ld, lv = self.eval(e.left)
        rd, rv = self.eval(e.right)
        if lt.is_string and rt.is_string:
            # both sides dictionary-typed: ids comparable only within ONE
            # dictionary (planner re-encodes otherwise)
            ldict = self.dictionary_of(e.left)
            rdict = self.dictionary_of(e.right)
            if ldict != rdict:
                # re-encode both sides into the sorted union (Q24's
                # c_birth_country <> upper(ca_country), s_zip = ca_zip)
                _, (llut, rlut) = self._union_dicts((ldict, rdict))
                if len(llut):
                    ld = jnp.asarray(llut)[
                        jnp.clip(ld, 0, len(llut) - 1)
                    ]
                if len(rlut):
                    rd = jnp.asarray(rlut)[
                        jnp.clip(rd, 0, len(rlut) - 1)
                    ]
            return self._cmp(e.op, ld, rd), _and_valid(lv, rv)
        if lt.is_long_decimal or rt.is_long_decimal:
            from presto_tpu import int128

            if "double" in (lt.name, rt.name) or "real" in (
                lt.name, rt.name
            ):
                l = self._long_f64(e.left, ld)
                r = self._long_f64(e.right, rd)
                return self._cmp(e.op, l, r), _and_valid(lv, rv)
            scale = max(
                lt.scale if lt.is_decimal else 0,
                rt.scale if rt.is_decimal else 0,
            )
            lh, ll = self._long_limbs(e.left, ld, scale)
            rh, rl = self._long_limbs(e.right, rd, scale)
            if e.op == "=":
                res = int128.eq(lh, ll, rh, rl)
            elif e.op in ("<>", "!="):
                res = ~int128.eq(lh, ll, rh, rl)
            elif e.op == "<":
                res = int128.lt(lh, ll, rh, rl)
            elif e.op == "<=":
                res = ~int128.lt(rh, rl, lh, ll)
            elif e.op == ">":
                res = int128.lt(rh, rl, lh, ll)
            elif e.op == ">=":
                res = ~int128.lt(lh, ll, rh, rl)
            else:
                raise ValueError(f"unknown comparison {e.op}")
            return res, _and_valid(lv, rv)
        l, r, _ = _numeric_pair(e.left, e.right, ld, rd)
        return self._cmp(e.op, l, r), _and_valid(lv, rv)

    # -- boolean (Kleene three-valued) -------------------------------------

    def _eval_and(self, e: And):
        data, valid = None, None
        for t in e.terms:
            d, v = self.eval(t)
            if data is None:
                data, valid = d, v
                continue
            # three-valued AND: false dominates null
            new_valid = (
                None
                if valid is None and v is None
                else _tv_and_valid(data, valid, d, v)
            )
            data = data & d
            valid = new_valid
        return data, valid

    def _eval_or(self, e: Or):
        data, valid = None, None
        for t in e.terms:
            d, v = self.eval(t)
            if data is None:
                data, valid = d, v
                continue
            new_valid = (
                None
                if valid is None and v is None
                else _tv_or_valid(data, valid, d, v)
            )
            data = data | d
            valid = new_valid
        return data, valid

    def _eval_not(self, e: Not):
        d, v = self.eval(e.arg)
        return ~d, v

    def _eval_isnull(self, e: IsNull):
        _, v = self.eval(e.arg)
        if v is None:
            res = jnp.zeros((self.page.capacity,), dtype=jnp.bool_)
        else:
            res = ~v
        if e.negate:
            res = ~res
        return res, None

    # -- conditional -------------------------------------------------------

    def _case_dicts(self, e: Case):
        """((union dictionary, per-branch LUTs), branch exprs) for a
        string-valued CASE — branches and the default re-encode into
        one sorted union (Q36/Q70/Q86's
        `case when lochierarchy = 0 then s_state end` sort keys)."""
        args = [v for _, v in e.whens]
        if e.default is not None:
            args.append(e.default)
        return (
            self._union_dicts(
                tuple(self.dictionary_of(a) for a in args)
            ),
            args,
        )

    def _eval_case_string(self, e: Case):
        (_, luts), _args = self._case_dicts(e)

        def remap(d, lut):
            if len(lut):
                return jnp.asarray(lut)[jnp.clip(d, 0, len(lut) - 1)]
            return d

        conds = []
        vals = []
        for (c, v), lut in zip(e.whens, luts):
            cd, cv = self.eval(c)
            cd = cd & cv if cv is not None else cd
            vd, vv = self.eval(v)
            conds.append(cd)
            vals.append((remap(vd, lut), vv))
        if e.default is not None:
            dd, dv = self.eval(e.default)
            dd = remap(dd, luts[-1])
        else:
            dd = jnp.zeros((self.page.capacity,), jnp.int32)
            dv = jnp.zeros((self.page.capacity,), jnp.bool_)
        out_d, out_v = dd, dv
        if out_v is None:
            out_v = jnp.ones((self.page.capacity,), jnp.bool_)
        for cd, (vd, vv) in zip(reversed(conds), reversed(vals)):
            out_d = jnp.where(cd, vd, out_d)
            bv = (
                vv
                if vv is not None
                else jnp.ones((self.page.capacity,), jnp.bool_)
            )
            out_v = jnp.where(cd, bv, out_v)
        return out_d, out_v

    def _eval_case(self, e: Case):
        if e.dtype.is_string:
            return self._eval_case_string(e)
        # evaluate all branches, select first matching WHEN (SQL order)
        conds = []
        vals = []
        for c, v in e.whens:
            cd, cv = self.eval(c)
            cd = cd & cv if cv is not None else cd  # null cond = no match
            vd, vv = self.eval(v)
            conds.append(cd)
            vals.append((vd, vv))
        long = e.dtype.is_long_decimal  # (cap, 2) limb branches
        if e.default is not None:
            dd, dv = self.eval(e.default)
            dd = _coerce_to(dd, e.default.dtype, e.dtype)
        else:
            shape = (
                (self.page.capacity, 2)
                if long
                else (self.page.capacity,)
            )
            dd = jnp.zeros(shape, dtype=e.dtype.jnp_dtype)
            dv = jnp.zeros((self.page.capacity,), dtype=jnp.bool_)
        out_d, out_v = dd, dv
        needs_valid = dv is not None or any(vv is not None for _, vv in vals)
        if needs_valid and out_v is None:
            out_v = jnp.ones((self.page.capacity,), dtype=jnp.bool_)
        branch_types = [v.dtype for _, v in e.whens]
        for cd, (vd, vv), bt in zip(
            reversed(conds), reversed(vals), reversed(branch_types)
        ):
            vd = _coerce_to(vd, bt, e.dtype)
            out_d = jnp.where(cd[..., None] if long else cd, vd, out_d)
            if needs_valid:
                branch_v = vv if vv is not None else jnp.ones(
                    jnp.shape(cd), jnp.bool_
                )
                out_v = jnp.where(cd, branch_v, out_v)
        return out_d, (out_v if needs_valid else None)

    def _union_dicts(self, dicts):
        """(sorted union Dictionary, per-input id LUTs): the shared
        re-encode for string coalesce and cross-dictionary compares —
        sorted union ids preserve value order, so </> stay valid."""
        key = ("union",) + tuple(dicts)
        if key not in self._transform_cache:
            from presto_tpu.page import Dictionary

            parts = [
                np.asarray(d.values, dtype=object) for d in dicts
            ]
            allv = (
                np.concatenate([p for p in parts if len(p)])
                if any(len(p) for p in parts)
                else np.array([], dtype=object)
            )
            uniq = (
                np.unique(allv.astype(str))
                if len(allv)
                else np.array([], dtype=str)
            )
            luts = [
                np.searchsorted(uniq, p.astype(str)).astype(np.int32)
                if len(p)
                else np.zeros(0, np.int32)
                for p in parts
            ]
            self._transform_cache[key] = (
                Dictionary(np.asarray(uniq, dtype=object)),
                luts,
            )
        return self._transform_cache[key]

    def _coalesce_dict(self, e: Coalesce):
        """(union dictionary, per-arg id LUTs) for string coalesce."""
        return self._union_dicts(
            tuple(self.dictionary_of(a) for a in e.args)
        )

    def _eval_coalesce(self, e: Coalesce):
        if e.dtype.is_string:
            _, luts = self._coalesce_dict(e)
            out_d = None
            out_v = None
            for a, lut in zip(e.args, luts):
                d, v = self.eval(a)
                if len(lut):
                    d = jnp.asarray(lut)[
                        jnp.clip(d, 0, len(lut) - 1)
                    ]
                if out_d is None:
                    out_d, out_v = d, v
                    continue
                if out_v is None:
                    break
                out_d = jnp.where(out_v, out_d, d)
                out_v = out_v | (v if v is not None else True)
            return out_d, out_v
        long = e.dtype.is_long_decimal
        out_d, out_v = self.eval(e.args[0])
        out_d = _coerce_to(out_d, e.args[0].dtype, e.dtype)
        for a in e.args[1:]:
            if out_v is None:
                return out_d, None
            d, v = self.eval(a)
            d = _coerce_to(d, a.dtype, e.dtype)
            out_d = jnp.where(
                out_v[..., None] if long else out_v, out_d, d
            )
            out_v = out_v | (v if v is not None else True)
        return out_d, out_v

    def _eval_cast(self, e: Cast):
        d, v = self.eval(e.arg)
        src, dst = e.arg.dtype, e.to
        if src == dst:
            return d, v
        if src.is_long_decimal or dst.is_long_decimal:
            return self._cast_long(d, v, src, dst)
        if dst.is_decimal:
            if src.is_decimal:
                return _rescale(d, src.scale, dst.scale), v
            if src.is_integer:
                return d.astype(jnp.int64) * (10 ** dst.scale), v
            if src.name in ("double", "real"):
                scaled = d.astype(jnp.float64) * (10 ** dst.scale)
                # half-up away from zero (jnp.round is half-to-even)
                return (
                    jnp.sign(scaled) * jnp.floor(jnp.abs(scaled) + 0.5)
                ).astype(jnp.int64), v
        if src.is_decimal:
            if dst.name in ("double", "real"):
                return (
                    d.astype(jnp.float64) / (10 ** src.scale)
                ).astype(dst.jnp_dtype), v
            if dst.is_integer:
                return _rescale(d, src.scale, 0).astype(dst.jnp_dtype), v
        return d.astype(dst.jnp_dtype), v

    def _cast_long(self, d, v, src: T.DataType, dst: T.DataType):
        """Casts in/out of the int128 limb representation."""
        from presto_tpu import int128

        if dst.is_long_decimal:
            if src.is_long_decimal:
                if dst.scale < src.scale:
                    h, l = int128.div_pow10_half_up(
                        d[..., 0], d[..., 1], src.scale - dst.scale
                    )
                else:
                    h, l = int128.mul_pow10(
                        d[..., 0], d[..., 1], dst.scale - src.scale
                    )
                return jnp.stack([h, l], axis=-1), v
            if src.is_decimal or src.is_integer:
                h, l = int128.from_i64(d.astype(jnp.int64))
                from_scale = src.scale if src.is_decimal else 0
                if dst.scale < from_scale:
                    h, l = int128.div_pow10_half_up(
                        h, l, from_scale - dst.scale
                    )
                else:
                    h, l = int128.mul_pow10(h, l, dst.scale - from_scale)
                return jnp.stack([h, l], axis=-1), v
            if src.name in ("double", "real"):
                raise NotImplementedError(
                    "double -> long decimal cast (use a decimal literal)"
                )
        # src is long decimal
        if dst.name in ("double", "real"):
            f = int128.to_f64(d[..., 0], d[..., 1]) * (10.0 ** -src.scale)
            return f.astype(dst.jnp_dtype), v
        if dst.is_decimal or dst.is_integer:
            # narrowing: rescale in int128 (half-up on downscale, like
            # the reference's rescale-with-round), then take the low
            # limb; values beyond int64 wrap (the reference raises on
            # overflow — documented deviation)
            to_scale = dst.scale if dst.is_decimal else 0
            h, l = d[..., 0], d[..., 1]
            if to_scale > src.scale:
                h, l = int128.mul_pow10(h, l, to_scale - src.scale)
            elif to_scale < src.scale:
                h, l = int128.div_pow10_half_up(
                    h, l, src.scale - to_scale
                )
            # dtype-faithful narrowing, like the short-decimal path
            return l.astype(dst.jnp_dtype), v
        raise NotImplementedError(f"cast {src} -> {dst}")

    # -- predicates --------------------------------------------------------

    def _eval_between(self, e: Between):
        lo = Compare(">=", e.arg, e.low)
        hi = Compare("<=", e.arg, e.high)
        d, v = self._eval_and(And((lo, hi)))
        return (~d if e.negate else d), v

    def _eval_inlist(self, e: InList):
        if e.arg.dtype.is_string:
            data, valid = self.eval(e.arg)
            d = self.dictionary_of(e.arg)
            ids = [
                d.id_of(lit.value)
                for lit in e.values
                if isinstance(lit, Literal)
            ]
            ids = [i for i in ids if i >= 0]
            if not ids:
                res = jnp.zeros((self.page.capacity,), jnp.bool_)
            else:
                res = jnp.isin(data, jnp.asarray(ids, jnp.int32))
            return (~res if e.negate else res), valid
        d, v = self.eval(e.arg)
        if all(isinstance(lit, Literal) for lit in e.values):
            vals = jnp.asarray(
                [lit.value for lit in e.values],
                dtype=e.arg.dtype.jnp_dtype,
            )
        else:
            # hoisted members (RuntimeParam): each evaluates to a traced
            # scalar already planner-coerced into the arg's type domain
            vals = jnp.stack(
                [
                    jnp.asarray(
                        self.eval(lit)[0], e.arg.dtype.jnp_dtype
                    ).reshape(())
                    for lit in e.values
                ]
            )
        res = jnp.isin(d, vals)
        return (~res if e.negate else res), v

    def _dict_lut_eval(self, arg: Expr, fn):
        data, valid = self.eval(arg)
        lut = self.dictionary_of(arg).predicate_lut(fn)
        if len(lut) == 0:
            res = jnp.zeros((self.page.capacity,), jnp.bool_)
        else:
            res = jnp.asarray(lut)[jnp.clip(data, 0, len(lut) - 1)]
        return res, valid

    def _eval_like(self, e: Like):
        assert e.arg.dtype.is_string
        rx = like_to_regex(e.pattern)
        res, valid = self._dict_lut_eval(
            e.arg, lambda s: rx.match(s) is not None
        )
        return (~res if e.negate else res), valid

    def _eval_param(self, e: Param):
        raise NotImplementedError(
            f"unbound scalar-subquery parameter ${e.param_id}: the executor "
            "must substitute Params before fragment compilation"
        )

    def _eval_runtimeparam(self, e: RuntimeParam):
        # the value is a traced scalar from the program's parameter
        # vector (plan/canonical.py installs it around _execute_node);
        # like a Literal it broadcasts against column arrays, and it is
        # non-null by eligibility (NULL literals stay constants — their
        # validity lane is program structure)
        from presto_tpu.plan import canonical

        d = canonical.active_param(e.index)
        return jnp.asarray(d, e.dtype.jnp_dtype), None

    def _eval_dictpredicate(self, e: DictPredicate):
        assert e.arg.dtype.is_string
        return self._dict_lut_eval(e.arg, e.fn)

    def _eval_dicttransform(self, e: DictTransform):
        data, valid = self.eval(e.arg)
        _, lut = self._transform(e)
        if len(lut) == 0:
            return jnp.zeros((self.page.capacity,), jnp.int32), valid
        mapped = jnp.asarray(lut)[jnp.clip(data, 0, len(lut) - 1)]
        return mapped, valid

    def _eval_mathfunc(self, e: MathFunc):
        d, v = self.eval(e.arg)
        at = e.arg.dtype
        if at.is_long_decimal:
            raise NotImplementedError(
                "math functions over long decimals: cast to "
                "decimal(18,s) or double first (documented deviation)"
            )
        if e.func == "abs":
            return jnp.abs(d), v
        if e.func == "sign":
            return jnp.sign(d).astype(e.dtype.jnp_dtype), v
        if e.func in ("round", "truncate") and (
            at.is_integer or at.is_decimal
        ):
            if at.is_integer:
                return d, v  # already integral
            # decimal: round/truncate the unscaled value to 0 digits,
            # result keeps the decimal type (rescaled back)
            factor = 10 ** at.scale
            half = factor // 2 if e.func == "round" else 0
            q = (jnp.abs(d.astype(jnp.int64)) + half) // factor
            return jnp.sign(d) * q * factor, v
        x = d.astype(jnp.float64)
        if at.is_decimal:
            x = x / (10 ** at.scale)
        if e.func == "sqrt":
            out = jnp.sqrt(jnp.maximum(x, 0.0))
            v = _and_valid(v, x >= 0)
            return out, v
        if e.func == "ln":
            out = jnp.log(jnp.maximum(x, jnp.finfo(jnp.float64).tiny))
            v = _and_valid(v, x > 0)
            return out, v
        if e.func in ("log2", "log10"):
            base = 2.0 if e.func == "log2" else 10.0
            out = jnp.log(
                jnp.maximum(x, jnp.finfo(jnp.float64).tiny)
            ) / jnp.log(base)
            v = _and_valid(v, x > 0)
            return out, v
        if e.func == "exp":
            return jnp.exp(x), v
        if e.func == "floor":
            return jnp.floor(x).astype(jnp.int64), v
        if e.func == "ceil":
            return jnp.ceil(x).astype(jnp.int64), v
        if e.func == "round":
            # SQL half-away-from-zero (jnp.round is half-to-even)
            return jnp.sign(x) * jnp.floor(jnp.abs(x) + 0.5), v
        if e.func == "truncate":
            return jnp.sign(x) * jnp.floor(jnp.abs(x)), v
        if e.func == "cbrt":
            return jnp.cbrt(x), v
        if e.func in ("sin", "cos", "tan", "asin", "acos", "atan"):
            fn = {
                "sin": jnp.sin, "cos": jnp.cos, "tan": jnp.tan,
                "asin": jnp.arcsin, "acos": jnp.arccos,
                "atan": jnp.arctan,
            }[e.func]
            if e.func in ("asin", "acos"):
                v = _and_valid(v, jnp.abs(x) <= 1.0)
                x = jnp.clip(x, -1.0, 1.0)
            return fn(x), v
        if e.func == "degrees":
            return x * (180.0 / float(np.pi)), v
        if e.func == "radians":
            return x * (float(np.pi) / 180.0), v
        if e.func in ("sinh", "cosh", "tanh"):
            fn = {
                "sinh": jnp.sinh, "cosh": jnp.cosh, "tanh": jnp.tanh,
            }[e.func]
            return fn(x), v
        raise NotImplementedError(f"math function {e.func}")

    def _eval_mathfunc2(self, e: MathFunc2):
        if (
            e.left.dtype.is_long_decimal
            or e.right.dtype.is_long_decimal
        ):
            raise NotImplementedError(
                "math functions over long decimals: cast to "
                "decimal(18,s) or double first (documented deviation)"
            )
        ld, lv = self.eval(e.left)
        rd, rv = self.eval(e.right)
        valid = _and_valid(lv, rv)
        lt = e.left.dtype
        x = ld.astype(jnp.float64)
        if lt.is_decimal:
            x = x / (10 ** lt.scale)
        y = rd.astype(jnp.float64)
        if e.right.dtype.is_decimal:
            y = y / (10 ** e.right.dtype.scale)
        if e.func == "power":
            return jnp.power(x, y), valid
        if e.func == "atan2":
            return jnp.arctan2(x, y), valid
        if e.func == "log":
            # Presto log(base, x)
            ok = (x > 0) & (y > 0)
            out = jnp.log(
                jnp.maximum(y, jnp.finfo(jnp.float64).tiny)
            ) / jnp.log(jnp.maximum(x, jnp.finfo(jnp.float64).tiny))
            return out, _and_valid(valid, ok)
        if e.func in ("round", "truncate"):
            factor = jnp.power(10.0, y)
            scaled = x * factor
            half = 0.5 if e.func == "round" else 0.0
            out = jnp.sign(scaled) * jnp.floor(
                jnp.abs(scaled) + half
            ) / factor
            if lt.is_integer:
                return out.astype(jnp.int64), valid
            if lt.is_decimal:
                return (
                    jnp.sign(out)
                    * jnp.floor(jnp.abs(out) * (10 ** lt.scale) + 0.5)
                ).astype(jnp.int64), valid
            return out, valid
        raise NotImplementedError(f"math function {e.func}")

    def _eval_datetrunc(self, e: DateTrunc):
        d, v = self.eval(e.arg)
        unit = e.unit
        is_ts = e.arg.dtype.name == "timestamp"
        if is_ts:
            us_per_day = 86_400_000_000
            days = jnp.floor_divide(d, us_per_day)
            if unit == "hour":
                q = 3_600_000_000
                return jnp.floor_divide(d, q) * q, v
            if unit == "minute":
                q = 60_000_000
                return jnp.floor_divide(d, q) * q, v
            if unit == "second":
                q = 1_000_000
                return jnp.floor_divide(d, q) * q, v
        else:
            days = d
        if unit == "day":
            out_days = days
        elif unit == "week":
            # epoch day 0 = Thursday; Monday-start ISO weeks
            out_days = days - (days + 3) % 7
        else:
            y, m, _day = _civil_from_days(days)
            if unit == "month":
                out_days = _days_from_civil(y, m, jnp.int64(1))
            elif unit == "quarter":
                qm = ((m - 1) // 3) * 3 + 1
                out_days = _days_from_civil(y, qm, jnp.int64(1))
            elif unit == "year":
                out_days = _days_from_civil(
                    y, jnp.int64(1), jnp.int64(1)
                )
            else:
                raise NotImplementedError(f"date_trunc({unit})")
        if is_ts:
            return out_days * 86_400_000_000, v
        return out_days.astype(e.arg.dtype.jnp_dtype), v

    def _eval_dateadd(self, e: DateAdd):
        nd, nv = self.eval(e.n)
        d, v = self.eval(e.arg)
        valid = _and_valid(nv, v)
        n = nd.astype(jnp.int64)
        is_ts = e.arg.dtype.name == "timestamp"
        us_per_day = 86_400_000_000
        days = jnp.floor_divide(d, us_per_day) if is_ts else d
        tod = d - days * us_per_day if is_ts else None
        if e.unit in ("day", "week"):
            out_days = days + n * (7 if e.unit == "week" else 1)
        else:
            months = n * (12 if e.unit == "year" else 1)
            y, m, day = _civil_from_days(days)
            total = y * 12 + (m - 1) + months
            y2 = jnp.floor_divide(total, 12)
            m2 = total - y2 * 12 + 1
            first = _days_from_civil(y2, m2, jnp.int64(1))
            nxt = _days_from_civil(
                y2 + (m2 == 12), jnp.where(m2 == 12, 1, m2 + 1),
                jnp.int64(1),
            )
            out_days = first + jnp.minimum(day, nxt - first) - 1
        if is_ts:
            return out_days * us_per_day + tod, valid
        return out_days.astype(e.arg.dtype.jnp_dtype), valid

    def _array_block(self, e: Expr):
        if not isinstance(e, ColumnRef):
            raise NotImplementedError(
                "array operations require a physical array column"
            )
        blk = self.page.block(e.name)
        if blk.offsets is None:
            raise NotImplementedError(
                f"{e.name} is not a physical array column"
            )
        return blk

    def _eval_arraylength(self, e: ArrayLength):
        blk = self._array_block(e.arg)
        lengths = (blk.offsets[1:] - blk.offsets[:-1]).astype(jnp.int64)
        return lengths, blk.valid

    def _eval_arraysubscript(self, e: ArraySubscript):
        blk = self._array_block(e.arg)
        idx_d, idx_v = self.eval(e.index)
        idx = jnp.broadcast_to(
            idx_d.astype(jnp.int64), (blk.capacity,)
        )
        lengths = (blk.offsets[1:] - blk.offsets[:-1]).astype(jnp.int64)
        # 1-based; negative counts from the end (Presto element_at)
        pos = jnp.where(idx < 0, lengths + idx, idx - 1)
        in_range = (pos >= 0) & (pos < lengths)
        src = jnp.clip(
            blk.offsets[:-1].astype(jnp.int64) + pos,
            0,
            max(blk.data.shape[0] - 1, 0),
        )
        data = blk.data[src]
        valid = in_range
        if blk.valid is not None:
            valid = valid & blk.valid
        if idx_v is not None:
            valid = valid & jnp.broadcast_to(idx_v, (blk.capacity,))
        return data, valid

    def _map_block(self, e: Expr):
        if not isinstance(e, ColumnRef):
            raise NotImplementedError(
                "map operations require a physical map column"
            )
        blk = self.page.block(e.name)
        if not blk.dtype.is_map:
            raise NotImplementedError(f"{e.name} is not a map column")
        return blk

    def _eval_mapsubscript(self, e: MapSubscript):
        blk = self._map_block(e.arg)
        kc, vc = blk.children
        cap = blk.capacity
        vcap = kc.data.shape[0]
        off = blk.offsets.astype(jnp.int32)

        # per-row lookup key in the child's device representation
        if e.key.dtype.is_string:
            if isinstance(e.key, Literal):
                kid = (
                    -1
                    if kc.dictionary is None or e.key.value is None
                    else kc.dictionary.id_of(str(e.key.value))
                )
                key_rows = jnp.full((cap,), kid, jnp.int32)
                kv = None
            else:
                kd, kv = self.eval(e.key)
                if self.dictionary_of(e.key) != kc.dictionary:
                    raise NotImplementedError(
                        "map subscript with a different-dictionary "
                        "string key requires re-encode"
                    )
                key_rows = jnp.broadcast_to(kd, (cap,))
        else:
            kd, kv = self.eval(e.key)
            key_rows = jnp.broadcast_to(jnp.asarray(kd), (cap,))

        j = jnp.arange(vcap, dtype=jnp.int32)
        row_of_j = jnp.minimum(
            jnp.searchsorted(off[1:], j, side="right"), cap - 1
        ).astype(jnp.int32)
        in_seg = j < off[cap]
        # compare in the WIDER domain: narrowing the key to the child
        # dtype would wrap modulo 2^32 and fabricate matches (a bigint
        # subscript of 2^32+5 must miss integer key 5, not hit it)
        flat_keys = kc.data
        if not e.key.dtype.is_string and jnp.issubdtype(
            flat_keys.dtype, jnp.integer
        ):
            flat_keys = flat_keys.astype(jnp.int64)
            key_rows = key_rows.astype(jnp.int64)
        match = in_seg & (flat_keys == key_rows[row_of_j])
        # segmented running max of (match ? j : -1), restart at segment
        # starts; read at each row's last flat slot
        seg_start = j == off[row_of_j]
        from jax import lax

        def combine(a, b):
            av, af = a
            bv, bf = b
            return jnp.where(bf, bv, jnp.maximum(av, bv)), af | bf

        vals, _ = lax.associative_scan(
            combine,
            (jnp.where(match, j, -1).astype(jnp.int32), seg_start),
        )
        last = jnp.clip(off[1:] - 1, 0, max(vcap - 1, 0))
        idx = jnp.where(off[1:] > off[:-1], vals[last], -1)
        found = idx >= 0
        safe = jnp.clip(idx, 0, max(vcap - 1, 0))
        data = vc.data[safe]
        valid = found
        if vc.valid is not None:
            valid = valid & vc.valid[safe]
        if blk.valid is not None:
            valid = valid & blk.valid
        if kv is not None:
            valid = valid & jnp.broadcast_to(kv, (cap,))
        return data, valid

    def _eval_rowfieldaccess(self, e: RowFieldAccess):
        if not isinstance(e.arg, ColumnRef):
            raise NotImplementedError(
                "row field access requires a physical row column"
            )
        blk = self.page.block(e.arg.name)
        if not blk.dtype.is_row:
            raise NotImplementedError(
                f"{e.arg.name} is not a row column"
            )
        ch = blk.children[blk.dtype.field_index(e.field)]
        valid = _and_valid(blk.valid, ch.valid)
        return ch.data, valid

    def _eval_valuehash(self, e: ValueHash):
        d, v = self.eval(e.arg)
        at = e.arg.dtype
        if at.is_long_decimal:
            x = (
                d[..., 0].astype(jnp.uint64)
                * jnp.uint64(0x9E3779B97F4A7C15)
            ) ^ d[..., 1].astype(jnp.uint64)
        elif at.name in ("double", "real"):
            from presto_tpu.ops.common import float_bits_i64

            # +0.0 and -0.0 are SQL-equal (one bit pattern)
            x = float_bits_i64(d).astype(jnp.uint64)
        else:
            x = jnp.asarray(d).astype(jnp.int64).astype(jnp.uint64)
        # splitmix64 finalizer (public-domain mixing constants), folded
        # to 32 bits so int64 sums of the hashes cannot wrap
        z = x + jnp.uint64(0x9E3779B97F4A7C15)
        z = (z ^ (z >> jnp.uint64(30))) * jnp.uint64(0xBF58476D1CE4E5B9)
        z = (z ^ (z >> jnp.uint64(27))) * jnp.uint64(0x94D049BB133111EB)
        z = z ^ (z >> jnp.uint64(31))
        h = (z & jnp.uint64(0xFFFFFFFF)).astype(jnp.int64)
        if v is not None:
            h = jnp.where(v, h, jnp.int64(0x9E3779B9))
        return h, None

    def _int_to_dict(self, e: "IntToDict"):
        """(Dictionary, value LUT over [lo, hi]), cached per key."""
        key = (e.fn_key, e.lo, e.hi)
        if key not in self._transform_cache:
            from presto_tpu.page import Dictionary

            vals = np.asarray(
                [str(e.fn(i)) for i in range(e.lo, e.hi + 1)],
                dtype=object,
            )
            uniq = np.unique(vals.astype(str))
            lut = np.searchsorted(uniq, vals.astype(str)).astype(
                np.int32
            )
            self._transform_cache[key] = (
                Dictionary(np.asarray(uniq, dtype=object)),
                lut,
            )
        return self._transform_cache[key]

    def _eval_inttodict(self, e: "IntToDict"):
        d, v = self.eval(e.arg)
        _, lut = self._int_to_dict(e)
        idx = jnp.clip(
            d.astype(jnp.int64) - e.lo, 0, e.hi - e.lo
        )
        return jnp.asarray(lut)[idx], v

    def _eval_dictintfunc(self, e: DictIntFunc):
        data, valid = self.eval(e.arg)
        dic = self.dictionary_of(e.arg)
        lut = np.asarray(
            [int(e.fn(v)) for v in dic.values], dtype=np.int64
        )
        if len(lut) == 0:
            return jnp.zeros((self.page.capacity,), jnp.int64), valid
        return jnp.asarray(lut)[jnp.clip(data, 0, len(lut) - 1)], valid

    def _eval_extract(self, e: Extract):
        d, v = self.eval(e.arg)
        if e.arg.dtype.name == "timestamp":
            d = jnp.floor_divide(d, 86_400_000_000)
        y, m, day = _civil_from_days(d)
        f = e.field.lower()
        if f == "year":
            return y, v
        if f == "month":
            return m, v
        if f == "day":
            return day, v
        if f == "quarter":
            return (m + 2) // 3, v
        if f in ("day_of_week", "dow"):
            # ISO: 1 = Monday .. 7 = Sunday; epoch day 0 was a Thursday
            return (d + 3) % 7 + 1, v
        if f in ("day_of_year", "doy"):
            return d - _days_from_civil(
                y, jnp.int64(1), jnp.int64(1)
            ) + 1, v
        if f == "week":
            # ISO week number of the ISO year containing the date
            thursday = d - (d + 3) % 7 + 3
            ty, _, _ = _civil_from_days(thursday)
            jan1 = _days_from_civil(ty, jnp.int64(1), jnp.int64(1))
            return (thursday - jan1) // 7 + 1, v
        raise NotImplementedError(f"extract({e.field})")


def _maybe_zero(e: Expr) -> bool:
    return not (isinstance(e, Literal) and e.value not in (0, None))


def _tv_and_valid(ld, lv, rd, rv):
    """Validity of (l AND r): known iff both known, or either is known-false."""
    lk = lv if lv is not None else True
    rk = rv if rv is not None else True
    known_false = ((ld == False) & lk) | ((rd == False) & rk)  # noqa: E712
    return (lk & rk) | known_false


def _tv_or_valid(ld, lv, rd, rv):
    lk = lv if lv is not None else True
    rk = rv if rv is not None else True
    known_true = (ld & lk) | (rd & rk)
    return (lk & rk) | known_true


def _coerce_to(data, from_t: T.DataType, to_t: T.DataType):
    if from_t == to_t:
        return data
    if to_t.is_long_decimal:
        from presto_tpu import int128

        if from_t.is_long_decimal:
            h, l = data[..., 0], data[..., 1]
            from_scale = from_t.scale
        else:
            h, l = int128.from_i64(data.astype(jnp.int64))
            from_scale = from_t.scale if from_t.is_decimal else 0
        if to_t.scale < from_scale:
            raise NotImplementedError(
                "long-decimal downscale requires int128 division"
            )
        h, l = int128.mul_pow10(h, l, to_t.scale - from_scale)
        return jnp.stack([h, l], axis=-1)
    if from_t.is_long_decimal:
        raise NotImplementedError(
            f"implicit narrowing of {from_t} to {to_t}; cast explicitly"
        )
    if to_t.is_decimal and from_t.is_decimal:
        return _rescale(data, from_t.scale, to_t.scale)
    if to_t.is_decimal and from_t.is_integer:
        return data.astype(jnp.int64) * (10 ** to_t.scale)
    return data.astype(to_t.jnp_dtype)


def eval_expr(expr: Expr, page: Page):
    """Lower ``expr`` over ``page`` -> (data, valid|None). Trace-time API."""
    return ExprLowerer(page).eval(expr)


def eval_predicate(expr: Expr, page: Page) -> jnp.ndarray:
    """Predicate as a keep-mask over live rows: NULL -> False (SQL WHERE),
    padding rows -> False."""
    d, v = eval_expr(expr, page)
    mask = d if v is None else (d & v)
    return mask & page.row_mask()
