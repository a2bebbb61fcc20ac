"""Scan-based reference query engine — the correctness oracle.

Pure-Python row-at-a-time evaluator of a BrokerRequest over in-memory
records.  Plays the role of the reference's golden model
(pinot-tools ``tools/scan/query/ScanBasedQueryProcessor.java:40``), used
by sentinel and differential tests to pin the TPU engine's semantics.

Semantics notes (matched to the reference engine):

- Predicate literals are compared in the column's stored type domain:
  numeric columns compare numerically, strings lexicographically.
- Multi-value (MV) columns: a row matches a positive predicate
  (EQ/IN/RANGE/REGEX) if ANY of its values matches; for negative
  predicates (NOT/NOT_IN) a row matches if NONE of its values is
  excluded (complement semantics).
- Group-by on an MV column produces one group per value in the row
  (rows are counted once per matching value).
- ``percentileNN`` is the exact reference formula: sort ascending, take
  ``sorted[int(n * NN/100)]`` (``quantile/PercentileUtil.java:50``).
  ``percentileestNN`` follows the same exact path here (the reference
  approximates with a q-digest; exactness is a superset of its contract).
- ``distinctcounthll`` / ``fasthll`` estimate cardinality with HLL; the
  oracle computes them through the same HLL sketch implementation used
  by the TPU engine (``pinot_tpu.engine.hll``) so results agree exactly.
- Group-by results are sorted by aggregated value, descending — except
  functions whose name starts with "min", which sort ascending
  (``AggregationGroupByOperatorService.java:146``) — and trimmed to TOP n.
"""
from __future__ import annotations

import math
import re
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

from pinot_tpu.common.request import (
    AggregationInfo,
    BrokerRequest,
    FilterOperator,
    FilterQueryTree,
    RangeSpec,
    expr_eval,
    group_sort_ascending,
)
from pinot_tpu.common.response import (
    AggregationResult,
    BrokerResponse,
    GroupByResult,
    SelectionResults,
)
from pinot_tpu.common.schema import DataType, Schema

Row = Dict[str, Any]


# ---------------------------------------------------------------------------
# Predicate evaluation
# ---------------------------------------------------------------------------


def _coerce(literal: str, dt: DataType) -> Any:
    return dt.convert(literal)


def _values_of(row: Row, column: str) -> List[Any]:
    v = row[column]
    if isinstance(v, (list, tuple)):
        return list(v)
    return [v]


class _LeafEvaluator:
    """Evaluates one leaf predicate against a row (PredicateEvaluator analog)."""

    def __init__(self, node: FilterQueryTree, schema: Schema) -> None:
        self.node = node
        self.column = node.column
        spec = schema.field(node.column)
        dt = spec.data_type
        self.is_string = dt.stored_type == DataType.STRING
        op = node.operator
        if op in (FilterOperator.EQUALITY, FilterOperator.IN):
            self.targets = {_coerce(v, dt) for v in node.values}
            self.negate = False
            self.kind = "set"
        elif op in (FilterOperator.NOT, FilterOperator.NOT_IN):
            self.targets = {_coerce(v, dt) for v in node.values}
            self.negate = True
            self.kind = "set"
        elif op == FilterOperator.RANGE:
            r = node.range_spec or RangeSpec()
            self.lower = _coerce(r.lower, dt) if r.lower is not None and r.lower != "*" else None
            self.upper = _coerce(r.upper, dt) if r.upper is not None and r.upper != "*" else None
            self.incl_lower = r.include_lower
            self.incl_upper = r.include_upper
            self.kind = "range"
        elif op == FilterOperator.REGEX:
            self.pattern = re.compile(node.values[0])
            self.kind = "regex"
        else:
            raise ValueError(f"unsupported leaf operator {op}")

    def _match_one(self, v: Any) -> bool:
        if self.kind == "set":
            return v in self.targets
        if self.kind == "range":
            if self.lower is not None:
                if self.incl_lower:
                    if v < self.lower:
                        return False
                elif v <= self.lower:
                    return False
            if self.upper is not None:
                if self.incl_upper:
                    if v > self.upper:
                        return False
                elif v >= self.upper:
                    return False
            return True
        if self.kind == "regex":
            return self.pattern.search(str(v)) is not None
        raise AssertionError

    def matches(self, row: Row) -> bool:
        vals = _values_of(row, self.column)
        if self.kind == "set" and self.negate:
            # NOT/NOT_IN over MV: no value may be in the excluded set.
            return all(v not in self.targets for v in vals)
        return any(self._match_one(v) for v in vals)


def _build_matcher(tree: Optional[FilterQueryTree], schema: Schema):
    if tree is None:
        return lambda row: True
    if tree.is_leaf:
        return _LeafEvaluator(tree, schema).matches
    child_fns = [_build_matcher(c, schema) for c in tree.children]
    if tree.operator == FilterOperator.AND:
        return lambda row: all(f(row) for f in child_fns)
    if tree.operator == FilterOperator.OR:
        return lambda row: any(f(row) for f in child_fns)
    raise ValueError(f"unsupported non-leaf operator {tree.operator}")


# ---------------------------------------------------------------------------
# Aggregation
# ---------------------------------------------------------------------------


def _numeric_values(row: Row, agg: AggregationInfo) -> List[float]:
    if agg.expr is not None:  # sum(a*(1-b)): one value a row, from single-value columns
        return [float(expr_eval(agg.expr, lambda c: float(row[c])))]
    vals = _values_of(row, agg.column)
    return [float(v) for v in vals]


class _Accumulator:
    """One aggregation function's running state (oracle-side, exact)."""

    def __init__(self, agg: AggregationInfo) -> None:
        self.agg = agg
        base = agg.base_function
        self.base = base
        self.count = 0
        self.sum = 0.0
        self.min = math.inf
        self.max = -math.inf
        self.distinct: set = set()
        self.values: List[float] = []  # for percentiles

    def add(self, row: Row) -> None:
        base = self.base
        if base == "count":
            if self.agg.is_mv:
                self.count += len(_values_of(row, self.agg.column))
            else:
                self.count += 1
            return
        if base in ("distinctcount", "distinctcounthll", "fasthll"):
            for v in _values_of(row, self.agg.column):
                self.distinct.add(v)
            return
        vals = _numeric_values(row, self.agg)
        for v in vals:
            self.count += 1
            self.sum += v
            if v < self.min:
                self.min = v
            if v > self.max:
                self.max = v
        if base.startswith("percentile"):
            self.values.extend(vals)

    def result(self) -> Any:
        base = self.base
        if base == "count":
            return self.count
        if base == "sum":
            return self.sum
        if base == "min":
            return self.min
        if base == "max":
            return self.max
        if base == "avg":
            return self.sum / self.count if self.count else -math.inf
        if base == "minmaxrange":
            return self.max - self.min
        if base == "distinctcount":
            return len(self.distinct)
        if base in ("distinctcounthll", "fasthll"):
            from pinot_tpu.engine.hll import hll_estimate_exact_values

            return hll_estimate_exact_values(self.distinct)
        if base.startswith("percentileest"):
            p = int(base[len("percentileest"):])
            return _percentile(self.values, p)
        if base.startswith("percentile"):
            p = int(base[len("percentile"):])
            return _percentile(self.values, p)
        raise ValueError(f"unknown aggregation {base}")


def _percentile(values: List[float], p: int) -> float:
    """Reference formula: quantile/PercentileUtil.java:50."""
    if not values:
        return -math.inf
    s = sorted(values)
    idx = min(int(len(s) * p / 100.0), len(s) - 1)
    return s[idx]


def _group_sort_ascending(function: str) -> bool:
    """AggregationGroupByOperatorService.java:146 — min* sorts ascending."""
    return group_sort_ascending(function)


# ---------------------------------------------------------------------------
# The engine
# ---------------------------------------------------------------------------


class ScanQueryProcessor:
    """Executes BrokerRequests over a list of rows by brute-force scan."""

    def __init__(self, schema: Schema, rows: Sequence[Row]) -> None:
        self.schema = schema
        # Normalize rows exactly like the segment builder: type-convert
        # every value, fill missing with default null values — so the
        # oracle sees the same stored values the engine does.
        self.rows = [self._normalize(r) for r in rows]

    def _normalize(self, row: Row) -> Row:
        out: Row = {}
        for spec in self.schema.all_fields():
            v = row.get(spec.name)
            if v is None:
                out[spec.name] = (
                    spec.get_default_null_value()
                    if spec.single_value
                    else [spec.get_default_null_value()]
                )
            elif spec.single_value:
                out[spec.name] = spec.stored_type.convert(v)
            else:
                vs = v if isinstance(v, (list, tuple)) else [v]
                out[spec.name] = [spec.stored_type.convert(x) for x in vs] or [
                    spec.get_default_null_value()
                ]
        return out

    def execute(self, request: BrokerRequest) -> BrokerResponse:
        matcher = _build_matcher(request.filter, self.schema)
        matched = [r for r in self.rows if matcher(r)]

        resp = BrokerResponse(
            num_docs_scanned=len(matched),
            total_docs=len(self.rows),
            num_segments_queried=1,
            num_servers_queried=1,
            num_servers_responded=1,
        )

        if request.is_aggregation:
            if request.is_group_by:
                resp.aggregation_results = self._group_by(request, matched)
            else:
                resp.aggregation_results = self._aggregate(request, matched)
        else:
            resp.selection_results = self._selection(request, matched)
        return resp

    # -- aggregation-only ---------------------------------------------
    def _aggregate(self, request: BrokerRequest, rows: List[Row]) -> List[AggregationResult]:
        out = []
        for agg in request.aggregations:
            acc = _Accumulator(agg)
            for row in rows:
                acc.add(row)
            out.append(AggregationResult(function=agg.display_name, value=acc.result()))
        return out

    # -- group-by ------------------------------------------------------
    def _group_keys(self, row: Row, columns: List[str]) -> List[Tuple[str, ...]]:
        """Cartesian product over MV group-by column values (Pinot MV
        group-by semantics: one group per MV value combination)."""
        keys: List[Tuple[str, ...]] = [()]
        for col in columns:
            vals = _values_of(row, col)
            keys = [k + (self._render(col, v),) for k in keys for v in vals]
        return keys

    def _render(self, column: str, v: Any) -> str:
        from pinot_tpu.common.values import render_value

        return render_value(self.schema.field(column).stored_type, v)

    def _group_by(self, request: BrokerRequest, rows: List[Row]) -> List[AggregationResult]:
        gb = request.group_by
        assert gb is not None
        groups: Dict[Tuple[str, ...], List[_Accumulator]] = {}
        for row in rows:
            for key in self._group_keys(row, gb.columns):
                accs = groups.get(key)
                if accs is None:
                    accs = [_Accumulator(a) for a in request.aggregations]
                    groups[key] = accs
                for acc in accs:
                    acc.add(row)

        out: List[AggregationResult] = []
        for i, agg in enumerate(request.aggregations):
            pairs = [(key, accs[i].result()) for key, accs in groups.items()]
            asc = _group_sort_ascending(agg.function)
            pairs.sort(key=lambda kv: (kv[1], kv[0]) if asc else (-kv[1], kv[0]))
            trimmed = pairs[: gb.top_n]
            out.append(
                AggregationResult(
                    function=agg.display_name,
                    group_by_columns=list(gb.columns),
                    group_by_result=[GroupByResult(group=list(k), value=v) for k, v in trimmed],
                )
            )
        return out

    # -- selection -----------------------------------------------------
    def _selection(self, request: BrokerRequest, rows: List[Row]) -> SelectionResults:
        sel = request.selection
        assert sel is not None
        columns = sel.columns
        if columns == ["*"] or not columns:
            columns = self.schema.column_names

        if sel.sorts:
            def sort_key(row: Row):
                key = []
                for s in sel.sorts:
                    v = row[s.column]
                    if isinstance(v, (list, tuple)):
                        v = v[0] if v else None
                    key.append(_Reversible(v, not s.ascending))
                return key

            ordered = sorted(rows, key=sort_key)
        else:
            ordered = rows

        window = ordered[sel.offset : sel.offset + sel.size]
        out_rows = [[row[c] for c in columns] for row in window]
        return SelectionResults(columns=list(columns), rows=out_rows)


class _Reversible:
    """Sort-key wrapper supporting per-column descending order."""

    __slots__ = ("v", "desc")

    def __init__(self, v: Any, desc: bool) -> None:
        self.v = v
        self.desc = desc

    def __lt__(self, other: "_Reversible") -> bool:
        if self.desc:
            return other.v < self.v
        return self.v < other.v

    def __eq__(self, other: object) -> bool:
        return isinstance(other, _Reversible) and self.v == other.v
