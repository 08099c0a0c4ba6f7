"""Logical dataflow graph — the analog of the reference's ``arroyo-datastream``
crate (/root/reference/arroyo-datastream/src/lib.rs).

Reproduces the full operator taxonomy (``Operator`` enum, lib.rs:321-372), the
window types (lib.rs:102-108), ``StreamNode``/``StreamEdge``/``EdgeType``
(lib.rs:497-553), the fluent ``Stream`` builder API (lib.rs:559-986), graph
validation (window-needs-watermark, lib.rs:1099-1117) and the graph hash used
for artifact caching (lib.rs:1140-1154).

Where the reference's operators carry *Rust source strings* to be spliced into
a generated binary (``make_graph_function``, lib.rs:1216-1700), ours carry
Python callables over columnar batches: element-wise expressions are functions
``cols -> cols`` traced by jax.jit inside the physical operators, so "compiling
a pipeline" is tracing, not cargo.
"""

from __future__ import annotations

import hashlib
import json
from dataclasses import dataclass, field
from enum import Enum
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import networkx as nx

MICROS = 1_000_000


# ---------------------------------------------------------------------------
# Window types (arroyo-datastream/src/lib.rs:102-108)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class TumblingWindow:
    width_micros: int


@dataclass(frozen=True)
class SlidingWindow:
    width_micros: int
    slide_micros: int


@dataclass(frozen=True)
class InstantWindow:
    pass


@dataclass(frozen=True)
class SessionWindow:
    gap_micros: int


WindowType = Any  # union of the four dataclasses above


def window_label(w: WindowType) -> str:
    if isinstance(w, TumblingWindow):
        return f"tumbling({w.width_micros}us)"
    if isinstance(w, SlidingWindow):
        return f"sliding({w.width_micros}us,{w.slide_micros}us)"
    if isinstance(w, InstantWindow):
        return "instant"
    if isinstance(w, SessionWindow):
        return f"session({w.gap_micros}us)"
    raise TypeError(w)


# ---------------------------------------------------------------------------
# Aggregates & expressions
# ---------------------------------------------------------------------------


class AggKind(Enum):
    COUNT = "count"
    COUNT_DISTINCT = "count_distinct"
    SUM = "sum"
    MIN = "min"
    MAX = "max"
    AVG = "avg"
    VEC = "vec"  # collect values (WindowAgg::Expression / flatten path)
    UDAF = "udaf"  # user aggregate fn(values)->scalar; buffered paths only


@dataclass(frozen=True)
class AggSpec:
    """One aggregate: kind + input column + output column name.

    ``fn`` carries the Python callable for UDAF kinds (user aggregates,
    the analog of the reference's registered UDFs executed in the worker,
    arroyo-sql/src/lib.rs:196-290 + operators/mod.rs:347-494).  UDAFs are
    not mergeable, so they plan onto the buffered window paths only —
    matching the reference's two-phase exclusion (operators.rs:165-167).
    """

    kind: AggKind
    column: Optional[str]  # None for COUNT(*)
    output: str
    fn: Optional[Any] = None


class ExprReturnType(Enum):
    """ExpressionReturnType (arroyo-datastream/src/lib.rs:549-553)."""

    PREDICATE = "predicate"
    RECORD = "record"
    OPTIONAL_RECORD = "optional_record"


@dataclass
class ColumnExpr:
    """A columnar expression: ``fn(cols: dict[str, array]) -> dict | array``.

    ``fn`` must be jnp-traceable (no data-dependent Python control flow); the
    physical ExpressionOperator jits it over the batch columns.  ``name`` keys
    the jit cache and the graph hash.
    """

    name: str
    fn: Callable[[Dict[str, Any]], Any]
    return_type: ExprReturnType = ExprReturnType.RECORD
    output_schema: Optional[Dict[str, Any]] = None
    sql: str = ""  # original SQL text when planner-generated (for hashing/UI)

    def hash_token(self) -> str:
        return self.sql or self.name


# ---------------------------------------------------------------------------
# Operator taxonomy (Operator enum, arroyo-datastream/src/lib.rs:321-372)
# ---------------------------------------------------------------------------


class OpKind(Enum):
    CONNECTOR_SOURCE = "connector_source"
    CONNECTOR_SINK = "connector_sink"
    EXPRESSION = "expression"  # map / filter / option-map
    FLAT_MAP = "flat_map"
    FLATTEN = "flatten"
    UDF = "udf"  # python UDF (reference: FusedWasmUDFs)
    WATERMARK = "watermark"
    KEY_BY = "key_by"
    GLOBAL_KEY = "global_key"
    WINDOW = "window"  # KeyedWindowFunc / SessionWindowFunc
    COUNT = "count"
    AGGREGATE = "aggregate"  # AggregateBehavior Max/Min/Sum
    WINDOW_JOIN = "window_join"
    SLIDING_WINDOW_AGGREGATOR = "sliding_window_aggregator"
    TUMBLING_WINDOW_AGGREGATOR = "tumbling_window_aggregator"
    TUMBLING_TOP_N = "tumbling_top_n"
    SLIDING_AGGREGATING_TOP_N = "sliding_aggregating_top_n"
    JOIN_WITH_EXPIRATION = "join_with_expiration"
    UPDATING = "updating"
    NON_WINDOW_AGGREGATOR = "non_window_aggregator"
    UPDATING_KEY = "updating_key"
    UNION = "union"  # N-ary stream merge (the reference bails on unions)
    WINDOW_ARGMAX = "window_argmax"  # fused self-join-on-window-max
    MULTI_WAY_JOIN = "multi_way_join"  # N-ary shared-key equi-join
    # factor-window sharing (graph/factor_windows.py, "Factor Windows"
    # PAPERS.md): ONE shared pane ring feeding per-query derived windows
    WINDOW_FACTOR = "window_factor"  # shared factor-pane aggregate
    DERIVED_WINDOW = "derived_window"  # rolls factor panes into a query window


class JoinType(Enum):
    INNER = "inner"
    LEFT = "left"
    RIGHT = "right"
    FULL = "full"
    SEMI = "semi"  # IN (SELECT ...): left rows emit once on first match


@dataclass
class PeriodicWatermarkSpec:
    """Operator::Watermark(PeriodicWatermark) — fixed-lateness or expression
    watermark with idle detection (operators/mod.rs:97-233)."""

    max_lateness_micros: int = 0
    idle_time_micros: Optional[int] = None
    expression: Optional[ColumnExpr] = None  # row -> watermark timestamp


@dataclass
class WindowSpec:
    """Operator::Window{typ, agg, flatten}."""

    typ: WindowType
    aggs: Tuple[AggSpec, ...] = ()
    flatten: bool = False
    # post-aggregate projection applied to {key cols + agg outputs + window bounds}
    projection: Optional[ColumnExpr] = None


@dataclass
class SlidingAggregatorSpec:
    """Operator::SlidingWindowAggregator — two-phase bin-merged sliding
    aggregate (arroyo-datastream/src/lib.rs:224-241;
    aggregating_window.rs:14-258)."""

    width_micros: int
    slide_micros: int
    aggs: Tuple[AggSpec, ...] = ()
    projection: Optional[ColumnExpr] = None
    # (agg output, 'max'|'min') when emission may pre-filter to local
    # per-pane argmax candidates (set by the planner only when the sole
    # consumer is a WindowArgmax stage, which settles the global answer)
    argmax_local: Optional[Tuple[str, str]] = None


@dataclass
class TumblingAggregatorSpec:
    width_micros: int
    aggs: Tuple[AggSpec, ...] = ()
    projection: Optional[ColumnExpr] = None
    argmax_local: Optional[Tuple[str, str]] = None  # see SlidingAggregatorSpec


@dataclass
class FactorPaneSpec:
    """Operator::WindowFactor — the shared half of a factor-window rewrite
    (graph/factor_windows.py).  One BinAggOperator ring of ``pane_micros``
    tumbling panes maintains the UNION of the member queries' decomposed
    partial aggregates (``__f_*`` columns) once per pane; the member
    queries consume the fired panes as lightweight derived windows."""

    pane_micros: int
    aggs: Tuple[AggSpec, ...] = ()


@dataclass
class DerivedWindowSpec:
    """Operator::DerivedWindow — the per-query half of a factor-window
    rewrite: rolls fired factor panes of ``pane_micros`` into this
    query's (width, slide) windows on the same device bin-ring kernels
    (merge-input mode), emitting exactly the rows the original
    sliding/tumbling aggregate would.  ``aggs``/``projection`` are the
    ORIGINAL member spec's, so checkpoint state tables keep the member's
    channel layout and epochs interchange with unfactored plans."""

    width_micros: int
    slide_micros: int
    pane_micros: int
    aggs: Tuple[AggSpec, ...] = ()
    projection: Optional[ColumnExpr] = None


@dataclass
class TopNSpec:
    """Operator::TumblingTopN (tumbling_top_n_window.rs).

    ``max_elements=None`` ranks without pruning; ``rank_column`` emits
    the 1-based per-partition rank (a materialized ROW_NUMBER())."""

    width_micros: int
    max_elements: Optional[int]
    # expression extracting the sort key column(s); descending order
    sort_column: str = ""
    partition_cols: Tuple[str, ...] = ()
    projection: Optional[ColumnExpr] = None
    rank_column: Optional[str] = None


@dataclass
class SlidingAggregatingTopNSpec:
    """Operator::SlidingAggregatingTopN — fused sliding aggregate + TopN
    (sliding_top_n_aggregating_window.rs; datastream lib.rs:242-262)."""

    width_micros: int
    slide_micros: int
    aggs: Tuple[AggSpec, ...] = ()
    partition_cols: Tuple[str, ...] = ()
    sort_column: str = ""
    max_elements: int = 10
    projection: Optional[ColumnExpr] = None


@dataclass
class JoinWithExpirationSpec:
    left_expiration_micros: int
    right_expiration_micros: int
    join_type: JoinType = JoinType.INNER
    # visible (name, kind) column schemas per side so outer joins can
    # null-pad the missing side even before any batch has arrived from it
    left_cols: Tuple[Tuple[str, str], ...] = ()
    right_cols: Tuple[Tuple[str, str], ...] = ()


@dataclass
class WindowArgmaxSpec:
    """Operator::WindowArgmax — the optimizer's fusion of
    ``A JOIN (SELECT max(x), window FROM A GROUP BY window) ON x = mx``
    (nexmark q5's hot-items shape): buffer A's rows per window, emit the
    rows achieving the window's max (ties included, exactly as the
    self-join emits them), and synthesize the pruned side's columns
    (mx := x).  ``minmax`` is 'max' or 'min'; ``synth_cols`` maps each
    pruned-side output column to the left column it copies."""

    value_col: str
    minmax: str
    synth_cols: Tuple[Tuple[str, str], ...]  # (out_name, left_col)
    width_micros: int  # buffer retention: one window span
    # the upstream aggregate output (__aggN) the value column carries —
    # lets the plan finalizer push a LOCAL candidate pre-filter into the
    # aggregate's emission kernel when this operator is its only consumer
    agg_out: str = ""
    # raw-stream mode (q7's shape: bids JOIN per-window max ON price=mx
    # with a window-range WHERE): inputs are raw rows rather than
    # aggregate outputs, so the operator (a) pre-filters each batch to
    # rows >= the window's running extremum before buffering (the max
    # only grows, so dominated rows can never be final candidates) and
    # (b) matches genuinely-late rows against the released window's
    # FINAL extremum, retained for late_ttl_micros — exactly how the
    # TTL'd join this fusion replaces would still hold the max row and
    # emit a late tying probe (and, like that join, drops the row once
    # the TTL passes)
    raw: bool = False
    late_ttl_micros: int = 0


@dataclass
class WindowJoinSpec:
    """Operator::WindowJoin — windowed stream-stream hash join; outer
    kinds null-pad the unmatched side per fired window (append-only, no
    retractions — each window fires once), matching the reference's
    list-merge codegen (arroyo-sql/src/expressions.rs:134-230)."""

    typ: WindowType
    join_type: JoinType = JoinType.INNER
    left_cols: Tuple[Tuple[str, str], ...] = ()
    right_cols: Tuple[Tuple[str, str], ...] = ()


@dataclass
class MultiWayJoinSpec:
    """Operator::MultiWayJoin — one N-ary INNER equi-join over sides that
    share one join key (the planner's cascaded-join rewrite, after
    "Streaming SQL Multi-Way Join Method for Long State Streams",
    PAPERS.md).  All sides are keyed identically; per fire the operator
    intersects the sides' sorted runs and expands the per-key cross
    product directly — the pairwise intermediates a nested join plan
    would materialize (|A⋈B| rows re-buffered, re-keyed, re-probed
    against C) never exist.

    ``typ`` set: windowed fire (each side buffered for one window span);
    ``typ`` None: TTL'd state probed on every arriving batch."""

    typ: Optional[WindowType] = None
    ttl_micros: int = 0
    side_cols: Tuple[Tuple[Tuple[str, str], ...], ...] = ()


@dataclass
class NonWindowAggregatorSpec:
    """Operator::NonWindowAggregator — updating aggregate with TTL
    (updating_aggregate.rs; datastream lib.rs:264-273)."""

    expiration_micros: int
    aggs: Tuple[AggSpec, ...] = ()
    projection: Optional[ColumnExpr] = None
    # when set (to a key-column name holding an event-time bound, e.g.
    # "window_end"): consolidate refinements in state and emit each key's
    # FINAL row once, when the watermark passes that bound — append-only
    # output instead of create/update refinements
    flush_key: Optional[str] = None


@dataclass
class ConnectorOpSpec:
    """ConnectorOp{operator, config, description}
    (arroyo-datastream/src/lib.rs:281-319)."""

    connector: str  # registry name, e.g. 'impulse', 'nexmark', 'kafka'
    config: Dict[str, Any] = field(default_factory=dict)
    description: str = ""


@dataclass
class LogicalOperator:
    kind: OpKind
    name: str
    spec: Any = None
    expr: Optional[ColumnExpr] = None
    key_cols: Tuple[str, ...] = ()

    def hash_token(self) -> str:
        tok: Dict[str, Any] = {"kind": self.kind.value, "name": self.name}
        if self.expr is not None:
            tok["expr"] = self.expr.hash_token()
            if self.expr.sql:
                # a structural sql token fully describes the computation;
                # the generated display name (agg_input_<n>) must not
                # break equality between duplicated subplans
                del tok["name"]
        if self.key_cols:
            tok["key"] = list(self.key_cols)
        if self.spec is not None:
            tok["spec"] = repr(self.spec)
        return json.dumps(tok, sort_keys=True)


# ---------------------------------------------------------------------------
# Graph
# ---------------------------------------------------------------------------


class EdgeType(Enum):
    FORWARD = "forward"
    SHUFFLE = "shuffle"
    SHUFFLE_JOIN_LEFT = "shuffle_join_0"
    SHUFFLE_JOIN_RIGHT = "shuffle_join_1"
    # additional multi-way join sides (the planner's cascaded-equi-join
    # rewrite feeds one N-ary operator instead of nesting pairwise joins)
    SHUFFLE_JOIN_2 = "shuffle_join_2"
    SHUFFLE_JOIN_3 = "shuffle_join_3"
    SHUFFLE_JOIN_4 = "shuffle_join_4"
    SHUFFLE_JOIN_5 = "shuffle_join_5"
    SHUFFLE_JOIN_6 = "shuffle_join_6"
    SHUFFLE_JOIN_7 = "shuffle_join_7"

    @property
    def is_shuffle(self) -> bool:
        return self is not EdgeType.FORWARD

    @property
    def join_side(self) -> Optional[int]:
        """Input-side index carried by shuffle_join_N edges, else None."""
        if self.value.startswith("shuffle_join_"):
            return int(self.value.rsplit("_", 1)[1])
        return None


def join_side_edge(i: int) -> EdgeType:
    """The shuffle_join edge type for side ``i`` (0-based)."""
    return EdgeType(f"shuffle_join_{i}")


@dataclass
class StreamNode:
    """StreamNode{operator_id, operator, parallelism} (lib.rs:497-502).

    ``max_parallelism`` pins operators whose semantics require a bounded
    subtask count (e.g. a global TopN merge stage must stay at 1) across
    rescales."""

    operator_id: str
    operator: LogicalOperator
    parallelism: int = 1
    max_parallelism: Optional[int] = None


@dataclass
class StreamEdge:
    """StreamEdge{key, value, typ} (lib.rs:517-522); key/value are schema
    descriptions used for display + hashing."""

    typ: EdgeType
    key_schema: str = "()"
    value_schema: str = ""


class Program:
    """Program{graph: DiGraph<StreamNode, StreamEdge>} (lib.rs:1068-1074)."""

    def __init__(self, name: str = "pipeline"):
        self.name = name
        self.graph = nx.DiGraph()
        self._counter = 0

    # -- construction ------------------------------------------------------

    def add_node(self, op: LogicalOperator, parallelism: int = 1) -> str:
        op_id = f"{self._counter}_{op.kind.value}"
        self._counter += 1
        self.graph.add_node(op_id, node=StreamNode(op_id, op, parallelism))
        return op_id

    def add_edge(self, src: str, dst: str, typ: EdgeType,
                 key_schema: str = "()", value_schema: str = "") -> None:
        self.graph.add_edge(src, dst, edge=StreamEdge(typ, key_schema, value_schema))

    def node(self, op_id: str) -> StreamNode:
        return self.graph.nodes[op_id]["node"]

    def edge(self, src: str, dst: str) -> StreamEdge:
        return self.graph.edges[src, dst]["edge"]

    def nodes(self) -> List[StreamNode]:
        return [self.graph.nodes[n]["node"] for n in self.graph.nodes]

    def sources(self) -> List[StreamNode]:
        return [self.node(n) for n in self.graph.nodes if self.graph.in_degree(n) == 0]

    def sinks(self) -> List[StreamNode]:
        return [self.node(n) for n in self.graph.nodes if self.graph.out_degree(n) == 0]

    def topo_order(self) -> List[str]:
        return list(nx.topological_sort(self.graph))

    # -- validation (lib.rs:1099-1117) ------------------------------------

    WINDOWED_KINDS = {
        OpKind.WINDOW,
        OpKind.WINDOW_JOIN,
        OpKind.SLIDING_WINDOW_AGGREGATOR,
        OpKind.TUMBLING_WINDOW_AGGREGATOR,
        OpKind.TUMBLING_TOP_N,
        OpKind.SLIDING_AGGREGATING_TOP_N,
        OpKind.WINDOW_FACTOR,
        OpKind.DERIVED_WINDOW,
    }

    def validate(self) -> List[str]:
        """Window operators require a watermark generator upstream."""
        errors: List[str] = []
        for op_id in self.graph.nodes:
            node = self.node(op_id)
            if node.operator.kind in self.WINDOWED_KINDS:
                if not self._has_upstream(op_id, OpKind.WATERMARK):
                    errors.append(
                        f"{op_id} ({node.operator.kind.value}) requires a "
                        "watermark-assigning operator upstream"
                    )
        return errors

    def _has_upstream(self, op_id: str, kind: OpKind) -> bool:
        for anc in nx.ancestors(self.graph, op_id):
            if self.node(anc).operator.kind == kind:
                return True
        return False

    # -- common-subplan elimination ----------------------------------------

    # sources whose output is a deterministic function of their config
    # AND whose config is faithfully comparable by repr: two scans of
    # the same definition are interchangeable with one scan fanned out,
    # so the dedup pass may merge them.  Anything with consumption
    # state (kafka/kinesis offsets, consumer groups, sse/webhook/
    # polling network reads) must NOT be here.  'memory' is
    # deliberately absent: its config embeds raw numpy batches whose
    # reprs TRUNCATE past 1000 elements, so equal reprs would not prove
    # equal data.
    #
    # Wall-clock caveat: when the config does NOT pin the time base
    # (nexmark base_time_micros / impulse event-time interval), each
    # UNMERGED scan samples its own now() a few ms apart, so the two
    # sides of a self-join were never bit-consistent to begin with;
    # merging gives both consumers one shared base — the semantically
    # intended reading of "the same table".  Exact merged==unmerged
    # parity therefore holds when the base is pinned (what the tests
    # assert) and is *approached from the consistent side* when not.
    # memory tables are fixed batch lists (the test workhorse): two scans
    # of the same table object replay identically, so they merge/compare
    # like the deterministic generators do
    _REPLAYABLE_SOURCES = frozenset({"nexmark", "impulse", "memory"})

    def eliminate_common_subplans(self) -> int:
        """Merge operators that compute the same thing over the same
        inputs (equal structural hash token + equal predecessor set with
        equal edge types), redirecting the duplicate's out-edges to the
        kept node — downstream fan-out is one Collector edge group per
        consumer, so both consumers see identical batches/watermarks.

        SQL with textually repeated subqueries (nexmark q5's
        AuctionBids/CountBids, WITH-clause reuse across the reference
        ledger) otherwise runs the whole duplicated chain twice — twice
        the device updates AND twice the pane-emission readbacks.  The
        reference planner leans on DataFusion, which does not dedupe
        across the join inputs either — this pass is a genuine win over
        it.

        Sinks (side effects) never merge.  Sources merge only when the
        connector is in ``_REPLAYABLE_SOURCES`` (deterministic output,
        repr-comparable config — e.g. q8's two nexmark scans become one
        generation pass with the union of their projections); anything
        with consumption state (kafka offsets, consumer groups) never
        does.  A merge that would create a parallel edge (e.g. both
        sides of a self-join collapsing onto one node, which a DiGraph
        cannot represent and the engine's per-(src, dst) queues do not
        support) is skipped.  Returns the number of nodes removed."""
        import os

        if os.environ.get("ARROYO_CSE", "1") in ("0", "off", "false"):
            return 0
        removed = 0
        changed = True
        while changed:
            changed = False
            by_sig: Dict[tuple, str] = {}
            for op_id in self.topo_order():
                node = self.node(op_id)
                preds = tuple(sorted(
                    (s, d["edge"].typ.value, d["edge"].key_schema)
                    for s, _, d in self.graph.in_edges(op_id, data=True)))
                if node.operator.kind == OpKind.CONNECTOR_SINK:
                    continue  # side effects: two sinks are two sinks
                if node.operator.kind == OpKind.CONNECTOR_SOURCE:
                    # two scans of the same DETERMINISTIC table (q8 reads
                    # nexmark twice: persons side + auctions side) merge
                    # into one generation pass; projections union.
                    # Consumption-stateful connectors (kafka offsets,
                    # consumer groups) stay excluded — merging would
                    # change their delivery semantics.
                    spec = node.operator.spec
                    if getattr(spec, "connector", None) \
                            not in self._REPLAYABLE_SOURCES:
                        continue
                    cfg = {k: v for k, v in spec.config.items()
                           if k != "projection"}
                    sig = ("src", spec.connector,
                           repr(sorted(cfg.items(), key=lambda kv: kv[0])),
                           node.parallelism, node.max_parallelism)
                else:
                    sig = (node.operator.hash_token(), node.parallelism,
                           node.max_parallelism, preds)
                keep = by_sig.get(sig)
                if keep is None:
                    by_sig[sig] = op_id
                    continue
                # expression tokens without a structural sql form are just
                # display names ("map"): equality proves nothing about the
                # wrapped fn, so only merge when the fns are literally the
                # same object (Stream-API callers need not discipline
                # their names for the pass to stay sound)
                expr = node.operator.expr
                if expr is not None and not expr.sql:
                    kept_expr = self.node(keep).operator.expr
                    if kept_expr is None or kept_expr.fn is not expr.fn:
                        continue
                # candidate duplicate: every out-edge must be movable
                outs = list(self.graph.out_edges(op_id, data=True))
                if any(self.graph.has_edge(keep, dst) for _, dst, _ in outs):
                    continue
                if node.operator.kind == OpKind.CONNECTOR_SOURCE:
                    kcfg = self.node(keep).operator.spec.config
                    pa = kcfg.get("projection")
                    pb = node.operator.spec.config.get("projection")
                    if pa and pb:  # both pruned: keep the union
                        kcfg["projection"] = sorted(set(pa) | set(pb))
                    else:  # either side needs every column
                        kcfg.pop("projection", None)
                for _, dst, data in outs:
                    self.graph.add_edge(keep, dst, **data)
                self.graph.remove_node(op_id)
                removed += 1
                changed = True
                break  # graph changed: recompute signatures
        return removed

    def subplan_equal(self, a: str, b: str) -> bool:
        """True when the subplans ending at ``a`` and ``b`` provably
        compute the same stream: identical structural tokens and
        identical (recursively equal) inputs.  Shared nodes short-
        circuit, so chains diverging off a common CTE compare in O(tail).
        Used by the argmax fusion to prove a self-join's two sides are
        the same aggregate; false negatives only cost the optimization."""
        if a == b:
            return True
        na, nb = self.node(a), self.node(b)
        if (na.operator.hash_token() != nb.operator.hash_token()
                or na.parallelism != nb.parallelism):
            return False
        if na.operator.kind == OpKind.CONNECTOR_SOURCE:
            # two DISTINCT scans are "the same stream" only for
            # deterministic replayable sources — kafka/sse scans are
            # independent consumers whose reads diverge even at equal
            # config (same policy as eliminate_common_subplans)
            if getattr(na.operator.spec, "connector", None) \
                    not in self._REPLAYABLE_SOURCES:
                return False
        ea_, eb_ = (na.operator.expr, nb.operator.expr)
        if ea_ is not None and not ea_.sql and ea_.fn is not (
                eb_.fn if eb_ is not None else None):
            return False  # name-only expr tokens prove nothing about fns
        key = lambda e: (e[2]["edge"].typ.value, e[2]["edge"].key_schema)
        pa = sorted(self.graph.in_edges(a, data=True), key=key)
        pb = sorted(self.graph.in_edges(b, data=True), key=key)
        if len(pa) != len(pb) or [key(e) for e in pa] != [key(e) for e in pb]:
            return False
        return all(self.subplan_equal(sa, sb)
                   for (sa, _, _), (sb, _, _) in zip(pa, pb))

    def prune_dead(self) -> int:
        """Remove operators whose output reaches no sink (subplans the
        optimizer bypassed, e.g. the pruned max side of an argmax
        fusion).  Returns the number of nodes removed."""
        removed = 0
        changed = True
        while changed:
            changed = False
            for nid in list(self.graph.nodes):
                if self.node(nid).operator.kind == OpKind.CONNECTOR_SINK:
                    continue
                if self.graph.out_degree(nid) == 0:
                    self.graph.remove_node(nid)
                    removed += 1
                    changed = True
        return removed

    # -- hashing (lib.rs:1140-1154) ---------------------------------------

    def get_hash(self) -> str:
        h = hashlib.sha256()
        for op_id in self.topo_order():
            node = self.node(op_id)
            h.update(node.operator.hash_token().encode())
            h.update(str(node.parallelism).encode())
            for _, dst, data in self.graph.out_edges(op_id, data=True):
                e: StreamEdge = data["edge"]
                h.update(f"{dst}:{e.typ.value}:{e.key_schema}:{e.value_schema}".encode())
        return h.hexdigest()[:16]

    # -- display -----------------------------------------------------------

    def dot(self) -> str:
        lines = ["digraph program {"]
        for op_id in self.graph.nodes:
            n = self.node(op_id)
            lines.append(f'  "{op_id}" [label="{n.operator.name} (p={n.parallelism})"];')
        for s, d, data in self.graph.edges(data=True):
            lines.append(f'  "{s}" -> "{d}" [label="{data["edge"].typ.value}"];')
        lines.append("}")
        return "\n".join(lines)

    def update_parallelism(self, overrides: Dict[str, int]) -> None:
        """Rescaling entry point (states/mod.rs:203-211)."""
        for op_id, p in overrides.items():
            node = self.node(op_id)
            if node.max_parallelism is not None:
                p = min(p, node.max_parallelism)
            node.parallelism = p


# ---------------------------------------------------------------------------
# Fluent builder (Stream<T>/KeyedStream<K,T>, lib.rs:559-986)
# ---------------------------------------------------------------------------


class Stream:
    """Fluent pipeline builder over a Program.

    ``Stream.source(...).map(...).key_by(...).window(...).sink(...)``
    """

    def __init__(self, program: Program, tail: str, keyed: Tuple[str, ...] = ()):
        self.program = program
        self.tail = tail
        self.keyed = keyed

    # -- sources -----------------------------------------------------------

    @staticmethod
    def source(connector: str, config: Optional[Dict[str, Any]] = None,
               parallelism: int = 1, program: Optional[Program] = None,
               name: Optional[str] = None) -> "Stream":
        from ..connectors.registry import get_connector, validate_config

        meta = get_connector(connector)
        if not meta.supports_source:
            raise ValueError(f"connector {connector!r} does not support sources")
        cfg = validate_config(connector, config or {})
        p = program or Program()
        op = LogicalOperator(
            OpKind.CONNECTOR_SOURCE,
            name or f"{connector}_source",
            spec=ConnectorOpSpec(connector, cfg),
        )
        return Stream(p, p.add_node(op, parallelism))

    # -- plumbing ----------------------------------------------------------

    def _chain(self, op: LogicalOperator, parallelism: Optional[int] = None,
               edge: EdgeType = EdgeType.FORWARD,
               keyed: Optional[Tuple[str, ...]] = None) -> "Stream":
        par = parallelism if parallelism is not None else self.program.node(self.tail).parallelism
        nid = self.program.add_node(op, par)
        key_schema = ",".join(self.keyed) if self.keyed else "()"
        self.program.add_edge(self.tail, nid, edge, key_schema=key_schema)
        return Stream(self.program, nid, self.keyed if keyed is None else keyed)

    # -- element-wise ------------------------------------------------------

    def map(self, fn: Callable, name: str = "map",
            sql: str = "", output_schema: Optional[Dict[str, Any]] = None
            ) -> "Stream":
        # output_schema ({col -> kind char}) is optional metadata the
        # SQL planner attaches from its compile-time schema so plan-time
        # analyses (shardcheck's sticky string-column checks) can see
        # through projections; execution never reads it
        expr = ColumnExpr(name, fn, ExprReturnType.RECORD, output_schema,
                          sql=sql)
        return self._chain(LogicalOperator(OpKind.EXPRESSION, name, expr=expr))

    def filter(self, fn: Callable, name: str = "filter") -> "Stream":
        expr = ColumnExpr(name, fn, ExprReturnType.PREDICATE)
        return self._chain(LogicalOperator(OpKind.EXPRESSION, name, expr=expr))

    def option_map(self, fn: Callable, name: str = "option_map") -> "Stream":
        expr = ColumnExpr(name, fn, ExprReturnType.OPTIONAL_RECORD)
        return self._chain(LogicalOperator(OpKind.EXPRESSION, name, expr=expr))

    def flat_map(self, fn: Callable, name: str = "flat_map") -> "Stream":
        expr = ColumnExpr(name, fn, ExprReturnType.RECORD)
        return self._chain(LogicalOperator(OpKind.FLAT_MAP, name, expr=expr))

    def flatten(self, name: str = "flatten") -> "Stream":
        return self._chain(LogicalOperator(OpKind.FLATTEN, name))

    def udf(self, fn: Callable, name: str = "udf",
            sql: str = "", output_schema: Optional[Dict[str, Any]] = None
            ) -> "Stream":
        expr = ColumnExpr(name, fn, ExprReturnType.RECORD, output_schema,
                          sql=sql)
        return self._chain(LogicalOperator(OpKind.UDF, name, expr=expr))

    # -- time --------------------------------------------------------------

    def watermark(self, max_lateness_micros: int = 0,
                  idle_time_micros: Optional[int] = None,
                  expression: Optional[Callable] = None,
                  name: str = "watermark") -> "Stream":
        expr = None
        if expression is not None:
            expr = ColumnExpr(f"{name}_expr", expression, ExprReturnType.RECORD)
        spec = PeriodicWatermarkSpec(max_lateness_micros, idle_time_micros, expr)
        return self._chain(LogicalOperator(OpKind.WATERMARK, name, spec=spec))

    # -- keying ------------------------------------------------------------

    def key_by(self, *cols: str, name: str = "key_by") -> "Stream":
        op = LogicalOperator(OpKind.KEY_BY, name, key_cols=tuple(cols))
        return self._chain(op, keyed=tuple(cols))

    def global_key(self, name: str = "global_key") -> "Stream":
        op = LogicalOperator(OpKind.GLOBAL_KEY, name)
        return self._chain(op, keyed=("__global",))

    # -- windows / aggregates (keyed) -------------------------------------

    def window(self, typ: WindowType, aggs: Sequence[AggSpec] = (),
               flatten: bool = False, projection: Optional[Callable] = None,
               name: Optional[str] = None, parallelism: Optional[int] = None) -> "Stream":
        proj = ColumnExpr(f"{name or 'window'}_proj", projection) if projection else None
        spec = WindowSpec(typ, tuple(aggs), flatten, proj)
        op = LogicalOperator(OpKind.WINDOW, name or f"window_{window_label(typ)}", spec=spec)
        return self._chain(op, parallelism, EdgeType.SHUFFLE)

    def sliding_aggregate(self, width_micros: int, slide_micros: int,
                          aggs: Sequence[AggSpec],
                          projection: Optional[Callable] = None,
                          name: str = "sliding_agg",
                          parallelism: Optional[int] = None) -> "Stream":
        proj = ColumnExpr(f"{name}_proj", projection) if projection else None
        spec = SlidingAggregatorSpec(width_micros, slide_micros, tuple(aggs), proj)
        op = LogicalOperator(OpKind.SLIDING_WINDOW_AGGREGATOR, name, spec=spec)
        return self._chain(op, parallelism, EdgeType.SHUFFLE)

    def tumbling_aggregate(self, width_micros: int, aggs: Sequence[AggSpec],
                           projection: Optional[Callable] = None,
                           name: str = "tumbling_agg",
                           parallelism: Optional[int] = None) -> "Stream":
        proj = ColumnExpr(f"{name}_proj", projection) if projection else None
        spec = TumblingAggregatorSpec(width_micros, tuple(aggs), proj)
        op = LogicalOperator(OpKind.TUMBLING_WINDOW_AGGREGATOR, name, spec=spec)
        return self._chain(op, parallelism, EdgeType.SHUFFLE)

    def tumbling_top_n(self, width_micros: int, max_elements: int, sort_column: str,
                       partition_cols: Sequence[str] = (),
                       projection: Optional[Callable] = None,
                       name: str = "tumbling_top_n",
                       parallelism: Optional[int] = None) -> "Stream":
        proj = ColumnExpr(f"{name}_proj", projection) if projection else None
        spec = TopNSpec(width_micros, max_elements, sort_column, tuple(partition_cols), proj)
        op = LogicalOperator(OpKind.TUMBLING_TOP_N, name, spec=spec)
        return self._chain(op, parallelism, EdgeType.SHUFFLE)

    def sliding_aggregating_top_n(self, width_micros: int, slide_micros: int,
                                  aggs: Sequence[AggSpec], partition_cols: Sequence[str],
                                  sort_column: str, max_elements: int,
                                  projection: Optional[Callable] = None,
                                  name: str = "sliding_topn",
                                  parallelism: Optional[int] = None) -> "Stream":
        proj = ColumnExpr(f"{name}_proj", projection) if projection else None
        spec = SlidingAggregatingTopNSpec(
            width_micros, slide_micros, tuple(aggs), tuple(partition_cols),
            sort_column, max_elements, proj)
        op = LogicalOperator(OpKind.SLIDING_AGGREGATING_TOP_N, name, spec=spec)
        return self._chain(op, parallelism, EdgeType.SHUFFLE)

    def count(self, name: str = "count") -> "Stream":
        return self._chain(LogicalOperator(OpKind.COUNT, name), edge=EdgeType.SHUFFLE)

    def aggregate(self, agg: AggSpec, name: str = "aggregate") -> "Stream":
        op = LogicalOperator(OpKind.AGGREGATE, name, spec=agg)
        return self._chain(op, edge=EdgeType.SHUFFLE)

    def non_window_aggregate(self, expiration_micros: int, aggs: Sequence[AggSpec],
                             projection: Optional[Callable] = None,
                             name: str = "updating_agg",
                             flush_key: Optional[str] = None) -> "Stream":
        proj = ColumnExpr(f"{name}_proj", projection) if projection else None
        spec = NonWindowAggregatorSpec(expiration_micros, tuple(aggs), proj,
                                       flush_key)
        op = LogicalOperator(OpKind.NON_WINDOW_AGGREGATOR, name, spec=spec)
        return self._chain(op, edge=EdgeType.SHUFFLE)

    # -- joins -------------------------------------------------------------

    def window_join(self, other: "Stream", window: WindowType,
                    join_type: JoinType = JoinType.INNER,
                    left_cols: Tuple[Tuple[str, str], ...] = (),
                    right_cols: Tuple[Tuple[str, str], ...] = (),
                    name: str = "window_join",
                    parallelism: Optional[int] = None) -> "Stream":
        assert self.program is other.program, "join streams must share a Program"
        spec = WindowJoinSpec(window, join_type, tuple(left_cols),
                              tuple(right_cols))
        op = LogicalOperator(OpKind.WINDOW_JOIN, name, spec=spec)
        par = parallelism or self.program.node(self.tail).parallelism
        nid = self.program.add_node(op, par)
        ks = ",".join(self.keyed) if self.keyed else "()"
        self.program.add_edge(self.tail, nid, EdgeType.SHUFFLE_JOIN_LEFT, key_schema=ks)
        self.program.add_edge(other.tail, nid, EdgeType.SHUFFLE_JOIN_RIGHT, key_schema=ks)
        return Stream(self.program, nid, self.keyed)

    def multi_way_join(self, others: Sequence["Stream"],
                       typ: Optional[WindowType] = None,
                       ttl_micros: int = 0,
                       side_cols: Tuple[Tuple[Tuple[str, str], ...], ...] = (),
                       name: str = "multi_way_join",
                       parallelism: Optional[int] = None) -> "Stream":
        """N-ary INNER equi-join over sides keyed by the same columns
        (``self`` is side 0).  See :class:`MultiWayJoinSpec`."""
        sides = [self] + list(others)
        assert 2 <= len(sides) <= 8, "multi-way join supports 2..8 sides"
        assert len({s.tail for s in sides}) == len(sides), \
            "multi-way join sides must be distinct nodes (a DiGraph " \
            "would collapse duplicate edges)"
        for o in sides[1:]:
            assert self.program is o.program, \
                "join streams must share a Program"
        # side_cols doubles as the side-count record the physical builder
        # and plan validator read — synthesize empty per-side specs when
        # the caller has none (Stream-API inner joins need no pads)
        if not side_cols:
            side_cols = tuple(() for _ in sides)
        assert len(side_cols) == len(sides), \
            "side_cols must have one entry per join side"
        spec = MultiWayJoinSpec(typ, ttl_micros, tuple(side_cols))
        op = LogicalOperator(OpKind.MULTI_WAY_JOIN, name, spec=spec)
        par = parallelism or self.program.node(self.tail).parallelism
        nid = self.program.add_node(op, par)
        ks = ",".join(self.keyed) if self.keyed else "()"
        for i, s in enumerate(sides):
            self.program.add_edge(s.tail, nid, join_side_edge(i),
                                  key_schema=ks)
        return Stream(self.program, nid, self.keyed)

    def window_argmax(self, value_col: str, minmax: str,
                      synth_cols: Tuple[Tuple[str, str], ...],
                      width_micros: int,
                      name: str = "window_argmax",
                      parallelism: Optional[int] = None,
                      agg_out: str = "", raw: bool = False,
                      late_ttl_micros: int = 0) -> "Stream":
        """Per-window argmax/argmin filter (see WindowArgmaxSpec).  The
        stream must be keyed by the window column so every row of one
        window lands on one subtask — the filter is then global."""
        spec = WindowArgmaxSpec(value_col, minmax, tuple(synth_cols),
                                width_micros, agg_out, raw, late_ttl_micros)
        op = LogicalOperator(OpKind.WINDOW_ARGMAX, name, spec=spec)
        return self._chain(op, parallelism, EdgeType.SHUFFLE)

    def join_with_expiration(self, other: "Stream", left_expiration_micros: int,
                             right_expiration_micros: int,
                             join_type: JoinType = JoinType.INNER,
                             left_cols: Tuple[Tuple[str, str], ...] = (),
                             right_cols: Tuple[Tuple[str, str], ...] = (),
                             name: str = "join", parallelism: Optional[int] = None) -> "Stream":
        assert self.program is other.program
        spec = JoinWithExpirationSpec(left_expiration_micros,
                                      right_expiration_micros, join_type,
                                      tuple(left_cols), tuple(right_cols))
        op = LogicalOperator(OpKind.JOIN_WITH_EXPIRATION, name, spec=spec)
        par = parallelism or self.program.node(self.tail).parallelism
        nid = self.program.add_node(op, par)
        ks = ",".join(self.keyed) if self.keyed else "()"
        self.program.add_edge(self.tail, nid, EdgeType.SHUFFLE_JOIN_LEFT, key_schema=ks)
        self.program.add_edge(other.tail, nid, EdgeType.SHUFFLE_JOIN_RIGHT, key_schema=ks)
        return Stream(self.program, nid, self.keyed)

    def union(self, other: "Stream", name: str = "union",
              parallelism: Optional[int] = None) -> "Stream":
        """Merge two streams (UNION ALL): batches from both flow through
        unchanged; the watermark is the min across inputs (WatermarkHolder
        semantics).  The reference has no union support
        (arroyo-sql/src/pipeline.rs:393)."""
        assert self.program is other.program, "union streams must share a Program"
        if other.tail == self.tail:
            # self-union: nx.DiGraph would collapse the duplicate (src,
            # dst) edge and silently drop the duplication — route one side
            # through a pass-through node
            dup = LogicalOperator(OpKind.UNION, f"{name}_dup")
            dup_id = self.program.add_node(
                dup, self.program.node(other.tail).parallelism)
            self.program.add_edge(other.tail, dup_id, EdgeType.FORWARD,
                                  key_schema="()")
            other = Stream(self.program, dup_id, None)
        op = LogicalOperator(OpKind.UNION, name)
        par = parallelism or self.program.node(self.tail).parallelism
        nid = self.program.add_node(op, par)
        self.program.add_edge(self.tail, nid, EdgeType.SHUFFLE,
                              key_schema="()")
        self.program.add_edge(other.tail, nid, EdgeType.SHUFFLE,
                              key_schema="()")
        return Stream(self.program, nid, None)

    # -- updating ----------------------------------------------------------

    def updating(self, fn: Callable, name: str = "updating") -> "Stream":
        expr = ColumnExpr(name, fn, ExprReturnType.OPTIONAL_RECORD)
        return self._chain(LogicalOperator(OpKind.UPDATING, name, expr=expr))

    def updating_key(self, *cols: str, name: str = "updating_key") -> "Stream":
        op = LogicalOperator(OpKind.UPDATING_KEY, name, key_cols=tuple(cols))
        return self._chain(op, keyed=tuple(cols))

    # -- sinks -------------------------------------------------------------

    def sink(self, connector: str, config: Optional[Dict[str, Any]] = None,
             parallelism: Optional[int] = None, name: Optional[str] = None,
             max_parallelism: Optional[int] = None) -> Program:
        from ..connectors.registry import get_connector, validate_config

        meta = get_connector(connector)
        if not meta.supports_sink:
            raise ValueError(f"connector {connector!r} does not support sinks")
        cfg = validate_config(connector, config or {})
        op = LogicalOperator(
            OpKind.CONNECTOR_SINK,
            name or f"{connector}_sink",
            spec=ConnectorOpSpec(connector, cfg),
        )
        tail = self._chain(op, parallelism)
        if max_parallelism is not None:
            # sinks that must stay single-writer (e.g. single_file) pin
            # here so rescales can never fan them out
            self.program.node(tail.tail).max_parallelism = max_parallelism
        return self.program
