"""Translator + executor (paper §4): physical plan → operator tree.

The translator decides, per operator, whether to instantiate the BARQ
(batch) or legacy (row) implementation, inserting batch↔row adapters at
engine boundaries (§4.2 Interoperability). Selection policy mirrors §4.2:

  * engine='barq'   — all-BARQ tree (every operator here has a batch impl);
  * engine='legacy' — all-row tree (the baseline of §5);
  * engine='mixed'  — BARQ for scans/joins/filters (the operators the paper
    vectorized first), row implementations for aggregation/sort/distinct,
    with adapters in between — demonstrating the gradual-migration path.

``Engine`` is the public entry point: parse/encode → optimize → translate →
execute → decode (the pipeline of Fig. 2).
"""

from __future__ import annotations

import dataclasses
import os
import time
from typing import Dict, List, Optional, Sequence, Tuple, Union

import numpy as np

from repro.core import algebra as A
from repro.core import telemetry
from repro.core import planner as PL
from repro.core.adaptive import AdaptiveBatchSizer
from repro.core.batch import NULL_ID, BatchPool, bucket_for
from repro.core.dictionary import Dictionary
from repro.core.legacy import operators as LOP
from repro.core.operators.adapters import BatchToRow, RowToBatch
from repro.core.operators.aggregate import (
    PartitionedDistinct,
    PartitionedGroupBy,
    SortDistinct,
    SortGroupBy,
    StreamingDistinct,
    StreamingGroupBy,
)
from repro.core.operators.base import BatchOperator, close_tree
from repro.core.operators.cross import CrossJoin
from repro.core.operators.lookup_join import LookupJoin
from repro.core.operators.merge_join import MergeJoin
from repro.core.operators.scan import IndexScan
from repro.core.operators.simple import (
    _UNSET as _UNSET_PROG,
    ExtendOp,
    FilterOp,
    ProjectOp,
    SliceOp,
    UnionOp,
)
from repro.core.operators.sort import OrderByOp, SortByVarOp
from repro.core.profiler import profile_tree
from repro.core.sip import SipFilter
from repro.core.stats import GraphStats
from repro.core.storage import QuadStore

AnyOp = Union[BatchOperator, LOP.RowOperator]


def _make_pool(cfg: EngineConfig) -> BatchPool:
    """The engine's buffer arena; under ``cfg.sanitize`` a shadow-tracked
    one that poisons releases and attributes leaks (DESIGN.md §16)."""
    if cfg.sanitize:
        from repro.analysis.sanitize import SanitizingBatchPool

        return SanitizingBatchPool(cfg.pool_max_per_bucket)
    return BatchPool(cfg.pool_max_per_bucket)


def _planner_program(p):
    """Planner program marker -> operator argument: None means the plan
    never went through a dictionary-aware planner (operators try one lazy
    compile); False means the planner already found the expression
    uncompilable (operators use the tree walk, no retry)."""
    if p is None:
        return _UNSET_PROG
    return p or None


@dataclasses.dataclass
class EngineConfig:
    engine: str = "barq"  # barq | legacy | mixed
    adaptive_batching: bool = True
    initial_batch: int = 64
    max_batch: int = 4096
    allow_child_skip: bool = True
    spill_dir: Optional[str] = None
    # join emission batch size: None = default (256); fixed-batch ablations
    # (bench_adaptive) set it so the joins follow the experiment too
    join_initial_batch: Optional[int] = None
    # binary-join physical strategy: None = cost-based (DESIGN.md §11),
    # "hash" / "merge" force one path (parity tests, ablations)
    join_strategy: Optional[str] = None
    # sideways information passing (DESIGN.md §12): None = cost-gated,
    # "on" = push prefilters wherever sound, "off" = disabled
    sip: Optional[str] = None
    # kernel backend for the bloom summaries (None = the platform's data
    # plane, kernels.ops.default_backend)
    sip_backend: Optional[str] = None
    # buffer pooling (DESIGN.md §2.3): recycle batch buffers through an
    # Engine-owned arena so steady-state execution is allocation-free and
    # repeated queries start warm
    pool_buffers: bool = True
    pool_max_per_bucket: int = 32
    # query telemetry (DESIGN.md §13): record a QueryTrace per execution
    # (spans + scoped kernel ledger + operator lane). Cheap enough to be
    # on by default; False skips trace creation entirely
    telemetry: bool = True
    # cardinality feedback (DESIGN.md §14): "off" = no history, "observe" =
    # record per-node actuals into the feedback store without touching
    # plans, "apply" = planner overrides estimates with observed history
    # (repeated misestimated queries re-plan with real cardinalities)
    cardinality_feedback: str = "off"
    # out-of-core execution (DESIGN.md §15): bytes of operator state a
    # pipeline breaker may keep resident. None = unlimited (pre-§15
    # behavior, plans byte-identical); set it and hash joins over budget
    # go grace (partition + spill to spill_dir), group-by/distinct run
    # partitioned.
    memory_budget: Optional[int] = None
    # mid-plan re-strategy (DESIGN.md §15): "on" defers order-insensitive
    # merge joins' sort-vs-hash choice to runtime (post-drain misestimate
    # check); "off" keeps the planner's static pick
    adaptive_join: str = "off"
    # correctness tooling (DESIGN.md §16). verify_plans runs the
    # PlanVerifier's structural invariant checks on every planned query;
    # sanitize wraps the buffer arena in shadow ownership tracking
    # (poisoned releases, use-after-release / double-release / leak
    # detection). Both default from the environment so CI can run the
    # whole suite hardened without touching call sites.
    verify_plans: bool = dataclasses.field(
        default_factory=lambda: os.environ.get("BARQ_VERIFY_PLANS", "") == "1"
    )
    sanitize: bool = dataclasses.field(
        default_factory=lambda: os.environ.get("BARQ_SANITIZE", "") == "1"
    )


class Translator:
    def __init__(self, store: QuadStore, cfg: EngineConfig,
                 pool: Optional[BatchPool] = None):
        self.store = store
        self.cfg = cfg
        # ``pool`` lets an Engine share one warm arena across queries;
        # standalone Translators keep making their own
        self.pool: Optional[BatchPool] = None
        if cfg.pool_buffers and cfg.engine != "legacy":
            self.pool = pool if pool is not None else _make_pool(cfg)
        # SIP runtime handles, keyed by annotation sid: consuming leaves
        # and exporting joins resolve to the same SipFilter object. Fresh
        # per Translator, so a plan reused through the server's plan cache
        # never sees stale summaries.
        self._sip_registry: Dict[int, SipFilter] = {}

    def _sip_filter(self, ann: "PL.PSipFilter") -> SipFilter:
        sf = self._sip_registry.get(ann.sid)
        if sf is None:
            sf = SipFilter(ann.var, sid=ann.sid, backend=self.cfg.sip_backend)
            self._sip_registry[ann.sid] = sf
        return sf

    # -- entry ------------------------------------------------------------------

    def translate(self, plan: PL.Phys) -> AnyOp:
        if self.cfg.engine == "legacy":
            return self._row(plan)
        op = self._build(plan)
        return op

    def _sizer(self, initial: Optional[int] = None) -> AdaptiveBatchSizer:
        # clamp the configured size to the compiled capacity buckets so
        # every operator's requests stay on the static-shape grid
        return AdaptiveBatchSizer(
            initial=min(
                bucket_for(initial or self.cfg.initial_batch),
                bucket_for(self.cfg.max_batch),
            ),
            max_size=self.cfg.max_batch,
            enabled=self.cfg.adaptive_batching,
        )

    def _join_sizer(self) -> AdaptiveBatchSizer:
        return self._sizer(self.cfg.join_initial_batch or 256)

    # -- engine-aware build (barq / mixed) ---------------------------------------------

    def _build(self, n: PL.Phys) -> AnyOp:
        """Lower one Phys node, stamping the planner's cardinality estimate
        (+ its source) and node fingerprint onto the produced operator's
        stats (EXPLAIN ANALYZE / feedback-recording input)."""
        op = self._build_node(n)
        est = getattr(n, "est_rows", 0.0)
        if est and op.stats.est_rows is None:
            op.stats.est_rows = float(est)
            op.stats.est_source = getattr(n, "est_source", "stats")
        if op.stats.node_fp is None:
            op.stats.node_fp = getattr(n, "fp", "") or None
        return op

    def _build_node(self, n: PL.Phys) -> AnyOp:
        mixed = self.cfg.engine == "mixed"
        if isinstance(n, PL.PScan):
            return IndexScan(
                self.store, n.pattern, n.sort_var, sizer=self._sizer(),
                pool=self.pool,
                sip_filters=[self._sip_filter(a) for a in n.sip],
            )
        if isinstance(n, PL.PPathExpand):
            # vectorized frontier engine (DESIGN.md §8): paths run on the
            # batch pipeline like every other leaf
            from repro.core.operators.path import PathExpand

            return PathExpand(
                self.store, n.pattern.expr, n.pattern.s, n.pattern.o,
                batch_size=self.cfg.max_batch, pool=self.pool,
                sip_filters=[self._sip_filter(a) for a in n.sip],
            )
        if isinstance(n, PL.PPathScan):
            # pre-§8 physical plans: row-based `+` bridged via adapter
            return RowToBatch(self._path_op(n), self.cfg.max_batch, pool=self.pool)
        if isinstance(n, PL.PSort):
            child = self._build(n.child)
            if mixed:
                # row-based sort consuming (possibly) batch input: adapter in
                # between, then back to batches at the pipeline break (§4.2)
                row_child = self._to_row(child)
                return RowToBatch(
                    LOP.RowSort(row_child, var=n.var), self.cfg.max_batch,
                    pool=self.pool,
                )
            return SortByVarOp(
                self._to_batch(child), n.var, self.cfg.max_batch, pool=self.pool
            )
        if isinstance(n, PL.PMergeJoin):
            if (
                self.cfg.adaptive_join == "on"
                and n.adaptive_ok
                and not n.sip_exports
                and isinstance(n.right, PL.PSort)
                and n.right.var == n.var
            ):
                # mid-plan re-strategy (DESIGN.md §15): the planned Sort is
                # a pipeline breaker, so defer sort-vs-hash until the build
                # input's true cardinality is known. Only sound when no
                # ancestor consumes this join's order (adaptive_ok) and no
                # SIP export hangs off the build window.
                from repro.core.operators.adaptive_join import AdaptiveMergeJoin

                return AdaptiveMergeJoin(
                    self._to_batch(self._build(n.left)),
                    self._to_batch(self._build(n.right.child)),
                    n.var,
                    mode=n.mode,
                    post_filter=n.post_filter,
                    dictionary=self.store.dict,
                    post_program=n.post_program,
                    pool=self.pool,
                    spill_dir=self.cfg.spill_dir,
                    est_build=getattr(n.right, "est_rows", 0.0) or 0.0,
                    memory_budget=self.cfg.memory_budget,
                )
            left = self._to_batch(self._build(n.left))
            right = self._to_batch(self._build(n.right))
            # SIP export (DESIGN.md §12): the build window summarizes as a
            # full bloom off a Sort's materialization, or a free O(1) code
            # range off a sorted scan; anything else stays pass-through
            for ann in n.sip_exports:
                sf = self._sip_filter(ann)
                if isinstance(right, SortByVarOp):
                    sf.bind(lambda r=right, v=ann.var: ("keys", r.sip_keys(v)))
                elif isinstance(right, IndexScan) and right.sorted_by() == ann.var:
                    sf.bind(lambda r=right: ("range",) + r.sip_code_range())
            return MergeJoin(
                left,
                right,
                n.var,
                mode=n.mode,
                post_filter=n.post_filter,
                dictionary=self.store.dict,
                sizer=self._join_sizer(),  # honors EngineConfig.join_initial_batch
                spill_dir=self.cfg.spill_dir,
                allow_child_skip=self.cfg.allow_child_skip,
                pool=self.pool,
                post_program=n.post_program,
            )
        if isinstance(n, PL.PLookupJoin):
            probe = self._to_batch(self._build(n.probe))
            build = self._to_batch(self._build(n.build))
            return LookupJoin(probe, build, n.var, n.mode, pool=self.pool)
        if isinstance(n, PL.PHashJoin):
            from repro.core.operators.hash_join import HashJoin

            op = HashJoin(
                self._to_batch(self._build(n.probe)),
                self._to_batch(self._build(n.build)),
                n.keys,
                mode=n.mode,
                post_filter=n.post_filter,
                dictionary=self.store.dict,
                sizer=self._join_sizer(),
                pool=self.pool,
                post_program=n.post_program,
                memory_budget=self.cfg.memory_budget,
                spill_dir=self.cfg.spill_dir,
                grace=True if n.grace else None,
                grace_parts=n.grace_parts,
            )
            # SIP export: reuse the materialized build layout as bloom keys
            for ann in n.sip_exports:
                self._sip_filter(ann).bind(
                    lambda j=op, v=ann.var: ("keys", j.sip_keys(v))
                )
            return op
        if isinstance(n, PL.PCross):
            return CrossJoin(
                self._to_batch(self._build(n.left)),
                self._to_batch(self._build(n.right)),
                pool=self.pool,
            )
        if isinstance(n, PL.PFilter):
            return FilterOp(
                self._to_batch(self._build(n.child)), n.expr, self.store.dict,
                program=_planner_program(n.program),
            )
        if isinstance(n, PL.PExtend):
            return ExtendOp(
                self._to_batch(self._build(n.child)), n.var, n.expr,
                self.store.dict, pool=self.pool,
                program=_planner_program(n.program),
            )
        if isinstance(n, PL.PProject):
            child = self._build(n.child)
            if isinstance(child, LOP.RowOperator):
                return LOP.RowProject(child, n.vars)
            return ProjectOp(child, n.vars, pool=self.pool)
        if isinstance(n, PL.PDistinct):
            child = self._build(n.child)
            if mixed:
                return LOP.RowDistinct(self._to_row(child))
            bchild = self._to_batch(child)
            if n.streaming_var is not None and bchild.sorted_by() == n.streaming_var:
                return StreamingDistinct(bchild, n.streaming_var)
            if n.grace:
                return PartitionedDistinct(
                    bchild, self.cfg.max_batch, pool=self.pool,
                    memory_budget=self.cfg.memory_budget,
                    spill_dir=self.cfg.spill_dir,
                    n_parts=n.grace_parts or 16,
                )
            return SortDistinct(bchild, self.cfg.max_batch)
        if isinstance(n, PL.PGroup):
            child = self._build(n.child)
            # weighted grouping (DESIGN.md §17) has no row operator
            if mixed and not PL.is_weighted_group(n):
                return LOP.RowGroupBy(
                    self._to_row(child), n.group_vars, n.aggs, self.store.dict
                )
            bchild = self._to_batch(child)
            if n.streaming and len(n.group_vars) <= 1:
                gv = n.group_vars[0] if n.group_vars else None
                if gv is None or bchild.sorted_by() == gv:
                    return StreamingGroupBy(
                        bchild, gv, n.aggs, self.store.dict,
                        self.cfg.max_batch, pool=self.pool, weight=n.weight,
                    )
            if n.grace and n.group_vars:
                return PartitionedGroupBy(
                    bchild, n.group_vars, n.aggs, self.store.dict,
                    self.cfg.max_batch, pool=self.pool,
                    memory_budget=self.cfg.memory_budget,
                    spill_dir=self.cfg.spill_dir,
                    n_parts=n.grace_parts or 16, weight=n.weight,
                )
            return SortGroupBy(
                bchild, n.group_vars, n.aggs, self.store.dict,
                self.cfg.max_batch, pool=self.pool, weight=n.weight,
            )
        if isinstance(n, PL.PHaving):
            # HAVING: expression-VM filter over the aggregate output
            child = self._build(n.child)
            if isinstance(child, LOP.RowOperator):  # mixed: row grouping
                return LOP.RowFilter(child, n.expr, self.store.dict)
            return FilterOp(
                self._to_batch(child), n.expr, self.store.dict,
                program=_planner_program(n.program), name="Having",
            )
        if isinstance(n, PL.POrderBy):
            child = self._build(n.child)
            if mixed:
                return RowToBatch(
                    LOP.RowSort(
                        self._to_row(child), keys=n.keys, dictionary=self.store.dict
                    ),
                    self.cfg.max_batch,
                    pool=self.pool,
                )
            return OrderByOp(
                self._to_batch(child), n.keys, self.store.dict,
                self.cfg.max_batch, pool=self.pool,
            )
        if isinstance(n, PL.PSlice):
            child = self._build(n.child)
            if isinstance(child, LOP.RowOperator):
                return LOP.RowLimit(child, n.limit, n.offset)
            return SliceOp(child, n.limit, n.offset)
        if isinstance(n, PL.PUnion):
            return UnionOp(
                self._to_batch(self._build(n.left)),
                self._to_batch(self._build(n.right)),
                pool=self.pool,
            )
        raise TypeError(type(n))

    # -- adapters ------------------------------------------------------------------

    def _to_batch(self, op: AnyOp) -> BatchOperator:
        if isinstance(op, BatchOperator):
            return op
        return RowToBatch(op, self.cfg.max_batch, pool=self.pool)

    def _to_row(self, op: AnyOp) -> LOP.RowOperator:
        if isinstance(op, LOP.RowOperator):
            return op
        return BatchToRow(op)

    def _path_op(self, n: "PL.PPathScan") -> LOP.RowOperator:
        from repro.core.algebra import V
        from repro.core.legacy.property_path import RowTransitivePath

        pat = n.pattern
        if not isinstance(pat.p, A.K):
            raise ValueError(
                "property paths require a constant predicate, got a "
                "variable in the predicate position"
            )
        assert isinstance(pat.s, V) and isinstance(pat.o, V), (
            "bound-endpoint paths are planned as filters over the closure"
        )
        return RowTransitivePath(self.store, pat.p.term, pat.s.id, pat.o.id)

    # -- all-row build (legacy engine, §5 baseline) -----------------------------------------

    def _row(self, n: PL.Phys) -> LOP.RowOperator:
        op = self._row_node(n)
        est = getattr(n, "est_rows", 0.0)
        if est and op.stats.est_rows is None:
            op.stats.est_rows = float(est)
            op.stats.est_source = getattr(n, "est_source", "stats")
        if op.stats.node_fp is None:
            op.stats.node_fp = getattr(n, "fp", "") or None
        return op

    def _row_node(self, n: PL.Phys) -> LOP.RowOperator:
        if isinstance(n, PL.PScan):
            return LOP.RowScan(self.store, n.pattern, n.sort_var)
        if isinstance(n, PL.PPathExpand):
            from repro.core.legacy.property_path import RowPathScan

            return RowPathScan(
                self.store, n.pattern.expr, n.pattern.s, n.pattern.o
            )
        if isinstance(n, PL.PPathScan):
            return self._path_op(n)
        if isinstance(n, PL.PSort):
            return LOP.RowSort(self._row(n.child), var=n.var)
        if isinstance(n, PL.PMergeJoin):
            return LOP.RowMergeJoin(
                self._row(n.left), self._row(n.right), n.var, mode=n.mode,
                post_filter=n.post_filter, dictionary=self.store.dict,
            )
        if isinstance(n, PL.PLookupJoin):
            # legacy uses sort+merge for the same plan shape
            probe = self._row(n.probe)
            build = LOP.RowSort(self._row(n.build), var=n.var)
            if probe.sorted_by() != n.var:
                probe = LOP.RowSort(probe, var=n.var)
            return LOP.RowMergeJoin(probe, build, n.var, mode=n.mode)
        if isinstance(n, PL.PHashJoin):
            return LOP.RowHashJoin(
                self._row(n.probe), self._row(n.build), n.keys, mode=n.mode,
                post_filter=n.post_filter, dictionary=self.store.dict,
            )
        if isinstance(n, PL.PCross):
            # block nested loop via bind join over a constant
            left = self._row(n.left)
            rplan = n.right

            def factory(_code, rplan=rplan):
                return self._row(rplan)

            return _RowCross(left, lambda: self._row(rplan))
        if isinstance(n, PL.PFilter):
            return LOP.RowFilter(self._row(n.child), n.expr, self.store.dict)
        if isinstance(n, PL.PExtend):
            return _RowExtend(self._row(n.child), n.var, n.expr, self.store.dict)
        if isinstance(n, PL.PProject):
            return LOP.RowProject(self._row(n.child), n.vars)
        if isinstance(n, PL.PDistinct):
            return LOP.RowDistinct(self._row(n.child))
        if isinstance(n, PL.PGroup):
            return LOP.RowGroupBy(
                self._row(n.child), n.group_vars, n.aggs, self.store.dict
            )
        if isinstance(n, PL.PHaving):
            return LOP.RowFilter(self._row(n.child), n.expr, self.store.dict)
        if isinstance(n, PL.POrderBy):
            return LOP.RowSort(
                self._row(n.child), keys=n.keys, dictionary=self.store.dict
            )
        if isinstance(n, PL.PSlice):
            return LOP.RowLimit(self._row(n.child), n.limit, n.offset)
        if isinstance(n, PL.PUnion):
            return LOP.RowUnion(self._row(n.left), self._row(n.right))
        raise TypeError(type(n))


class _RowCross(LOP.RowOperator):
    def __init__(self, left: LOP.RowOperator, right_factory):
        self.left = left
        self.right_factory = right_factory
        self._lrow: Optional[dict] = None
        self._right: Optional[LOP.RowOperator] = None
        probe = right_factory()
        lv = tuple(left.var_ids())
        self._vars = lv + tuple(v for v in probe.var_ids() if v not in lv)
        super().__init__("Cross", "(row)")

    def var_ids(self):
        return self._vars

    def children(self):
        return [self.left]

    def _next(self):
        while True:
            if self._lrow is None:
                self._lrow = self.left.next_row()
                if self._lrow is None:
                    return None
                self._right = self.right_factory()
            r = self._right.next_row()
            if r is None:
                self._lrow = None
                continue
            out = dict(self._lrow)
            out.update(r)
            return out

    def _reset(self):
        self.left.reset()
        self._lrow = None


class _RowExtend(LOP.RowOperator):
    def __init__(self, child: LOP.RowOperator, var: int, expr, dictionary: Dictionary):
        from repro.core.expressions import eval_expr_values
        from repro.core.legacy.operators import _row_to_batch

        self.child, self.var, self.expr, self.dictionary = child, var, expr, dictionary
        self._eval = eval_expr_values
        self._to_batch = _row_to_batch
        super().__init__("Bind", "(row)")

    def var_ids(self):
        return self.child.var_ids() + (self.var,)

    def sorted_by(self):
        return self.child.sorted_by()

    def children(self):
        return [self.child]

    def _next(self):
        r = self.child.next_row()
        if r is None:
            return None
        b = self._to_batch(r, self.child.var_ids())
        vals, ok = self._eval(self.expr, b, self.dictionary)
        out = dict(r)
        if ok[0]:
            v = float(vals[0])
            out[self.var] = self.dictionary.encode(int(v) if v.is_integer() else v)
        return out

    def _reset(self):
        self.child.reset()


# ---------------------------------------------------------------------------
# public engine facade
# ---------------------------------------------------------------------------


class QueryResult:
    def __init__(self, var_table: A.VarTable, proj: Tuple[int, ...],
                 rows: np.ndarray, root: AnyOp,
                 pool: Optional[BatchPool] = None,
                 pool_base: Optional[Dict[str, int]] = None,
                 trace: Optional[telemetry.QueryTrace] = None):
        self.var_table = var_table
        self.proj = proj
        self.rows = rows  # (n, n_proj) int32 codes
        self.root = root
        self.pool = pool  # buffer arena (may be Engine-shared and warm)
        # pool counters bracketing this execution: profile()/pool_delta()
        # report this query's contribution, not the arena's lifetime
        # totals — and the end snapshot is frozen here so later queries on
        # the same warm arena can't leak into this result's report
        self.pool_base = pool_base
        self.pool_final: Optional[Dict[str, int]] = (
            dict(pool.stats()) if pool is not None else None
        )
        self.trace = trace  # QueryTrace, or None with telemetry disabled

    @property
    def n_rows(self) -> int:
        return int(self.rows.shape[0])

    def decoded(self, dictionary: Dictionary) -> List[dict]:
        names = [self.var_table.name(v) for v in self.proj]
        out = []
        for row in self.rows:
            out.append(
                {
                    nm: (None if c == NULL_ID else dictionary.decode(int(c)))
                    for nm, c in zip(names, row)
                }
            )
        return out

    def pool_delta(self) -> Dict[str, int]:
        """This query's pool counters (end-of-execution snapshot minus the
        pre-execution one)."""
        if self.pool_final is None:
            return {}
        from repro.core.profiler import _pool_delta

        return _pool_delta(self.pool_final, self.pool_base)

    def profile(self, analyze: bool = False) -> str:
        return profile_tree(self.root, self.var_table,
                            pool=self.pool_final,
                            pool_base=self.pool_base, analyze=analyze)

    def explain_analyze(self) -> str:
        """EXPLAIN ANALYZE report: per-operator actual vs planner-estimated
        rows with MISEST flags at q-error >= profiler.QERROR_FLAG."""
        return self.profile(analyze=True)


class Engine:
    """Public API: Engine(store).execute(plan | sparql_text)."""

    def __init__(self, store: QuadStore, cfg: Optional[EngineConfig] = None,
                 feedback: Optional[telemetry.CardinalityFeedback] = None):
        self.store = store
        self.cfg = cfg or EngineConfig()
        self.stats = GraphStats(store)
        mode = self.cfg.cardinality_feedback or "off"
        assert mode in ("off", "observe", "apply"), mode
        # cardinality feedback store (DESIGN.md §14): caller-shared (the
        # serving layer hands in its WorkloadRepository's store) or
        # Engine-owned. "observe" records without applying; "apply" also
        # hands it to the planner.
        self.feedback: Optional[telemetry.CardinalityFeedback] = None
        if mode != "off":
            self.feedback = (
                feedback if feedback is not None
                else telemetry.CardinalityFeedback()
            )
        assert (self.cfg.adaptive_join or "off") in ("off", "on")
        self.planner = PL.Planner(
            self.stats,
            barq_enabled=self.cfg.engine != "legacy",
            dictionary=store.dict,
            join_strategy=self.cfg.join_strategy,
            sip=self.cfg.sip,
            feedback=self.feedback if mode == "apply" else None,
            memory_budget=self.cfg.memory_budget,
            adaptive_join=self.cfg.adaptive_join,
        )
        # Engine-owned warm arena (DESIGN.md §2.3/§13): shared across this
        # Engine's queries so repeated traffic skips cold-start allocations.
        # Per-query attribution comes from pool_base snapshots, not resets.
        self.pool: Optional[BatchPool] = (
            _make_pool(self.cfg)
            if self.cfg.pool_buffers and self.cfg.engine != "legacy"
            else None
        )

    def plan_fingerprint(self) -> str:
        """Identity of every config knob that changes plan shape. Plan
        caches keyed on query text alone serve a stale shape after a
        config change — fold this in (see serve.query_server). Under
        ``cardinality_feedback="apply"`` the feedback store's version is
        folded in too: new observations must invalidate cached plans, or
        a repeated query would never re-plan against its history."""
        base = (
            f"{self.cfg.engine}|{self.cfg.join_strategy}|{self.cfg.sip}"
            f"|mb{self.cfg.memory_budget}|aj{self.cfg.adaptive_join}"
        )
        if self.cfg.cardinality_feedback == "apply" and self.feedback is not None:
            base += f"|fb{self.feedback.version}"
        return base

    def parse(self, text: str) -> Tuple[A.PlanNode, A.VarTable]:
        from repro.core.parser import parse_query

        return parse_query(text)

    def plan(self, node: A.PlanNode) -> PL.Phys:
        phys = self.planner.plan(node)
        if self.cfg.verify_plans:
            # structural invariant checks (DESIGN.md §16): raises
            # PlanInvariantError naming the node on a malformed plan
            from repro.analysis.plan_verify import verify_plan

            verify_plan(phys)
        return phys

    def execute_plan(
        self, phys: PL.Phys, var_table: Optional[A.VarTable] = None,
        trace: Optional[telemetry.QueryTrace] = None,
    ) -> QueryResult:
        if trace is None and self.cfg.telemetry:
            trace = telemetry.QueryTrace()
        if trace is None:
            return self._run_plan(phys, var_table, None)
        with telemetry.trace_query(trace=trace):
            return self._run_plan(phys, var_table, trace)

    def _run_plan(
        self, phys: PL.Phys, var_table: Optional[A.VarTable],
        trace: Optional[telemetry.QueryTrace],
    ) -> QueryResult:
        pool = self.pool
        pool_base = dict(pool.stats()) if pool is not None else None
        t0 = time.perf_counter()
        translator = Translator(self.store, self.cfg, pool=pool)
        op = translator.translate(phys)
        if trace is not None:
            trace.add_span("translate", "query", t0, time.perf_counter() - t0)
        pool = translator.pool
        if pool_base is None and pool is not None:
            pool_base = {}  # translator-local arena: delta == absolute
        proj = tuple(
            phys_v for phys_v in PL.phys_vars(phys)
        )
        t0 = time.perf_counter()
        try:
            if isinstance(op, LOP.RowOperator):
                rows = op.drain()
                arr = np.full((len(rows), len(proj)), NULL_ID, dtype=np.int32)
                for i, r in enumerate(rows):
                    for j, v in enumerate(proj):
                        arr[i, j] = r.get(v, int(NULL_ID))
            else:
                # streaming drain: copy each batch's projection out, then give
                # the buffers straight back to the arena — the release() side of
                # the zero-copy pipeline (DESIGN.md §2.3)
                blocks = []
                while True:
                    b = op.next_batch()
                    if b is None:
                        break
                    if not b.n_active:
                        b.release()
                        continue
                    cb = b.compact()
                    order = [cb.col_index(v) for v in proj]
                    blocks.append(cb.columns[order, : cb.n_rows].T)  # fancy-index copy
                    cb.release()
                arr = (
                    np.concatenate(blocks, axis=0)
                    if blocks
                    else np.zeros((0, len(proj)), dtype=np.int32)
                )
        finally:
            # operator teardown: drop spill files and window buffers even
            # when the drain raised mid-query (DESIGN.md §15). Stats stay
            # intact, so EXPLAIN ANALYZE / feedback below still work.
            close_tree(op)
        if pool is not None and pool is not self.pool:
            # translator-local arena: return its memory now. The Engine's
            # shared pool stays warm — its recycled buffers (bounded by
            # max_per_bucket per shape) seed the next query.
            pool.drain()
        if trace is not None:
            trace.add_span("execute", "query", t0, time.perf_counter() - t0,
                           rows=int(arr.shape[0]))
            trace.add_operator_tree(op)
        if self.feedback is not None:
            self._record_actuals(op)
        return QueryResult(var_table or A.VarTable(), proj, arr, op, pool,
                           pool_base=pool_base, trace=trace)

    def _record_actuals(self, root: AnyOp) -> None:
        """Feed the drained tree's actual output rows into the feedback
        store, keyed by node fingerprint. Pass-through chains (Sort over
        Scan, ...) share one fingerprint — record it once, from the
        topmost operator (identical counts by construction)."""
        seen = set()

        def walk(op) -> None:
            fp = op.stats.node_fp
            if fp and fp not in seen:
                seen.add(fp)
                self.feedback.record(fp, op.stats.results)
            for c in op.children():
                walk(c)

        walk(root)

    def execute(self, node_or_text: Union[str, A.PlanNode],
                var_table: Optional[A.VarTable] = None,
                trace: Optional[telemetry.QueryTrace] = None) -> QueryResult:
        if trace is None and self.cfg.telemetry:
            label = (
                " ".join(node_or_text.split())[:120]
                if isinstance(node_or_text, str) else "query"
            )
            trace = telemetry.QueryTrace(label)
        if trace is None:
            if isinstance(node_or_text, str):
                node, var_table = self.parse(node_or_text)
            else:
                node = node_or_text
            return self._run_plan(self.plan(node), var_table, None)
        with telemetry.trace_query(trace=trace):
            if isinstance(node_or_text, str):
                with trace.span("parse"):
                    node, var_table = self.parse(node_or_text)
            else:
                node = node_or_text
            with trace.span("plan"):
                phys = self.plan(node)
            return self._run_plan(phys, var_table, trace)

    # -- EXPLAIN / EXPLAIN ANALYZE ------------------------------------------

    def explain(self, node_or_text: Union[str, A.PlanNode],
                var_table: Optional[A.VarTable] = None) -> str:
        """The chosen physical plan (no execution)."""
        if isinstance(node_or_text, str):
            node, var_table = self.parse(node_or_text)
        else:
            node = node_or_text
        return PL.explain(self.plan(node), var_table)

    def explain_analyze(self, node_or_text: Union[str, A.PlanNode],
                        var_table: Optional[A.VarTable] = None) -> str:
        """Execute and render per-operator estimated vs actual rows with
        misestimate flags (DESIGN.md §13)."""
        return self.execute(node_or_text, var_table).explain_analyze()
