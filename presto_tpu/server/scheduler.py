"""Cross-host query scheduling: fragment -> per-worker tasks.

Reference parity: ``SqlQueryScheduler`` / ``SqlStageExecution`` — a leaf
stage is N tasks over assigned splits of the partitioned source,
intermediate data flows through exchanges, the root stage gathers
(SURVEY.md §2.1 "Query scheduler", §3.2).

TPU-first shape:
- ONE source-partitioned stage per distributable fragment: a scan is
  split by row ranges across workers; every other scan is replicated
  (each worker scans it fully — the reference's REPLICATED build-side
  choice, SURVEY.md §2.4).
- The partitioned scan must reach the stage cut through row-distributive
  edges only: filters, projections, and the *streamed/probe* side of
  joins (the preserved side of outer joins). Concatenating per-worker
  results is only correct when each input row's contribution is
  independent of the partition — the reference encodes the same rule by
  hash-partitioning the probe side and broadcasting the build side.
- The stage is CUT at the lowest aggregation/distinct above the
  partitioned scan: workers run the PARTIAL step, the coordinator runs
  the FINAL merge (via the same ``split_aggregation`` rewrite the
  in-slice engine uses) and then everything above the cut (which may
  include further joins/aggregations over full gathered data).
- If no scan admits a valid partitioning, ``plan_stage`` returns None
  and the coordinator executes the fragment locally (correctness first;
  the reference similarly falls back to single-task stages).
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional, Tuple

from presto_tpu.parallel.agg_split import split_aggregation
from presto_tpu.plan import nodes as N


@dataclasses.dataclass(frozen=True)
class StagePlan:
    """One distributable fragment scheduled across workers."""

    worker_fragment: N.PlanNode  # runs on every worker over its splits
    final_root: N.PlanNode  # coordinator plan over the RemoteSourceNode
    partition_scan: int  # walk index (in worker_fragment) of split scan
    partition_rows: int  # total row count of the partitioned table


def plan_stage(
    fragment_root: N.PlanNode,
    catalogs,
    replicated_limit: Optional[int] = None,
) -> Optional[StagePlan]:
    """Decompose one distributable fragment into worker/final steps.

    Tries candidate partition scans largest-first; returns None when no
    scan can be partitioned without changing semantics (the coordinator
    then runs the fragment locally).

    ``replicated_limit`` (streaming use): reject a candidate whose
    worker fragment would replicate another scan bigger than this —
    the streamed batch runner stages replicated scans whole, so an
    oversized one must instead be the partition scan of an *earlier*
    recursion step (exec.streaming resolves big-probe-over-big-build
    plans inner-fragment-first this way).
    """
    scans = [
        n for n in N.walk(fragment_root) if isinstance(n, N.TableScanNode)
    ]
    sized: List[Tuple[int, N.TableScanNode]] = []
    for s in scans:
        conn = catalogs.get(s.handle.catalog)
        stats = conn.metadata().get_table_stats(s.handle)
        sized.append((int(stats.row_count or 0), s))
    sized.sort(key=lambda t: -t[0])

    for rows, scan in sized:
        stage = _try_cut(fragment_root, scan, rows)
        if stage is None:
            continue
        if replicated_limit is not None:
            others = [
                r
                for r, s in sized
                if s is not scan
                and any(
                    n is s for n in N.walk(stage.worker_fragment)
                )
            ]
            if any(r > replicated_limit for r in others):
                continue
        return stage
    return None


def _path_to(root: N.PlanNode, target: N.PlanNode) -> Optional[list]:
    """Node path root->...->target by identity, or None."""
    if root is target:
        return [root]
    for c in root.children():
        sub = _path_to(c, target)
        if sub is not None:
            return [root] + sub
    return None


def _edge_distributive(parent: N.PlanNode, child: N.PlanNode) -> bool:
    """True when partitioning ``child``'s rows and concatenating
    ``parent``'s per-partition outputs equals running ``parent`` whole.
    """
    if isinstance(parent, (N.FilterNode, N.ProjectNode)):
        return True
    if isinstance(parent, N.JoinNode):
        if parent.join_type == "inner":
            return True  # inner join distributes over either side
        # semi/anti/left preserve the LEFT (probe) side only
        return child is parent.left
    if isinstance(parent, N.CrossJoinNode):
        # right side is a broadcast scalar; only the left streams
        return child is parent.left
    return False


def _try_cut(
    fragment_root: N.PlanNode, scan: N.TableScanNode, rows: int
) -> Optional[StagePlan]:
    path = _path_to(fragment_root, scan)
    if path is None:
        return None

    # lowest aggregation/distinct above the scan = the stage cut
    cut_i = None
    for i in range(len(path) - 2, -1, -1):
        if isinstance(path[i], (N.AggregationNode, N.DistinctNode)):
            cut_i = i
            break
    # every edge from the scan up to (but not including) the cut must be
    # row-distributive; with no cut, every edge up to the root
    lowest_parent = cut_i + 1 if cut_i is not None else 0
    for i in range(len(path) - 1, lowest_parent, -1):
        if not _edge_distributive(path[i - 1], path[i]):
            return None

    if cut_i is None:
        worker_root = fragment_root
        final_root: N.PlanNode = N.RemoteSourceNode(
            fragment_root=worker_root
        )
    else:
        cut = path[cut_i]
        if isinstance(cut, N.AggregationNode):
            try:
                partial_aggs, fkeys, faggs, post = split_aggregation(
                    cut.group_keys, cut.aggs
                )
            except NotImplementedError:
                # un-decomposable aggregate (e.g. array_agg): no
                # distributed cut; the caller falls back to local
                # execution
                return None
            worker_root = dataclasses.replace(cut, aggs=partial_aggs)
            remote = N.RemoteSourceNode(fragment_root=worker_root)
            final_sub: N.PlanNode = N.AggregationNode(
                source=remote,
                group_keys=fkeys,
                aggs=faggs,
                # the planner's bucket and the keys' proved ranges ride
                # along; what the merge's page is sized by is decided
                # where it meets its input (ops.aggregation)
                max_groups=cut.max_groups,
                key_ranges=cut.key_ranges,
            )
            if post:
                final_sub = N.ProjectNode(
                    source=final_sub, projections=post
                )
        else:  # DistinctNode: dedup-of-dedups
            worker_root = cut
            remote = N.RemoteSourceNode(fragment_root=worker_root)
            final_sub = N.DistinctNode(
                source=remote, max_groups=cut.max_groups
            )
        final_root = _replace_on_path(path[:cut_i], cut, final_sub)

    scan_idx = None
    for i, node in enumerate(N.walk(worker_root)):
        if node is scan:
            scan_idx = i
            break
    if scan_idx is None:  # scan above the cut: nothing to partition
        return None
    return StagePlan(
        worker_fragment=worker_root,
        final_root=final_root,
        partition_scan=scan_idx,
        partition_rows=rows,
    )


def _replace_on_path(
    ancestors: list, old: N.PlanNode, new: N.PlanNode
) -> N.PlanNode:
    """Rebuild the ancestor chain with ``old`` (a direct child of the
    last ancestor) swapped for ``new``."""
    for parent in reversed(ancestors):
        changes = {}
        for f in dataclasses.fields(parent):
            if getattr(parent, f.name) is old:
                changes[f.name] = new
        assert changes, "path ancestor does not reference its child"
        new = dataclasses.replace(parent, **changes)
        old = parent
    return new


def stable_workers(workers) -> list:
    """Placement set for gather/merge/join stages under preemptible-
    aware scheduling: these stages hold the only copy of merged state
    (their buffers are NOT spool-backed the way producer partitions
    are), so they belong on stable nodes — preemptibles keep the
    spool-backed shuffle-producer work, where a preemption costs one
    re-servable partition, not a stage re-run. Returns the
    non-preemptible subset when any exists; an all-preemptible pool
    still schedules (recovery, not placement, is the safety net
    there)."""
    stable = [
        w for w in workers if not getattr(w, "preemptible", False)
    ]
    return stable if stable else list(workers)


def select_exchange_transport(
    workers, enabled: bool, schemas=()
) -> str:
    """Transport selection for one partitioned exchange stage — the
    ONE place that decides ICI vs HTTP (the exchange-plane confinement
    rule pins it here; producers and consumers only *honor* the choice
    carried on ``FragmentSpec.ici_slice``).

    Returns the slice id when every candidate worker announces the
    SAME non-empty slice (co-located: one host process driving one
    device mesh — the topology the in-slice exchange segment requires)
    and every exchanged schema is ICI-transportable (fixed-width
    scalar columns; array/map/row keep the serialized wire). Returns
    "" (the HTTP wire) otherwise: mixed slices, unannounced topology,
    a DRAINING peer in the set, an oversized fan-out, or the gate off.
    A DRAINING worker's edges must degrade to HTTP so the
    zero-failure-drain contract holds even for stages planned at the
    drain boundary."""
    from presto_tpu.parallel.exchange import MAX_ICI_PARTS

    if not enabled or not workers:
        return ""
    if len(workers) > MAX_ICI_PARTS:
        return ""
    slices = set()
    for w in workers:
        if getattr(w, "state", "ACTIVE") != "ACTIVE":
            return ""
        slices.add(getattr(w, "slice_id", ""))
    if len(slices) != 1:
        return ""
    (slice_id,) = slices
    if not slice_id:
        return ""
    for schema in schemas:
        for t in schema.values():
            if t.is_array or t.is_map or t.is_row:
                return ""
    return slice_id


def select_exchange_edges(
    workers, enabled: bool, schemas=()
) -> str:
    """Per-EDGE transport selection for one partitioned exchange
    stage — the successor of :func:`select_exchange_transport`'s
    all-or-nothing rule, and like it the ONE place that decides ICI vs
    HTTP (the exchange-plane confinement rule pins selection here).

    Returns the DOMINANT slice: the largest group of ACTIVE workers
    announcing the same non-empty slice id, provided at least two
    workers share it (a single worker has no in-slice peer to exchange
    with) and every exchanged schema is ICI-transportable. Workers
    outside the dominant slice no longer veto the stage — the slice id
    still rides ``FragmentSpec.ici_slice``, and each EDGE settles
    per-worker at run time: a producer whose own slice does not match
    emits on the HTTP lane (``exchange.ici_fallbacks``), and a
    consumer simply misses the segment for that source and pulls HTTP
    — so a lone cross-slice worker rides HTTP on its own edges without
    taxing the co-located pairs. DRAINING/INACTIVE workers are
    excluded from the count (their edges degrade at drain time), but
    do not demote the rest. Ties break deterministically (largest
    count, then lexicographically greatest slice id)."""
    from presto_tpu.parallel.exchange import MAX_ICI_PARTS

    if not enabled or not workers:
        return ""
    if len(workers) > MAX_ICI_PARTS:
        return ""
    counts: dict = {}
    for w in workers:
        if getattr(w, "state", "ACTIVE") != "ACTIVE":
            continue
        sid = getattr(w, "slice_id", "")
        if sid:
            counts[sid] = counts.get(sid, 0) + 1
    if not counts:
        return ""
    best, n = max(counts.items(), key=lambda kv: (kv[1], kv[0]))
    if n < 2:
        return ""
    for schema in schemas:
        for t in schema.values():
            if t.is_array or t.is_map or t.is_row:
                return ""
    return best


def assign_ranges(total_rows: int, n_ranges: int) -> List[Tuple[int, int]]:
    """Contiguous row ranges of the partitioned scan. The coordinator
    over-partitions (n_ranges = workers x split_queue_factor) and lets
    workers drain a shared queue — dynamic split placement."""
    chunk = -(-total_rows // max(n_ranges, 1))
    out = []
    for i in range(n_ranges):
        lo = min(i * chunk, total_rows)
        hi = min((i + 1) * chunk, total_rows)
        out.append((lo, hi))
    return out
