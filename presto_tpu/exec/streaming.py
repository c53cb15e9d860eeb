"""Larger-than-HBM execution: split-streamed partial aggregation with
hash-bucketed host-RAM spill.

Reference parity: the three mechanisms of SURVEY.md §5.7 in one design —
(a) split parallelism streaming batches through the operator pipeline
(§2.4), (b) partitioned spill: partial states hash-partitioned to
host-RAM buckets during the single input pass (§2.1 "Spilling"), and
(c) grouped execution: each bucket's final merge runs alone on the
device, bounding live HBM state to one bucket (§2.4 "Grouped / bucketed
execution").

TPU-first shape: the *same* stage-cut rewrite the multi-host scheduler
uses (server.scheduler.plan_stage — partial agg below the cut, final
merge above) is applied locally; the compiled partial fragment is ONE
XLA program reused for every batch (fixed capacity bucket), so the
stream costs zero recompiles after the first batch. Host RAM is the
spill tier (SURVEY.md §5.7 "host-RAM as the spill tier").

Recursion handles multi-big-scan plans (e.g. TPC-H Q18, where both the
semi-join subquery and the outer pipeline scan SF100 lineitem):
``plan_stage(replicated_limit=...)`` refuses a cut that would replicate
an oversized scan, so the inner fragment streams first and its
materialized (small) result feeds the outer recursion as a leaf.
"""

from __future__ import annotations

import dataclasses
import zlib
from typing import Dict, List, Tuple

import numpy as np

from presto_tpu.connectors.tpch import DictColumn
from presto_tpu.exec.staging import (
    PREFETCH_DEPTH,
    MaskedColumn,
    prefetch_iter,
    stage_page,
)
from presto_tpu.plan import nodes as N
from presto_tpu.parallel.fragmenter import insert_gathers
from presto_tpu.server import pages_wire
from presto_tpu.server.scheduler import (
    _path_to,
    _replace_on_path,
    plan_stage,
)


class StreamingError(RuntimeError):
    pass


def _prefetch_splits(runner, scan, ranges, capacity):
    """Iterate staged split pages of ``ranges`` with pipelined
    prefetch staging (exec.staging.prefetch_iter): a background host
    thread stages batch N+1 while the caller's device program runs
    batch N. Each prefetch-staged batch opens a ``stage:prefetch``
    span on the query's trace, so EXPLAIN ANALYZE shows the staging
    window overlapping the open ``execute`` span."""
    qs = runner._active_qs
    trace = getattr(qs, "trace", None) if qs is not None else None

    def load(rng):
        # prefetch thread: inherit the caller's stats sink (runner
        # thread-locals don't cross threads)
        runner._qs_local.value = qs
        # no owner: the columns stay pinned until the query ends
        # (runner.release_pins), so the release is not needed here
        if trace is None:
            return runner.stage_split(scan, rng[0], rng[1], capacity)[0]
        with trace.span(
            "stage:prefetch", parent=trace.root, lo=rng[0], hi=rng[1],
        ):
            return runner.stage_split(scan, rng[0], rng[1], capacity)[0]

    return prefetch_iter(ranges, load, PREFETCH_DEPTH)


def _scan_rows(catalogs, scan: N.TableScanNode) -> int:
    conn = catalogs.get(scan.handle.catalog)
    stats = conn.metadata().get_table_stats(scan.handle)
    return int(stats.row_count or 0)


def needs_streaming(root: N.PlanNode, catalogs, session) -> bool:
    """True when some scan exceeds the device residency budget."""
    max_rows = int(session.get("max_device_rows"))
    return any(
        isinstance(n, N.TableScanNode)
        and _scan_rows(catalogs, n) > max_rows
        for n in N.walk(root)
    )


def run_streamed(runner, droot: N.PlanNode):
    """Execute a device plan whose inputs exceed ``max_device_rows``.

    Mirrors the distributed runner's shape: fragment the plan at the
    gather boundary, stream each oversized fragment, run the root
    fragment over the gathered pages.
    """
    if not runner.session.get("spill_enabled"):
        raise StreamingError(
            "input exceeds max_device_rows and spill_enabled=false "
            "(reference behavior: the query fails on memory rather "
            "than spilling)"
        )
    froot = insert_gathers(droot)
    leaves = [
        n
        for n in N.walk(froot)
        if isinstance(n, (N.TableScanNode, N.RemoteSourceNode))
    ]
    # remote leaves RUN here (recursive fragment execution), so this
    # site cannot use runner.leaf_pages (which only resolves
    # already-produced pages)
    pages = []
    for leaf in leaves:
        if isinstance(leaf, N.RemoteSourceNode):
            pages.append(_run_fragment(runner, leaf.fragment_root, {}))
        else:
            pages.append(runner._load_table(leaf))
    return runner._run_with_pages(froot, leaves, pages)


# ------------------------------------------------------------- fragment


def _run_fragment(runner, frag_root: N.PlanNode, materialized: Dict):
    """Run one distributable fragment, streaming if it holds an
    oversized scan. ``materialized`` maps id(RemoteSourceNode) -> Page
    produced by an earlier recursion step."""
    max_rows = int(runner.session.get("max_device_rows"))
    big = [
        s
        for s in N.walk(frag_root)
        if isinstance(s, N.TableScanNode)
        and _scan_rows(runner.catalogs, s) > max_rows
    ]
    if not big:
        leaves, pages = runner.leaf_pages(frag_root, materialized)
        return runner._run_with_pages(frag_root, leaves, pages)

    stage = plan_stage(
        frag_root, runner.catalogs, replicated_limit=max_rows
    )
    if stage is None:
        out = _try_partitioned_join(
            runner, frag_root, materialized, max_rows
        )
        if out is not None:
            return out
        raise StreamingError(
            "fragment exceeds max_device_rows and admits no "
            "semantics-preserving streaming cut"
        )

    bucket_root, rest_root, frag_remote, rest_remote = _split_final(
        stage.final_root, stage.worker_fragment
    )

    # --- the single input pass: batch -> partial -> bucket spill
    from presto_tpu.exec.staging import bucket_capacity

    worker_root = stage.worker_fragment
    batch = min(
        int(runner.session.get("page_capacity")), max_rows
    )
    # the cut aggregation's page is sized where it meets its batch
    # (ops.aggregation._out_capacity): no more slots than the batch has
    # rows, here as in the served worker
    batch_cap = bucket_capacity(batch)
    part_scan = list(N.walk(worker_root))[stage.partition_scan]
    n_buckets = _n_buckets_for(stage.partition_rows, max_rows)
    key_names = _bucket_key_names(worker_root)
    schema = dict(worker_root.output_schema())

    leaves = [
        n
        for n in N.walk(worker_root)
        if isinstance(n, (N.TableScanNode, N.RemoteSourceNode))
    ]
    base_pages = {}
    for n in leaves:
        if isinstance(n, N.RemoteSourceNode):
            base_pages[id(n)] = materialized[id(n)]
        elif n is not part_scan:
            base_pages[id(n)] = runner._load_table(n)

    cut_agg = isinstance(worker_root, (N.AggregationNode, N.DistinctNode))
    spill: List[List[tuple]] = [[] for _ in range(n_buckets)]
    # fixed capacity: every batch (incl. the tail) reuses ONE compiled
    # partial-fragment program; prefetch staging overlaps batch N+1's
    # host->device transfer with batch N's device execution
    ranges = [
        (lo, min(lo + batch, stage.partition_rows))
        for lo in range(0, stage.partition_rows, batch)
    ]
    for batch_page in _prefetch_splits(
        runner, part_scan, ranges, batch_cap
    ):
        pages = [
            batch_page if n is part_scan else base_pages[id(n)]
            for n in leaves
        ]
        out = runner._run_with_pages(
            worker_root, leaves, pages, cut_agg=cut_agg
        )
        part_payload, _, nrows = _page_to_payload(out)
        if nrows == 0:
            continue
        _spill_partial(
            spill, part_payload, schema, key_names, nrows, n_buckets
        )

    # --- per-bucket final merge on device
    result = merge_spilled_buckets(
        runner, spill, schema, bucket_root, frag_remote
    )

    if rest_root is None:
        return result
    # the rest of the fragment may hold further oversized scans: recurse
    return _run_fragment(
        runner, rest_root, {**materialized, id(rest_remote): result}
    )


def _n_buckets_for(rows: int, max_rows: int) -> int:
    """Spill bucket count: 4x over-partitioned so each bucket's merge
    stays comfortably under the residency budget despite skew."""
    return max(1, -(-rows // max_rows) * 4)


def grouped_final_merge(
    runner, payloads, schema, final_root, worker_fragment, max_rows
):
    """Distributed-gather twin of the local streamed path: when the
    gathered partial states exceed the device budget, hash-bucket them
    by group key and merge one bucket at a time (grouped execution at
    the coordinator — the memory-funnel fix of VERDICT r2 weak 5).

    Returns the final Page, or None when bucketing does not apply
    (small gather, or no group keys to bucket by). Honors the same
    ``spill_enabled`` policy as run_streamed: disabled spill means the
    query FAILS rather than silently spilling host-side."""
    total_rows = sum(n for _, _, n in payloads)
    key_names = _bucket_key_names(worker_fragment)
    if total_rows <= max_rows or not key_names:
        return None
    if not runner.session.get("spill_enabled"):
        raise StreamingError(
            "gathered partial states exceed max_device_rows and "
            "spill_enabled=false (reference behavior: fail on memory "
            "rather than spill)"
        )
    bucket_root, rest_root, frag_remote, rest_remote = _split_final(
        final_root, worker_fragment
    )
    n_buckets = _n_buckets_for(total_rows, max_rows)
    spill = bucketize_payloads(payloads, schema, key_names, n_buckets)
    page = merge_spilled_buckets(
        runner, spill, schema, bucket_root, frag_remote
    )
    if rest_root is None:
        return page
    local_scans = [
        n for n in N.walk(rest_root) if isinstance(n, N.TableScanNode)
    ]
    leaves = [rest_remote] + local_scans
    pages = [page] + [runner._load_table(s) for s in local_scans]
    return runner._run_with_pages(rest_root, leaves, pages)


def merge_spilled_buckets(
    runner, spill: List[List[tuple]], schema, bucket_root, frag_remote
):
    """Per-bucket final merge on device: each bucket's partial states
    stage alone, run the bucket-safe chain, and free as they go —
    live HBM state stays bounded to one bucket (grouped execution,
    SURVEY.md §2.4). Shared by the local streamed path and the
    coordinator's distributed gather (which has the same memory-funnel
    shape at scale)."""
    outs: List[tuple] = []
    out_schema = dict((bucket_root or frag_remote).output_schema())
    for b in range(len(spill)):
        if not spill[b]:
            continue
        merged = pages_wire.merge_payloads(spill[b], schema)
        page = stage_page(merged, schema)
        spill[b] = []  # free the spilled partials as we go
        if bucket_root is None:
            outs.append(_page_to_payload(page))
            continue
        out = runner._run_with_pages(bucket_root, [frag_remote], [page])
        pl = _page_to_payload(out)
        if pl[2]:
            outs.append(pl)

    if outs:
        merged = pages_wire.merge_payloads(outs, out_schema)
    else:
        merged = {
            name: np.empty(0, t.np_dtype)
            for name, t in out_schema.items()
        }
    return stage_page(merged, out_schema)


def bucketize_payloads(
    payloads: List[tuple], schema, key_names: List[str], n_buckets: int
) -> List[List[tuple]]:
    """Hash-partition wire payloads into group-key buckets (the spill
    shape merge_spilled_buckets consumes)."""
    spill: List[List[tuple]] = [[] for _ in range(n_buckets)]
    for payload, pschema, nrows in payloads:
        if not nrows:
            continue
        _spill_partial(spill, payload, schema, key_names, nrows, n_buckets)
    return spill


def _split_final(
    final_root: N.PlanNode, worker_fragment: N.PlanNode = None
):
    """Split the coordinator-side plan into the bucket-safe chain (the
    final agg/distinct merge plus row-wise filters/projections directly
    above it — safe because groups are complete within one bucket) and
    the rest. Returns (bucket_root|None, rest_root|None, remote,
    rest_remote|None) — ``rest_remote`` is the leaf in rest_root the
    bucket-merged page binds to.

    ``worker_fragment`` identifies THIS stage's remote when the final
    plan holds several RemoteSourceNodes (recursive streaming leaves
    earlier fragments' remotes in the tree — picking the first in walk
    order built bucket chains around, and bound results to, the WRONG
    exchange)."""
    remote = next(
        n
        for n in N.walk(final_root)
        if isinstance(n, N.RemoteSourceNode)
        and (
            worker_fragment is None
            or n.fragment_root is worker_fragment
        )
    )
    path = _path_to(final_root, remote)
    j = len(path) - 2
    if j >= 0 and isinstance(
        path[j], (N.AggregationNode, N.DistinctNode)
    ):
        j -= 1
        while j >= 0 and isinstance(
            path[j], (N.FilterNode, N.ProjectNode)
        ):
            j -= 1
    bucket_root = path[j + 1]
    if bucket_root is remote:
        # no bucket-safe chain: the merged page binds to the stage
        # remote itself inside the (unchanged) rest plan
        return None, (
            None if final_root is remote else final_root
        ), remote, remote
    if bucket_root is final_root:
        return bucket_root, None, remote, None
    rest_remote = N.RemoteSourceNode(fragment_root=bucket_root)
    rest_root = _replace_on_path(
        path[: j + 1], bucket_root, rest_remote
    )
    return bucket_root, rest_root, remote, rest_remote


def _bucket_key_names(worker_root: N.PlanNode) -> List[str]:
    """Group-key output columns of the cut node = the spill partition
    key (DistinctNode dedups whole rows: every column is key)."""
    if isinstance(worker_root, N.AggregationNode):
        return [n for n, _ in worker_root.group_keys]
    if isinstance(worker_root, N.DistinctNode):
        return list(worker_root.output_schema())
    return []  # no cut: pure distributive fragment, single bucket


# ---------------------------------------------- partitioned join spill


def _oversized_scans(runner, root: N.PlanNode, max_rows: int):
    return [
        s
        for s in N.walk(root)
        if isinstance(s, N.TableScanNode)
        and _scan_rows(runner.catalogs, s) > max_rows
    ]


def _row_distributive_to_root(root: N.PlanNode, scan: N.PlanNode) -> bool:
    """True when every edge scan->root is a Filter/Project (streaming
    batches of the scan through the subtree and concatenating equals
    running it whole)."""
    path = _path_to(root, scan)
    if path is None:
        return False
    return all(
        isinstance(p, (N.FilterNode, N.ProjectNode)) for p in path[:-1]
    )


def _try_partitioned_join(
    runner, frag_root: N.PlanNode, materialized: Dict, max_rows: int
):
    """Join build-side spill (reference: HashBuilderOperator partitioned
    spill + LookupJoinOperator unspill — SURVEY.md §2.1 "Spilling").

    When a join's BUILD side exceeds the device budget (so neither side
    can be replicated and no agg cut applies), hash-partition BOTH
    sides by the equi-join keys into host-RAM buckets — each side
    streamed through its own compiled sub-fragment in split batches —
    then join bucket-by-bucket on device and concatenate. Valid for
    every equi-join type: a key lands in exactly one bucket on both
    sides, so per-bucket joins partition the full join (probe-preserved
    rows included). Returns the fragment's result page, or None when no
    join admits this shape (caller falls back to the error)."""
    for J in N.walk(frag_root):
        if not isinstance(J, N.JoinNode):
            continue
        if not _oversized_scans(runner, J.right, max_rows):
            continue  # build fits: not this join's problem
        sides = []
        for side_root, keys in (
            (J.left, J.left_keys),
            (J.right, J.right_keys),
        ):
            big = _oversized_scans(runner, side_root, max_rows)
            if len(big) > 1 or (
                big and not _row_distributive_to_root(side_root, big[0])
            ):
                sides = None
                break
            sides.append((side_root, list(keys), big[0] if big else None))
        if sides is None:
            continue
        probe_rows = sum(
            _scan_rows(runner.catalogs, s)
            for s in N.walk(J.left)
            if isinstance(s, N.TableScanNode)
        )
        build_rows = sum(
            _scan_rows(runner.catalogs, s)
            for s in N.walk(J.right)
            if isinstance(s, N.TableScanNode)
        )
        n_buckets = _n_buckets_for(probe_rows + build_rows, max_rows)

        spills = []
        for side_root, keys, big_scan in sides:
            spills.append(
                _stream_side_to_buckets(
                    runner, side_root, keys, big_scan, n_buckets,
                    materialized, max_rows,
                )
            )
        (p_spill, p_schema), (b_spill, b_schema) = spills

        lremote = N.RemoteSourceNode(fragment_root=J.left)
        rremote = N.RemoteSourceNode(fragment_root=J.right)
        bucket_join = dataclasses.replace(J, left=lremote, right=rremote)
        out_schema = dict(bucket_join.output_schema())
        outs: List[tuple] = []
        for b in range(n_buckets):
            # probe-preserved types skip probe-empty buckets; FULL also
            # preserves build rows, so build-only buckets must still run
            if not p_spill[b] and (
                J.join_type != "full" or not b_spill[b]
            ):
                p_spill[b], b_spill[b] = [], []
                continue
            p_page = stage_page(
                pages_wire.merge_payloads(p_spill[b], p_schema)
                if p_spill[b]
                else {
                    n: np.empty(0, t.np_dtype)
                    for n, t in p_schema.items()
                },
                p_schema,
            )
            b_page = stage_page(
                pages_wire.merge_payloads(b_spill[b], b_schema)
                if b_spill[b]
                else {
                    n: np.empty(0, t.np_dtype)
                    for n, t in b_schema.items()
                },
                b_schema,
            )
            p_spill[b], b_spill[b] = [], []  # free as we go
            out = runner._run_with_pages(
                bucket_join, [lremote, rremote], [p_page, b_page]
            )
            pl = _page_to_payload(out)
            if pl[2]:
                outs.append(pl)

        if outs:
            merged = pages_wire.merge_payloads(outs, out_schema)
        else:
            merged = {
                n: np.empty(0, t.np_dtype)
                for n, t in out_schema.items()
            }
        join_page = stage_page(merged, out_schema)
        if J is frag_root:
            return join_page
        remote = N.RemoteSourceNode(fragment_root=J)
        path = _path_to(frag_root, J)
        rest_root = _replace_on_path(path[:-1], J, remote)
        return _run_fragment(
            runner, rest_root, {**materialized, id(remote): join_page}
        )
    return None


def _stream_side_to_buckets(
    runner,
    side_root: N.PlanNode,
    key_cols: List[str],
    big_scan,
    n_buckets: int,
    materialized: Dict,
    max_rows: int,
):
    """Run one join side, hash-bucketing its output rows by the join
    keys into host-RAM spill buckets. A side with no oversized scan
    runs whole; a side with one streams the scan in split batches
    through ONE compiled sub-fragment program."""
    from presto_tpu.exec.staging import bucket_capacity

    schema = dict(side_root.output_schema())
    spill: List[List[tuple]] = [[] for _ in range(n_buckets)]

    def spill_page(page):
        payload, pschema, nrows = _page_to_payload(page)
        if nrows:
            _spill_partial(
                spill, payload, schema, key_cols, nrows, n_buckets
            )

    if big_scan is None:
        leaves, pages = runner.leaf_pages(side_root, materialized)
        spill_page(
            runner._run_with_pages(side_root, leaves, pages)
        )
        return spill, schema

    # _row_distributive_to_root admitted only Filter/Project edges, so
    # the side is a linear chain and big_scan is its ONLY leaf
    batch = min(int(runner.session.get("page_capacity")), max_rows)
    batch_cap = bucket_capacity(batch)
    total = _scan_rows(runner.catalogs, big_scan)
    ranges = [
        (lo, min(lo + batch, total)) for lo in range(0, total, batch)
    ]
    for batch_page in _prefetch_splits(
        runner, big_scan, ranges, batch_cap
    ):
        spill_page(
            runner._run_with_pages(side_root, [big_scan], [batch_page])
        )
    return spill, schema


# ------------------------------------------------------- host-side spill


def _page_to_payload(page) -> Tuple[Dict, Dict, int]:
    """Device page -> (staging payload, schema, nrows) on host numpy —
    the same shape pages_wire.deserialize_page produces, so bucket
    merges reuse pages_wire.merge_payloads (incl. dictionary remap)."""
    from presto_tpu.exec.staging import ArrayColumn

    cols, n = pages_wire.page_to_wire_columns(page)
    payload: Dict = {}
    schema: Dict = {}
    for name, data, valid, dtype, dict_values in cols:
        schema[name] = dtype
        if isinstance(data, ArrayColumn):
            payload[name] = ArrayColumn(
                offsets=data.offsets,
                values=data.values,
                valid=data.valid,
                dict_values=dict_values,
            )
        elif valid is not None:
            payload[name] = MaskedColumn(
                data=np.asarray(data),
                valid=np.asarray(valid),
                values=dict_values,
            )
        elif dict_values is not None:
            payload[name] = DictColumn(
                ids=np.asarray(data, np.int32),
                values=np.asarray(dict_values, object),
            )
        else:
            payload[name] = np.asarray(data)
    return payload, schema, n


def _mix64(x: np.ndarray) -> np.ndarray:
    x = x.astype(np.uint64, copy=True)
    with np.errstate(over="ignore"):
        x ^= x >> np.uint64(30)
        x *= np.uint64(0xBF58476D1CE4E5B9)
        x ^= x >> np.uint64(27)
        x *= np.uint64(0x94D049BB133111EB)
        x ^= x >> np.uint64(31)
    return x


def _col_hash_input(col, nrows: int) -> np.ndarray:
    """uint64 image of a column for bucket hashing. Dictionary ids are
    mapped through a per-VALUE crc so the hash is stable across batches
    whose dictionaries differ; NULLs hash to 0 (one bucket)."""
    from presto_tpu.exec.staging import ArrayColumn

    if isinstance(col, ArrayColumn):
        raise NotImplementedError(
            "array columns cannot be bucket-hash keys"
        )
    if isinstance(col, MaskedColumn):
        base = _col_hash_input(
            DictColumn(ids=np.asarray(col.data, np.int64), values=col.values)
            if col.values is not None
            else col.data,
            nrows,
        )
        return np.where(col.valid[:nrows], base, np.uint64(0))
    if isinstance(col, DictColumn):
        vals = np.asarray(col.values, object)
        crc = np.asarray(
            [zlib.crc32(str(v).encode()) for v in vals], np.uint64
        )
        ids = np.clip(np.asarray(col.ids, np.int64), 0, max(len(vals) - 1, 0))
        if len(vals) == 0:
            return np.zeros(nrows, np.uint64)
        return crc[ids[:nrows]]
    data = np.asarray(col)[:nrows]
    if data.ndim == 2 and data.shape[1] == 2:
        # long-decimal limb pairs: mix the hi limb, fold in lo — equal
        # int128 values hash equally (matches exchange.partition_hash's
        # two-lane fold up to the mixing order, which only this host
        # bucketing uses)
        hi = data[:, 0].astype(np.int64).view(np.uint64)
        lo = data[:, 1].astype(np.int64).view(np.uint64)
        return _mix64(hi) ^ lo
    if data.ndim != 1:
        raise NotImplementedError(
            f"cannot bucket-hash a {data.ndim}-D column"
        )
    if data.dtype.kind == "f":
        d = data.astype(np.float64, copy=True)
        d[d == 0] = 0.0  # -0.0 hashes like +0.0
        return d.view(np.uint64)
    return data.astype(np.int64).view(np.uint64)


def _bucket_of(payload, key_names, nrows, n_buckets) -> np.ndarray:
    h = np.full(nrows, 0x9E3779B97F4A7C15, np.uint64)
    for name in key_names:
        h ^= _mix64(_col_hash_input(payload[name], nrows))
        h = _mix64(h)
    return (h % np.uint64(n_buckets)).astype(np.int64)


def _slice_payload(payload, schema, mask) -> Dict:
    from presto_tpu.exec.staging import ArrayColumn

    out = {}
    for name in schema:
        col = payload[name]
        if isinstance(col, ArrayColumn):
            off = np.asarray(col.offsets, np.int64)
            idx = np.nonzero(mask)[0]
            lens = off[1:] - off[:-1]
            new_off = np.zeros(len(idx) + 1, np.int32)
            np.cumsum(lens[idx], out=new_off[1:])
            vals = (
                np.concatenate(
                    [
                        np.asarray(col.values)[off[i]: off[i + 1]]
                        for i in idx
                    ]
                )
                if len(idx)
                else np.asarray(col.values)[:0]
            )
            out[name] = ArrayColumn(
                offsets=new_off,
                values=vals,
                valid=(
                    None
                    if col.valid is None
                    else np.asarray(col.valid)[: len(mask)][mask]
                ),
                dict_values=col.dict_values,
            )
            continue
        if isinstance(col, MaskedColumn):
            out[name] = MaskedColumn(
                data=np.asarray(col.data)[: len(mask)][mask],
                valid=np.asarray(col.valid)[: len(mask)][mask],
                values=col.values,
            )
        elif isinstance(col, DictColumn):
            out[name] = DictColumn(
                ids=np.asarray(col.ids)[: len(mask)][mask],
                values=col.values,
            )
        else:
            out[name] = np.asarray(col)[: len(mask)][mask]
    return out


def _spill_partial(
    spill, payload, schema, key_names, nrows, n_buckets
) -> None:
    if n_buckets == 1 or not key_names:
        spill[0].append((_truncate_payload(payload, schema, nrows),
                         schema, nrows))
        return
    buckets = _bucket_of(payload, key_names, nrows, n_buckets)
    for b in np.unique(buckets):
        mask = buckets == b
        sliced = _slice_payload(payload, schema, mask)
        spill[int(b)].append((sliced, schema, int(mask.sum())))


def _truncate_payload(payload, schema, nrows) -> Dict:
    mask = np.ones(nrows, dtype=bool)
    return _slice_payload(payload, schema, mask)
