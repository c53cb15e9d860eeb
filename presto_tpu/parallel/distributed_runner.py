"""Distributed query runner: shard_map fragments over a device mesh.

Reference parity: the DistributedQueryRunner test harness + the
scheduler/worker split it exercises — a stage is N identical tasks over
hash-partitioned data, exchanges move rows between stages, the root
stage gathers (SURVEY.md §2.4, §3.2, §4.3).

TPU-first redesign (SURVEY.md §7 step 6): a "stage" is not N processes —
it is ONE compiled program ``shard_map``-ed over the mesh axis
``workers``. Every exchange the reference does over HTTP happens inside
the program as an ICI collective (see presto_tpu.parallel.exchange):

- table scans are row-sharded across workers (split parallelism),
- grouped aggregation runs partial-per-shard, repartitions partial
  states by key hash (``all_to_all``), then merges (the reference's
  PARTIAL/FINAL step split),
- joins choose broadcast (``all_gather`` the build side) vs partitioned
  (``all_to_all`` both sides on the key) — the reference's
  AddExchanges REPLICATED vs PARTITIONED join decision,
- the root fragment (final sort/limit/window/output) runs single-device
  over the gathered fragment output, like the reference's
  single-partition root stage.

Each subtree carries a distribution: 'part' (rows split across workers)
or 'repl' (every worker holds identical rows). Replicated results are
gathered by taking shard 0; partitioned results concatenate shards.

Correctness CI runs this on 8 virtual CPU devices (tests/conftest.py);
the same code path compiles for a real TPU slice mesh.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map as _shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from presto_tpu import expr as E
from presto_tpu.exec.local_runner import (
    ExecutionError,
    LocalQueryRunner,
    _scale_capacities,
    cross_join_single_row,
)
from presto_tpu.exec.staging import bucket_capacity, stage_page
from presto_tpu.ops import (
    distinct as distinct_op,
    filter_project,
    hash_aggregate,
    hash_join,
    project,
)
from presto_tpu.page import Block, Page, compact_page
from presto_tpu.parallel.agg_split import split_aggregation
from presto_tpu.parallel.exchange import (
    gather_stacked,
    partition_exchange,
    partition_hash,
    replicate,
)
from presto_tpu.parallel.fragmenter import insert_gathers
from presto_tpu.plan import nodes as N

_AXIS = "workers"


def _ceil_div(a: int, b: int) -> int:
    return -(-a // b)


#: jit cache for the gather step, shared across queries and runners —
#: one compiled program per (leaf shapes, shard_cap, replicated) combo.
_gather_jit = jax.jit(gather_stacked, static_argnums=(2, 3))


class DistributedQueryRunner(LocalQueryRunner):
    """LocalQueryRunner whose distributable plan subtrees execute as one
    shard_map program over an ``n_devices``-wide mesh."""

    def __init__(
        self,
        n_devices: Optional[int] = None,
        devices: Optional[list] = None,
        catalogs=None,
        session=None,
        broadcast_threshold: int = 1 << 16,
        repl_threshold: int = 1 << 13,
    ):
        super().__init__(catalogs=catalogs, session=session)
        if n_devices is None:
            # hash_partition_count session property (reference: the
            # fixed hash-distribution width; 0 = use every device)
            hpc = int(self.session.get("hash_partition_count"))
            if hpc > 0:
                n_devices = hpc
        if devices is None:
            devices = jax.devices()
            if n_devices is not None:
                devices = devices[: n_devices]
        self.devices = list(devices)
        self.n = len(self.devices)
        self.mesh = Mesh(np.array(self.devices), (_AXIS,))
        self.broadcast_threshold = broadcast_threshold
        self.repl_threshold = repl_threshold
        self._frag_compiled: Dict[tuple, tuple] = {}
        self._shard_cache: Dict[tuple, Page] = {}

    # ---------------------------------------------------------------- run

    def execute_plan(self, plan, qs=None):
        # the mesh fragment executor (_exec_dist inside shard_map) has
        # no parameter-vector plumbing: materialize statement-cache
        # plans back to literal form first — the statement cache still
        # skips planning, and _run_with_pages re-hoists the non-mesh
        # parts; _frag_compiled keeps literal keys (documented limit)
        from presto_tpu.plan import canonical

        return super().execute_plan(
            canonical.materialize_plan(plan), qs=qs
        )

    def _run(self, root: N.PlanNode) -> Page:
        if self.n == 1:
            return super()._run(root)
        froot = insert_gathers(root)
        sources = [
            n
            for n in N.walk(froot)
            if isinstance(n, (N.TableScanNode, N.RemoteSourceNode))
        ]
        pages: List[Page] = []
        for s in sources:
            if isinstance(s, N.RemoteSourceNode):
                pages.append(self._run_fragment(s.fragment_root))
            else:
                pages.append(self._load_table(s))
        return self._run_with_pages(froot, sources, pages)

    # ----------------------------------------------------- fragment stage

    def _run_fragment(self, froot: N.PlanNode) -> Page:
        scans = [n for n in N.walk(froot) if isinstance(n, N.TableScanNode)]
        tables = [self._load_table_sharded(s) for s in scans]
        balance = 2
        tries = 0
        root = froot
        while True:
            out, flags, err_flags, meta = self._execute_fragment(
                root, scans, tables, balance
            )
            for msg, flag in zip(meta["errors"], err_flags):
                if bool(np.any(np.asarray(flag))):
                    raise ExecutionError(msg)
            if not any(bool(np.any(np.asarray(f))) for f in flags):
                counts = out.num_valid  # (n,)
                shard_cap = out.capacity // self.n
                return self._gather(
                    out, counts, shard_cap, meta["dist"] == "repl"
                )
            tries += 1
            if tries >= self.MAX_RETRIES:
                raise ExecutionError(
                    "capacity overflow persisted after distributed retries"
                )
            root = _scale_capacities(root, 4)
            balance *= 2

    def _execute_fragment(self, root, scans, tables, balance):
        key = (root.fingerprint(), balance, self.n)
        entry = self._frag_compiled.get(key)
        if entry is None:
            scan_ids = {id(s): i for i, s in enumerate(scans)}
            meta: dict = {}

            def prog(pages_in):
                local = [
                    dataclasses.replace(p, num_valid=p.num_valid[0])
                    for p in pages_in
                ]
                flags: List = []
                errors: List = []
                out, dist = self._exec_dist(
                    root, local, scan_ids, flags, errors, balance
                )
                meta["dist"] = dist
                meta["errors"] = [m for m, _ in errors]
                # fragment boundary: gather_stacked treats num_valid as a
                # per-shard prefix count, so lazy masks stop here
                out = compact_page(out)
                out = dataclasses.replace(
                    out, num_valid=out.num_valid.reshape(1)
                )
                return (
                    out,
                    tuple(f.reshape(1) for f in flags),
                    tuple(e.reshape(1) for _, e in errors),
                )

            mapped = _shard_map(
                prog,
                mesh=self.mesh,
                in_specs=(P(_AXIS),),
                out_specs=P(_AXIS),
            )
            fn = jax.jit(mapped)
            entry = (fn, meta)
            self._frag_compiled[key] = entry
        fn, meta = entry
        from presto_tpu.exec.staging import stage_sharded

        sharding = NamedSharding(self.mesh, P(_AXIS))
        pages_in = stage_sharded(tables, sharding)
        out, flags, err_flags = fn(pages_in)
        return out, flags, err_flags, meta

    def _gather(self, out, counts, shard_cap, replicated) -> Page:
        return _gather_jit(out, counts, shard_cap, replicated)

    # -------------------------------------------------- sharded staging

    def _load_table_sharded(self, scan: N.TableScanNode) -> Page:
        from presto_tpu.connectors.spi import payload_len

        # constraint in the key: a partition-pruned page must never
        # serve a differently-constrained scan (same hazard as the
        # local _load_table cache)
        key = (scan.handle, scan.columns, scan.constraint, self.n)
        table = self._shard_cache.get(key)
        total = None
        if table is None:
            merged = self._load_merged_payload(scan)
            total = payload_len(next(iter(merged.values())))
            chunk = max(_ceil_div(total, self.n), 1)
            shard_cap = bucket_capacity(chunk)
            schema = dict(scan.schema)
            shard_pages = []
            for i in range(self.n):
                lo, hi = min(i * chunk, total), min((i + 1) * chunk, total)
                payload = {
                    c: _slice_col(v, lo, hi) for c, v in merged.items()
                }
                shard_pages.append(stage_page(payload, schema, shard_cap))
            table = _stack_shards(shard_pages)
            if self.catalogs.get(scan.handle.catalog).cacheable():
                self._shard_cache[key] = table
        if self._active_qs is not None:
            self._active_qs.input_rows += int(np.sum(np.asarray(table.num_valid)))
            self._active_qs.input_bytes += sum(
                int(b.data.nbytes) for b in table.blocks
            )
        return table

    # -------------------------------------- distribution-aware execution

    def _exec_dist(
        self, node, pages, scan_ids, flags, errors, balance
    ) -> Tuple[Page, str]:
        rec = lambda c: self._exec_dist(  # noqa: E731
            c, pages, scan_ids, flags, errors, balance
        )
        nw = self.n

        if isinstance(node, N.TableScanNode):
            return pages[scan_ids[id(node)]], "part"

        if isinstance(node, N.FilterNode):
            src, d = rec(node.source)
            schema = node.source.output_schema()
            projs = [(n_, E.ColumnRef(n_, t)) for n_, t in schema.items()]
            return filter_project(src, node.predicate, projs), d

        if isinstance(node, N.ProjectNode):
            src, d = rec(node.source)
            return project(src, node.projections), d

        if isinstance(node, N.AggregationNode):
            return self._exec_agg(node, rec, flags, balance)

        if isinstance(node, N.DistinctNode):
            return self._exec_distinct(node, rec, flags, balance)

        if isinstance(node, N.JoinNode):
            return self._exec_join(node, rec, flags, balance)

        if isinstance(node, N.CrossJoinNode):
            left, dl = rec(node.left)
            right, dr = rec(node.right)
            if dr == "part":
                right = replicate(right, nw, _AXIS)
            errors.append(
                (
                    "cross join build produced more than one row",
                    right.num_valid > 1,
                )
            )
            return cross_join_single_row(left, right), dl

        raise ExecutionError(
            f"cannot execute {type(node).__name__} in a sharded fragment"
        )

    def _exec_agg(self, node, rec, flags, balance):
        nw = self.n
        src, d = rec(node.source)
        if d == "repl":
            out, ovf = hash_aggregate(
                src, node.group_keys, node.aggs, node.max_groups
            )
            flags.append(ovf)
            return out, "repl"
        try:
            partial_aggs, fkeys, faggs, post = split_aggregation(
                node.group_keys, node.aggs
            )
        except NotImplementedError:
            # order-sensitive aggregates (array_agg / approx_percentile
            # / min_by / max_by) have no mergeable partial state:
            # replicate the sharded input and aggregate single-node
            # (same fallback the HTTP scheduler takes —
            # server/scheduler.py)
            merged = replicate(src, nw, _AXIS)
            out, ovf = hash_aggregate(
                merged, node.group_keys, node.aggs, node.max_groups
            )
            flags.append(ovf)
            return out, "repl"
        if not node.group_keys:
            part_pg, _ = hash_aggregate(src, (), partial_aggs, 1)
            merged = replicate(part_pg, nw, _AXIS)
            out, _ = hash_aggregate(merged, (), faggs, 1)
            if post:
                out = project(out, post)
            return out, "repl"
        part_pg, ovf = hash_aggregate(
            src, node.group_keys, partial_aggs, node.max_groups
        )
        flags.append(ovf)
        routed, dist = self._route_partials(
            part_pg,
            [n_ for n_, _ in node.group_keys],
            node.max_groups,
            balance,
            flags,
        )
        out, fovf = hash_aggregate(routed, fkeys, faggs, node.max_groups)
        flags.append(fovf)
        if post:
            out = project(out, post)
        return out, dist

    def _exec_distinct(self, node, rec, flags, balance):
        nw = self.n
        src, d = rec(node.source)
        if d == "repl":
            out, ovf = distinct_op(src, node.max_groups)
            flags.append(ovf)
            return out, "repl"
        part_pg, ovf = distinct_op(src, node.max_groups)
        flags.append(ovf)
        routed, dist = self._route_partials(
            part_pg, list(part_pg.names), node.max_groups, balance, flags
        )
        out, fovf = distinct_op(routed, node.max_groups)
        flags.append(fovf)
        return out, dist

    def _route_partials(self, part_pg, key_cols, max_groups, balance, flags):
        """Route partial group/distinct states to their merge worker:
        replicate (all_gather) below repl_threshold, else hash-repartition
        (all_to_all) — every worker merges only its key range."""
        nw = self.n
        if max_groups <= self.repl_threshold:
            return replicate(part_pg, nw, _AXIS), "repl"
        h = partition_hash(part_pg, key_cols)
        dest = (h % jnp.uint64(nw)).astype(jnp.int32)
        bucket_cap = bucket_capacity(_ceil_div(balance * max_groups, nw))
        routed, xovf = partition_exchange(
            part_pg, dest, nw, _AXIS, bucket_cap
        )
        flags.append(xovf)
        return routed, "part"

    def _exec_join(self, node, rec, flags, balance):
        nw = self.n
        probe, dp = rec(node.left)
        build, db = rec(node.right)

        def local_join(p, b):
            out, ovf = hash_join(
                p,
                b,
                node.left_keys,
                node.right_keys,
                join_type=node.join_type,
                build_payload=node.payload,
                build_unique=node.build_unique,
                out_capacity=node.out_capacity,
                payload_rename=dict(node.payload_rename),
            )
            flags.append(ovf)
            if node.residual is not None:
                schema = out.schema()
                projs = [
                    (n_, E.ColumnRef(n_, t)) for n_, t in schema.items()
                ]
                out = filter_project(out, node.residual, projs)
            return out

        if db == "repl":
            return local_join(probe, build), dp
        # join_distribution_type session property (reference:
        # AddExchanges' cost-based choice, overridable per session):
        # AUTOMATIC = capacity threshold, BROADCAST = always replicate
        # the build side, PARTITIONED = always hash-repartition both
        jdt = str(self.session.get("join_distribution_type")).upper()
        broadcast = (
            build.capacity <= self.broadcast_threshold
            if jdt == "AUTOMATIC"
            else jdt == "BROADCAST"
        )
        if dp == "repl" or broadcast:
            # REPLICATED join: all_gather the build side (AddExchanges'
            # broadcast choice for small builds)
            return local_join(probe, replicate(build, nw, _AXIS)), dp
        # PARTITIONED join: all_to_all both sides on the key hash
        hp = partition_hash(probe, node.left_keys)
        hb = partition_hash(build, node.right_keys)
        cap_p = bucket_capacity(_ceil_div(balance * probe.capacity, nw))
        cap_b = bucket_capacity(_ceil_div(balance * build.capacity, nw))
        p2, o1 = partition_exchange(
            probe, (hp % jnp.uint64(nw)).astype(jnp.int32), nw, _AXIS, cap_p
        )
        b2, o2 = partition_exchange(
            build, (hb % jnp.uint64(nw)).astype(jnp.int32), nw, _AXIS, cap_b
        )
        flags.extend([o1, o2])
        return local_join(p2, b2), "part"


# ------------------------------------------------------------------ helpers


def _slice_col(v, lo: int, hi: int):
    if hasattr(v, "ids"):  # DictColumn: shared closed-form dictionary
        return type(v)(ids=v.ids[lo:hi], values=v.values)
    return v[lo:hi]


def _stack_shards(shard_pages: List[Page]) -> Page:
    """Concatenate per-shard pages into flat stacked leaves; normalizes
    valid masks so every shard agrees on mask presence per column."""
    names = shard_pages[0].names
    blocks: List[Block] = []
    for j, name in enumerate(names):
        blks = [p.blocks[j] for p in shard_pages]
        data = jnp.concatenate([b.data for b in blks])
        if any(b.valid is not None for b in blks):
            valid = jnp.concatenate(
                [
                    b.valid
                    if b.valid is not None
                    else jnp.ones((b.capacity,), jnp.bool_)
                    for b in blks
                ]
            )
        else:
            valid = None
        blocks.append(
            dataclasses.replace(blks[0], data=data, valid=valid)
        )
    num_valid = jnp.stack([p.num_valid for p in shard_pages])
    return Page(blocks=tuple(blocks), num_valid=num_valid, names=names)
