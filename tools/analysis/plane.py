"""Compile-plane invariant checker: ``plan-params`` and
``history-sites``.

The zero-recompile serving plane (plan/canonical.py) and the
history-based statistics plane (plan/history.py) are only correct
while their privileged constructs stay confined:

- a ``RuntimeParam`` minted outside the canonicalizer bypasses the
  dtype/structure eligibility rules and miscompiles;
- a ``BoundParam`` minted outside it breaks the ordinal<->value
  correspondence the statement cache binds by;
- a compile-cache (``_compiled``) key assembled elsewhere can bake
  literals back in and re-open the compile-per-literal-variant hole;
- a history record, fingerprint, or ``lookup_rows`` call outside the
  store forks the canonical identity and the estimate provenance.

This is the AST successor of ``check_plan_params.py`` +
``check_history_sites.py``: calls are matched as calls (an
``isinstance(x, RuntimeParam)`` or a ``qs.plan_fingerprint`` attribute
read never needed an exemption to begin with), and the two legacy
read-only exemptions for ``_compiled`` (``len(self._compiled)``,
``self._runner._compiled``) are expressed structurally instead of by
line-scrubbing — a disallowed call sharing a line with an exempt read
still flags.
"""

from __future__ import annotations

import ast
from typing import List

from analysis import core

_CANONICAL = "plan/canonical.py"
_RUNNER = "exec/local_runner.py"
_HISTORY = "plan/history.py"

#: call-confinement rules: terminal callee name -> allowed modules
_PLAN_CALLS = {
    "RuntimeParam": {_CANONICAL, "plan/planner.py", "expr.py"},
    "BoundParam": {_CANONICAL, "sql/ast.py"},
    "hoist_params": {_CANONICAL, _RUNNER},
}

_HISTORY_CALLS = {
    "QueryHistoryStore": {_HISTORY, _RUNNER},
    "record_query": {_HISTORY, _RUNNER},
    "lookup_rows": {_HISTORY, "plan/optimizer.py"},
    "node_fingerprint": {
        _HISTORY,
        _RUNNER,
        "exec/explain.py",
        "server/coordinator.py",
    },
    "node_fingerprints": {
        _HISTORY,
        _RUNNER,
        "exec/explain.py",
        "server/coordinator.py",
    },
    "plan_fingerprint": {
        _HISTORY,
        _RUNNER,
        "exec/explain.py",
        "server/coordinator.py",
    },
}


def _exempt_compiled_reads(mod: core.Module) -> set:
    """ids of ``_compiled`` Attribute nodes that are read-only by
    structure: the direct argument of ``len()``, or reached through
    ``self._runner`` (a test/debug peek at the runner's cache)."""
    exempt = set()
    for node in mod.nodes:
        if (
            isinstance(node, ast.Call)
            and isinstance(node.func, ast.Name)
            and node.func.id == "len"
            and len(node.args) == 1
            and isinstance(node.args[0], ast.Attribute)
            and node.args[0].attr == "_compiled"
        ):
            exempt.add(id(node.args[0]))
        elif isinstance(node, ast.Attribute) and node.attr == "_compiled":
            chain = core.dotted_name(node)
            if chain and chain.startswith("self._runner."):
                exempt.add(id(node))
    return exempt


def _confined_calls(modules, rules, rule_id, route_hint):
    findings = []
    for mod in modules:
        for node in mod.nodes:
            if not isinstance(node, ast.Call):
                continue
            term = core.terminal_name(node.func)
            allowed = rules.get(term)
            if allowed is None or mod.rel in allowed:
                continue
            findings.append(
                mod.finding(
                    rule_id,
                    node.lineno,
                    f"{term}() outside its audited modules "
                    f"({', '.join(sorted(allowed))}) — route through "
                    f"{route_hint}",
                )
            )
    return findings


@core.register(
    "plan-params",
    "literal hoisting, RuntimeParam/BoundParam construction, and "
    "compile-cache keying confined to plan/canonical.py + audited "
    "consumers",
)
def plan_params_pass(modules: List[core.Module], src_dir: str):
    findings = _confined_calls(
        modules, _PLAN_CALLS, "plan-params", "presto_tpu.plan.canonical"
    )
    for mod in modules:
        if mod.rel == _RUNNER:
            continue
        exempt = _exempt_compiled_reads(mod)
        for node in mod.nodes:
            if (
                isinstance(node, ast.Attribute)
                and node.attr == "_compiled"
                and id(node) not in exempt
            ):
                findings.append(
                    mod.finding(
                        "plan-params",
                        node.lineno,
                        "_compiled store access outside "
                        "exec/local_runner.py — compile-cache keys "
                        "are built in exactly one place",
                    )
                )
    return findings


@core.register(
    "history-sites",
    "history records, canonical fingerprints, and estimate-time "
    "lookups confined to plan/history.py + audited consumers",
)
def history_sites_pass(modules: List[core.Module], src_dir: str):
    return _confined_calls(
        modules,
        _HISTORY_CALLS,
        "history-sites",
        "presto_tpu.plan.history",
    )


# ------------------------------------------------------- serving batch

_COORDINATOR = "server/coordinator.py"

#: the micro-batch serving plane is only correct while its privileged
#: constructs stay confined: batch-axis stacking / the vmapped compile
#: entry in plan/canonical.py (a stacking built elsewhere can disagree
#: with the hoisting eligibility rules and CROSS members' answers, not
#: just miss a cache), the batched executor in its one audited caller,
#: and batch-queue key construction in server/coordinator.py (a queue
#: key minted elsewhere could group statements that do not share a
#: compiled program)
_BATCH_CALLS = {
    "stack_param_vectors": {_CANONICAL, _RUNNER},
    "vmap_program": {_CANONICAL, _RUNNER},
    "batch_entry_key": {_CANONICAL, _RUNNER},
    "batch_lanes": {_CANONICAL, _RUNNER},
    "execute_plan_microbatch": {_RUNNER, _COORDINATOR},
    "compact_page_window": {"page.py", _RUNNER},
    "MicrobatchQueue": {_COORDINATOR},
    "_microbatch_key": {_COORDINATOR},
}


# ------------------------------------------------------ exchange plane

_EXCHANGE = "parallel/exchange.py"
_EXCHANGE_SPI = "server/exchange_spi.py"
_SCHEDULER = "server/scheduler.py"
_WORKER = "server/worker.py"

#: the ICI-native shuffle is only correct while its privileged
#: constructs stay confined: device collectives and the exchange
#: kernels in parallel/exchange.py (a bucket hash built elsewhere can
#: silently disagree with the host wire hash and lose rows across
#: partitions on a mixed-transport retry), the segment + emit/fetch
#: surface in server/exchange_spi.py with the worker as its one
#: audited consumer, and transport SELECTION in the scheduler (a
#: transport chosen ad hoc can put an ICI edge across slices, where
#: the segment cannot serve it)
_EXCHANGE_CALLS = {
    "all_to_all": {_EXCHANGE},
    "all_gather": {_EXCHANGE},
    "shard_map": {_EXCHANGE, "parallel/distributed_runner.py"},
    "bucket_dest": {_EXCHANGE, _EXCHANGE_SPI},
    "ici_append": {_EXCHANGE, _EXCHANGE_SPI},
    "ici_partition_counts": {_EXCHANGE, _EXCHANGE_SPI},
    "wire_crc_table": {_EXCHANGE, _EXCHANGE_SPI},
    "partition_exchange": {_EXCHANGE, "parallel/distributed_runner.py"},
    # single-program collective kernels: constructed in
    # parallel/exchange.py, driven only by the exchange SPI
    "collective_counts": {_EXCHANGE, _EXCHANGE_SPI},
    "collective_gather": {_EXCHANGE, _EXCHANGE_SPI},
    "collective_take": {_EXCHANGE, _EXCHANGE_SPI},
    "IciSegment": {_EXCHANGE_SPI},
    "emit_partitioned": {_EXCHANGE_SPI, _WORKER},
    "emit_gather": {_EXCHANGE_SPI, _WORKER},
    "ici_fetch": {_EXCHANGE_SPI, _WORKER},
    "device_merge": {_EXCHANGE_SPI, _WORKER},
    "collective_merge": {_EXCHANGE_SPI, _WORKER},
    "collective_payloads": {_EXCHANGE_SPI, _WORKER},
    "ici_batches_to_payloads": {_EXCHANGE_SPI, _WORKER},
    "serialize_ici_frames": {_EXCHANGE_SPI, _WORKER},
    "buffer_frames": {_EXCHANGE_SPI, _WORKER},
    # the coordinator's half of the ICI gather edge
    "ici_gather": {_EXCHANGE_SPI, "server/coordinator.py"},
    "select_exchange_transport": {_SCHEDULER, "server/coordinator.py"},
    "select_exchange_edges": {_SCHEDULER, "server/coordinator.py"},
}


@core.register(
    "exchange-plane",
    "collective construction and ICI exchange kernels confined to "
    "parallel/exchange.py, the segment/emit/fetch surface to "
    "server/exchange_spi.py (+ the worker), transport selection to "
    "the scheduler",
)
def exchange_plane_pass(modules: List[core.Module], src_dir: str):
    return _confined_calls(
        modules,
        _EXCHANGE_CALLS,
        "exchange-plane",
        "presto_tpu.parallel.exchange / "
        "presto_tpu.server.exchange_spi / the scheduler",
    )


# ----------------------------------------------------- adaptive plane

_DYNFILTER = "exec/dynfilter.py"
_OPTIMIZER = "plan/optimizer.py"

#: the adaptive-execution plane is only correct while its privileged
#: constructs stay confined: epoch reads/bumps and the shared
#: divergence test live in plan/history.py (an epoch minted elsewhere
#: would desynchronize every staleness judgement), the statement-cache
#: replan seam in plan/canonical.py with the runner as its one audited
#: consumer (a replan decided elsewhere could serve a plan whose
#: consulted evidence was never captured), and runtime strategy-switch
#: construction in the coordinator + exec/dynfilter.py (a switch built
#: elsewhere could bypass the fail-open discipline and turn a wrong
#: estimate into a failed query)
_ADAPTIVE_CALLS = {
    # epoch plane: reads confined to history + the replan seam
    "epoch_of": {_HISTORY, _CANONICAL},
    "learned_rows": {_HISTORY, _CANONICAL},
    # the ONE divergence test both layers share
    "diverged": {_HISTORY, _CANONICAL, _DYNFILTER, _COORDINATOR},
    # consult capture: the runner wraps canonical planning in it;
    # the optimizer notes the classic fallback estimate
    "capture_consults": {_HISTORY, _RUNNER},
    "note_estimate": {_HISTORY, _OPTIMIZER},
    "with_overrides": {_HISTORY, _COORDINATOR},
    # the replan seam and its audited consumer
    "stale_consults": {_CANONICAL, _RUNNER},
    "_adaptive_replan": {_RUNNER},
    # runtime strategy-switch construction
    "_adaptive_maybe_switch": {_COORDINATOR},
    "_adaptive_probe_build": {_COORDINATOR},
    "_adaptive_nparts": {_COORDINATOR},
    "_adaptive_note": {_COORDINATOR},
}


@core.register(
    "adaptive-plane",
    "adaptive-execution constructs confined: epoch reads/bumps and "
    "the divergence test to plan/history.py, the replan seam to "
    "plan/canonical.py (+ the runner), strategy-switch construction "
    "to the coordinator and exec/dynfilter.py",
)
def adaptive_plane_pass(modules: List[core.Module], src_dir: str):
    return _confined_calls(
        modules,
        _ADAPTIVE_CALLS,
        "adaptive-plane",
        "presto_tpu.plan.history / presto_tpu.plan.canonical / the "
        "coordinator's adaptive seam",
    )


@core.register(
    "serving-batch",
    "micro-batch constructs confined: batch-axis stacking and vmap "
    "entries to plan/canonical.py, batch-queue keys to "
    "server/coordinator.py",
)
def serving_batch_pass(modules: List[core.Module], src_dir: str):
    findings = _confined_calls(
        modules,
        _BATCH_CALLS,
        "serving-batch",
        "presto_tpu.plan.canonical / the coordinator batch queue",
    )
    # raw vmap anywhere outside the canonicalizer is a batch-axis
    # construction site by definition
    for mod in modules:
        if mod.rel == _CANONICAL:
            continue
        for node in mod.nodes:
            if (
                isinstance(node, ast.Call)
                and core.terminal_name(node.func) == "vmap"
            ):
                findings.append(
                    mod.finding(
                        "serving-batch",
                        node.lineno,
                        "raw vmap — batched program entries are "
                        "constructed only by plan/canonical.py "
                        "(vmap_program)",
                    )
                )
    return findings


_TELEMETRY = "utils/telemetry.py"
_DEVICEDIAG = "utils/devicediag.py"
_STAGING = "exec/staging.py"

#: the device-plane numbers are only trustworthy while their
#: increment sites stay the audited choke points: a rogue
#: ``count_dispatch`` in a connector would double-count the plane the
#: ROADMAP's "dispatch counts visibly down" is judged by, a second
#: DeviceTelemetry instance would fork the counters the bench diffs,
#: and a sampler/federation constructed outside the coordinator would
#: sample a registry no system table serves. (bench.py and tests are
#: outside the analyzed tree; they consume snapshots, not counters.)
_TELEMETRY_CALLS = {
    # the ONE instance lives in utils/telemetry.py (module singleton)
    "DeviceTelemetry": {_TELEMETRY},
    # federation/sampler construction: the coordinator's boot seam
    "MetricsFederation": {_TELEMETRY, _COORDINATOR},
    "MetricsSampler": {_TELEMETRY, _COORDINATOR},
    # increment choke points (the exchange SPI counts its collective
    # and gather dispatches through the same audited name)
    "count_dispatch": {_TELEMETRY, _RUNNER, _EXCHANGE_SPI},
    "count_compile": {_TELEMETRY, _RUNNER},
    "count_program_out": {_TELEMETRY, _RUNNER},
    "count_sync": {_TELEMETRY, _RUNNER},
    "count_agg_page": {_TELEMETRY, _RUNNER},
    "count_h2d": {_TELEMETRY, _STAGING},
    "count_d2h": {_TELEMETRY, _RUNNER, _STAGING, _EXCHANGE_SPI},
    "count_padding": {_TELEMETRY, _RUNNER, _STAGING},
    # per-query attribution fold: the runner's locked seam
    "_fold_device_stat": {_RUNNER},
    # structured diagnosis: probes from the worker boot seam only
    # (the bench rides the same helper from outside the tree);
    # recording is the probe's own epilogue
    "probe_backend": {_DEVICEDIAG, _WORKER},
    "record_diag": {_DEVICEDIAG},
    # the history-derived progress denominator: kept inside
    # plan/history.py (the lookup_rows confinement) with the
    # coordinator as its one consumer
    "progress_total_rows": {_HISTORY, _COORDINATOR},
}


@core.register(
    "telemetry-plane",
    "device-telemetry constructs confined: counter increments to the "
    "runner/staging/exchange choke points, sampler+federation "
    "construction to the coordinator, probes to the worker boot seam",
)
def telemetry_plane_pass(modules: List[core.Module], src_dir: str):
    return _confined_calls(
        modules,
        _TELEMETRY_CALLS,
        "telemetry-plane",
        "presto_tpu.utils.telemetry (DEVICE) / the coordinator's "
        "telemetry seam",
    )
