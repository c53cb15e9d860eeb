"""How far runs of the same code lie apart, by the driver's rules, and
whether a bound of ``BENCHMARK.json`` stands on the runs it was set
from (``tests/data/spreads.json``, ``tests/test_spread.py``).

Two readings of one set of runs, both as shares of the set's median:

- ``iqr_share``: the distance between the first and the third quartile
  as ``statistics.quantiles(values, n=4)`` gives them — the contract's
  spread, taken over all the runs when a bound is judged too loose
  (it may be at most ``LOOSE`` times the widest one);
- ``spread_share``: the range of the set after leaving out the one run
  farthest from its median, where that narrows it — what the driver's
  refusal of PR 33 measured against the bound (``PERF_LEDGER.jsonl``).
  The driver admits a new cell where the mean of its two sets is at
  most half of the bound; the benchmark holds itself to ``MARGIN``,
  for another machine.
  A range is never narrower than the quartiles' distance of the same
  runs, so a bound that stands on it stands on either reading.

Nothing here is read by a run: ``run.py`` and the harness do not import
this file.
"""

from __future__ import annotations

import statistics
from typing import Dict, List, Sequence

MARGIN = 0.4  # what this benchmark asks of its own sets (PERF.md section 2)
LOOSE = 8.0  # the driver: bound over the widest quartile distance it reads


def iqr(values: Sequence[float]) -> float:
    q1, _, q3 = statistics.quantiles(values, n=4)
    return q3 - q1


def iqr_share(values: Sequence[float]) -> float:
    return iqr(values) / statistics.median(values)


def without_farthest(values: Sequence[float]) -> List[float]:
    """The set less the one run farthest from its median, where that
    narrows the range; the set itself where it has under three runs."""
    v = sorted(values)
    if len(v) < 3:
        return v
    m = statistics.median(v)
    rest = v[1:] if m - v[0] > v[-1] - m else v[:-1]
    return rest if rest[-1] - rest[0] < v[-1] - v[0] else v


def spread(values: Sequence[float]) -> float:
    """The driver's rule, in the metric's own unit."""
    v = without_farthest(values)
    return v[-1] - v[0]


def spread_share(values: Sequence[float]) -> float:
    return spread(values) / statistics.median(values)


def cell_summary(sets: Sequence[Sequence[float]]) -> Dict[str, float]:
    """Of one metric in one cell, over its sets of runs: the mean of
    the sets' spreads (driver's rule) and the widest quartile distance,
    each over the median of its set."""
    return {
        "mean_spread_share": statistics.fmean(spread_share(s) for s in sets),
        "widest_iqr_share": max(iqr_share(s) for s in sets),
    }


def smallest_bound(cells: Dict[str, Sequence[Sequence[float]]],
                   candidates: Sequence[float], margin: float = MARGIN) -> float:
    """The smallest candidate under which every cell's mean spread is at
    most ``margin`` of the bound; ``ValueError`` where none holds."""
    worst = max(cell_summary(sets)["mean_spread_share"] for sets in cells.values())
    for b in sorted(candidates):
        if worst <= margin * b:
            return b
    raise ValueError(f"no candidate holds a mean spread of {worst:.4%}")
