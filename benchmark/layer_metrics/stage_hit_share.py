"""Share of the columns of streamed split batches that the staging
cache held when a batch asked for them, over the traced slice: 100 %
means nothing of the scanned table was read or staged again. ``None``
(the metric is left out) where no batch looked a column up — a table
staged whole never does — and a ``KeyError`` the harness reads the same
way where the program has no such counters."""


def read(obs: dict, spec: dict):
    hits = obs["counters"]["stage_col_hits"]
    looked = hits + obs["counters"]["stage_col_misses"]
    return 100.0 * hits / looked if looked else None
