"""The connector's own host columns, and the comparisons that decide
``correct``. Copied in spirit from ``chip_smoke.py`` (PR 22) so that a
later change to the smoke cannot move the yardstick.

Columns are read split by split through the connector SPI — nothing of
the engine's planner, executor or staging is involved — and kept for
the run, so every reference of a run reads one copy.
"""

from __future__ import annotations

import datetime
import decimal
from typing import Dict, Sequence, Tuple

import numpy as np


def day(y: int, m: int, d: int) -> int:
    """A date as the engine stores it: days since 1970-01-01."""
    return (datetime.date(y, m, d) - datetime.date(1970, 1, 1)).days


def iso(days: int) -> str:
    return (datetime.date(1970, 1, 1) + datetime.timedelta(days=int(days))).isoformat()


def same_sum(got, want: int, scale: int) -> bool:
    """Exact equality of a scaled-int64 sum. A float the client printed
    is ``int / 10**scale`` correctly rounded (``page.py``), so past
    2**53 the comparison is of that same division; anything else (a
    long decimal printed as text) is compared as the unscaled integer."""
    if got is None:
        return False
    if isinstance(got, float):
        return got == want / 10 ** scale
    return decimal.Decimal(str(got)).scaleb(scale) == want


def close(a: float, b: float, rel: float = 1e-9) -> bool:
    return abs(a - b) <= rel * max(abs(a), abs(b), 1e-300)


class HostData:
    """Row counts and host columns of one schema of one connector."""

    def __init__(self, connector, catalog: str, schema: str):
        self.connector = connector
        self.catalog = catalog
        self.schema = schema
        self._cols: Dict[Tuple[str, str], np.ndarray] = {}
        self._dicts: Dict[Tuple[str, str], np.ndarray] = {}

    def _splits(self, table: str):
        from presto_tpu.connectors.spi import TableHandle

        src = self.connector.get_splits(TableHandle(self.catalog, self.schema, table))
        while not src.exhausted:
            yield from src.next_batch(64)

    def rows(self, table: str) -> int:
        return sum(s.row_end - s.row_start for s in self._splits(table))

    def columns(self, table: str, columns: Sequence[str]):
        """``(cols, dicts)``: numpy arrays by column name; a varchar
        column is its int32 ids, with the dictionary under ``dicts``."""
        missing = [c for c in columns if (table, c) not in self._cols]
        if missing:
            parts = {c: [] for c in missing}
            for split in self._splits(table):
                got = self.connector.create_page_source(split, list(missing))
                for c in missing:
                    v = got[c]
                    if hasattr(v, "ids"):
                        values = np.asarray(v.values, dtype=object)
                        prev = self._dicts.setdefault((table, c), values)
                        if prev is not values and not np.array_equal(prev, values):
                            raise AssertionError(
                                f"{table}.{c}: the dictionary differs between splits"
                            )
                        v = v.ids
                    parts[c].append(np.asarray(v))
            for c in missing:
                self._cols[(table, c)] = np.concatenate(parts[c])
        cols = {c: self._cols[(table, c)] for c in columns}
        dicts = {c: self._dicts[(table, c)] for c in columns if (table, c) in self._dicts}
        return cols, dicts

    def nbytes(self, tables: Dict[str, Sequence[str]]) -> int:
        """Bytes of the host columns a statement has to read, each once."""
        return int(sum(
            a.nbytes for t, cs in tables.items() for a in self.columns(t, cs)[0].values()
        ))
