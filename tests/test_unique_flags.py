"""The manifest owns a cold table's uniqueness (ISSUE 31): after every
``TableStore.append``, whoever calls it and with no ``unique=`` passed,
the manifest's ``unique`` flags are current, so a table that is not in
RAM plans its PK joins as lookups (``Table.is_unique`` answers from the
manifest alone). The rule: integer-kind columns that are not nullable
count; over no stored rows a column is unique iff its values are
distinct; on a later append it stays unique iff it was flagged, the tail
has no repeat and the tail does not meet the stored values: decided from
the partitions' min/max where the ranges lie apart (no read), else by
reading that one column of the partitions it may meet.
"""

from __future__ import annotations

import numpy as np
import pytest

import cloudberry_tpu as cb
from cloudberry_tpu import types as T
from cloudberry_tpu.config import Config
from cloudberry_tpu.storage import table_store as TS
from cloudberry_tpu.types import Schema

SCHEMA = Schema.of(k=T.INT64, v=T.INT64)


def _cols(k, v=None) -> dict:
    k = np.asarray(k, dtype=np.int64)
    return {"k": k, "v": np.zeros(len(k), dtype=np.int64) if v is None
            else np.asarray(v, dtype=np.int64)}


def _flags(store, table="t") -> dict:
    return store.read_manifest(table)["unique"]


@pytest.fixture
def store(tmp_path):
    return TS.TableStore(str(tmp_path))


@pytest.fixture
def reads(store, monkeypatch):
    """The (columns, partitions) of every ``read_partitions`` call."""
    seen = []
    real = store.read_partitions

    def read_partitions(table, parts, columns=None, **kw):
        seen.append((tuple(columns), len(parts)))
        return real(table, parts, columns, **kw)
    monkeypatch.setattr(store, "read_partitions", read_partitions)
    return seen


def test_a_bulk_load_keeps_its_ascending_key_flagged_and_reads_nothing(
        store, reads):
    """``replace`` then two appends, as the benchmark's loader and
    ``stream_load_tpch`` write a table: no ``unique=`` anywhere."""
    store.append("t", _cols(np.arange(0, 1000)), SCHEMA, replace=True,
                 rows_per_partition=256)
    assert _flags(store) == {"k": True, "v": False}
    store.append("t", _cols(np.arange(1000, 1500)), SCHEMA,
                 rows_per_partition=256)
    store.append("t", _cols(np.arange(1500, 1501)), SCHEMA,
                 rows_per_partition=256)
    assert _flags(store) == {"k": True, "v": False}
    assert reads == []      # the ranges lie apart: min/max settled it


def test_replace_forgets_the_flags_of_the_rows_it_drops(store):
    store.append("t", _cols([1, 1, 2], [5, 6, 7]), SCHEMA)
    assert _flags(store) == {"k": False, "v": True}
    store.append("t", _cols([3, 4], [8, 8]), SCHEMA, replace=True)
    assert _flags(store) == {"k": True, "v": False}


@pytest.mark.parametrize("tail, stays", [
    ([20, 21, 22], True),
    ([20, 21, 20], False),          # unsorted, the repeat apart
    ([20, 20], False),              # a span narrower than the row count
    ([], True),
], ids=["distinct", "repeat_apart", "narrow_span", "empty"])
def test_a_repeat_inside_a_tail_clears_the_flag(store, reads, tail, stays):
    store.append("t", _cols(np.arange(10)), SCHEMA)
    store.append("t", _cols(tail), SCHEMA)
    assert _flags(store)["k"] is stays
    assert reads == []      # the tail alone, or its range, decided


@pytest.mark.parametrize("tail, stays, read", [
    # the ranges lie apart: nothing to meet, nothing read
    ([40, 41], True, []),
    # the tail lies inside the second partition's range only: that one
    # column of that one partition is read
    ([25], True, [(("k",), 1)]),
    ([24], False, [(("k",), 1)]),
    # it spans both partitions and meets the first
    ([4, 25], False, [(("k",), 2)]),
    ([5, 25], True, [(("k",), 2)]),
], ids=["apart", "inside_no_meet", "inside_meets", "spans_meets",
        "spans_no_meet"])
def test_a_repeat_across_head_and_tail_clears_the_flag(store, reads, tail,
                                                       stays, read):
    store.append("t", _cols([0, 2, 4, 6]), SCHEMA)      # range 0..6
    store.append("t", _cols([20, 22, 24, 26]), SCHEMA)  # range 20..26
    assert _flags(store)["k"] is True and reads == []
    store.append("t", _cols(tail), SCHEMA)
    assert _flags(store)["k"] is stays
    assert reads == read


def test_a_column_once_not_unique_is_never_looked_at_again(store, reads):
    store.append("t", _cols([1, 2, 3]), SCHEMA)
    store.append("t", _cols([3]), SCHEMA)
    assert _flags(store)["k"] is False
    del reads[:]
    store.append("t", _cols([2, 9]), SCHEMA)    # inside the stored range
    assert _flags(store)["k"] is False and reads == []


def test_a_span_narrower_than_the_row_count_needs_no_sort(monkeypatch):
    def no_sort(*a, **kw):
        raise AssertionError("sorted")
    monkeypatch.setattr(TS.np, "unique", no_sort)
    assert TS._distinct(np.asarray([3, 1, 2, 3])) is False
    assert TS._distinct(np.arange(5, 50)) is True       # ascending
    assert TS._distinct(np.asarray([7])) is True
    monkeypatch.undo()
    assert TS._distinct(np.asarray([9, 1, 5])) is True
    assert TS._distinct(np.asarray([9, 1, 5, 1, 20])) is False


def test_a_nullable_column_is_never_flagged(store):
    valid = {"k": np.asarray([True, False, True])}
    store.append("t", _cols([1, 0, 3], [4, 5, 6]), SCHEMA, validity=valid)
    assert _flags(store) == {"v": True}
    # nullable from a later append on: the stored flag goes
    store.append("t", _cols([7], [7]), SCHEMA,
                 validity={"v": np.asarray([False])})
    assert _flags(store) == {"v": False}
    store.append("t", _cols([8], [8]), SCHEMA)
    assert _flags(store) == {"v": False}


def test_only_integer_kind_columns_count(store):
    schema = Schema.of(k=T.INT32, f=T.FLOAT64, s=T.STRING)
    from cloudberry_tpu.columnar.dictionary import StringDictionary

    d = {"s": StringDictionary(["a", "b", "c"])}
    store.append("t", {"k": np.asarray([1, 2, 3], dtype=np.int32),
                       "f": np.asarray([1.0, 2.0, 3.0]),
                       "s": np.asarray([0, 1, 2], dtype=np.int32)},
                 schema, dicts=d)
    # a string column is its dictionary codes: it counts, as it does for
    # save_table; a float column does not
    assert _flags(store) == {"k": True, "s": True}
    # codes carry no min/max in the manifest: the column is read
    store.append("t", {"k": np.asarray([4], dtype=np.int32),
                       "f": np.asarray([4.0]),
                       "s": np.asarray([1], dtype=np.int32)},
                 schema, dicts=d)
    assert _flags(store) == {"k": True, "s": False}


def test_an_explicit_unique_wins(store):
    store.append("t", _cols([1, 1]), SCHEMA, unique={"k": True})
    assert _flags(store) == {"k": True}
    store.append("t", _cols([5, 6]), SCHEMA, unique={})
    assert _flags(store) == {}
    # nothing is known of the stored rows any more: nothing is claimed
    store.append("t", _cols([7, 8]), SCHEMA)
    assert _flags(store) == {"k": False, "v": False}


def test_a_manifest_without_flags_claims_nothing(store):
    """A table written before the flags were kept on every append."""
    store.append("t", _cols([1, 2]), SCHEMA)
    man = store.read_manifest("t")
    del man["unique"]
    store._commit("t", man)
    store.append("t", _cols([3, 4]), SCHEMA)
    assert _flags(store) == {"k": False, "v": False}


def _session(root):
    return cb.Session(Config(n_segments=1).with_overrides(
        **{"storage.root": root}))


def test_a_cold_table_answers_is_unique_from_the_manifest(tmp_path):
    root = str(tmp_path)
    store = TS.TableStore(root)
    store.append("t", _cols(np.arange(100), np.arange(100) % 7), SCHEMA,
                 replace=True)
    store.append("t", _cols(np.arange(100, 150), np.arange(50) % 7), SCHEMA)
    t = _session(root).catalog.table("t")
    assert t.cold
    assert t.is_unique("k") and not t.is_unique("v")
    t.ensure_loaded()           # and the data agrees
    assert not t.cold
    assert t.is_unique("k") and not t.is_unique("v")


@pytest.mark.parametrize("rows, flag", [
    ("(4), (5)", True),         # beyond the stored range
    ("(0)", True),              # before it
    ("(2)", False),             # meets a stored row
    ("(7), (7)", False),        # repeats itself
])
def test_insert_keeps_the_flags_it_kept_before(tmp_path, rows, flag):
    """The catalog's incremental append passes nothing: the store's rule
    gives what the catalog's own gave."""
    root = str(tmp_path)
    s = _session(root)
    s.sql("create table k (id bigint, w bigint) distributed by (id)")
    s.sql("insert into k values (1, 1), (2, 1), (3, 1)")
    assert _flags(s.catalog.store, "k") == {"id": True, "w": False}
    s.sql(f"insert into k (id, w) values "
          f"{rows.replace(')', ', 1)')}")
    assert _flags(s.catalog.store, "k") == {"id": flag, "w": False}
    cold = _session(root).catalog.table("k")
    assert cold.cold and cold.is_unique("id") is flag
