import numpy as np
import pytest

from cloudberry_tpu import types as T
from cloudberry_tpu.columnar.dictionary import StringDictionary
from cloudberry_tpu.storage import micropartition as mp
from cloudberry_tpu.storage.table_store import TableStore
from cloudberry_tpu.types import Schema


@pytest.fixture
def schema():
    return Schema.of(k=T.INT64, v=T.DECIMAL(2), s=T.STRING, d=T.DATE)


def _data(n, seed=0):
    rng = np.random.default_rng(seed)
    d = StringDictionary()
    return {
        "k": np.arange(n, dtype=np.int64),
        "v": rng.integers(0, 10_000, n).astype(np.int64),
        "s": d.encode(rng.choice(["aa", "bb", "cc"], n)),
        "d": rng.integers(8000, 9000, n).astype(np.int32),
    }, {"s": d}


def test_micropartition_roundtrip(tmp_path, schema):
    data, dicts = _data(1000)
    path = str(tmp_path / "p1.cbmp")
    footer = mp.write_micropartition(path, data, schema, dicts)
    assert footer["num_rows"] == 1000
    got = mp.read_columns(path)
    for k in data:
        np.testing.assert_array_equal(got[k], data[k])
    # column projection reads only what's asked
    got_k = mp.read_columns(path, ["k"])
    assert set(got_k) == {"k"}
    # stats present and correct
    f2 = mp.read_footer(path)
    kcol = next(c for c in f2["columns"] if c["name"] == "k")
    assert kcol["min"] == 0 and kcol["max"] == 999
    scol = next(c for c in f2["columns"] if c["name"] == "s")
    assert scol["dictionary"] == dicts["s"].values


def test_rle_kicks_in(tmp_path, schema):
    data, dicts = _data(10_000)
    data["v"] = np.full(10_000, 777, dtype=np.int64)  # constant → RLE
    path = str(tmp_path / "p2.cbmp")
    footer = mp.write_micropartition(path, data, schema, dicts)
    vcol = next(c for c in footer["columns"] if c["name"] == "v")
    assert vcol["encoding"] == "rle"
    assert vcol["length"] < 200
    got = mp.read_columns(path, ["v"])
    assert (got["v"] == 777).all()


def test_prune_by_stats(tmp_path, schema):
    data, dicts = _data(100)
    path = str(tmp_path / "p3.cbmp")
    mp.write_micropartition(path, data, schema, dicts)
    f = mp.read_footer(path)
    assert mp.prune_by_stats(f, "k", lo=50, hi=60)
    assert not mp.prune_by_stats(f, "k", lo=1000, hi=None)
    assert not mp.prune_by_stats(f, "k", lo=None, hi=-1)
    assert mp.prune_by_stats(f, "nosuchcol", lo=0, hi=0)


def test_corrupt_file_detected(tmp_path, schema):
    data, dicts = _data(10)
    path = str(tmp_path / "p4.cbmp")
    mp.write_micropartition(path, data, schema, dicts)
    with open(path, "r+b") as fh:
        fh.seek(0)
        fh.write(b"XXXXXXXX")
    with pytest.raises(ValueError):
        mp.read_footer(path)


def test_store_append_scan_snapshot(tmp_path, schema):
    store = TableStore(str(tmp_path))
    d1, dicts = _data(500)
    v1 = store.append("t", d1, schema, dicts, rows_per_partition=200)
    assert v1 == 1
    cols, sch, dd = store.scan("t")
    assert len(cols["k"]) == 500
    assert sch.names == schema.names
    assert dd["s"].values == dicts["s"].values

    d2, _ = _data(300, seed=1)
    d2["s"] = dicts["s"].encode(np.asarray(["aa"] * 300))
    d2["k"] = d2["k"] + 10_000
    v2 = store.append("t", d2, schema, dicts)
    assert v2 == 2
    cols2, _, _ = store.scan("t")
    assert len(cols2["k"]) == 800
    # time travel: old snapshot still sees 500 rows
    old, _, _ = store.scan("t", version=1)
    assert len(old["k"]) == 500


def test_store_prune_and_delete(tmp_path, schema):
    store = TableStore(str(tmp_path))
    d1, dicts = _data(1000)
    store.append("t", d1, schema, dicts, rows_per_partition=100)
    # prune: only partitions overlapping k in [250, 260] are read
    cols, _, _ = store.scan("t", prune={"k": (250, 260)})
    assert len(cols["k"]) == 100  # exactly one 100-row partition survives
    assert 250 in cols["k"] and 260 in cols["k"]

    # delete-vector semantics (visimap analog)
    store.delete_rows("t", lambda c: c["k"] % 2 == 0)
    cols2, _, _ = store.scan("t")
    assert len(cols2["k"]) == 500
    assert (cols2["k"] % 2 == 1).all()
    # old snapshot unaffected (snapshot isolation)
    cols3, _, _ = store.scan("t", version=1)
    assert len(cols3["k"]) == 1000


def test_session_persistence_roundtrip(tmp_path):
    import cloudberry_tpu as cb

    s = cb.Session()
    s.sql("create table m (a bigint, b decimal(10,2), c text) distributed by (a)")
    s.sql("insert into m values (1, 1.5, 'x'), (2, 2.5, 'y'), (3, 3.5, 'x')")
    store = TableStore(str(tmp_path))
    store.save_table(s.catalog.table("m"))

    s2 = cb.Session()
    store.load_table(s2.catalog, "m")
    df = s2.sql("select c, sum(b) as t from m group by c order by c").to_pandas()
    assert df["c"].tolist() == ["x", "y"]
    assert df["t"].tolist() == [5.0, 2.5]


def test_append_dict_must_extend(tmp_path, schema):
    store = TableStore(str(tmp_path))
    d1, dicts = _data(50)
    store.append("t", d1, schema, dicts)
    bad = StringDictionary(["zz"])  # not an extension
    d2, _ = _data(50, seed=2)
    d2["s"] = np.zeros(50, dtype=np.int32)
    with pytest.raises(ValueError):
        store.append("t", d2, schema, {"s": bad})
    # extension is fine
    ext = StringDictionary(dicts["s"].values + ["dd"])
    d2["s"] = np.full(50, 3, dtype=np.int32)
    store.append("t", d2, schema, {"s": ext})
    cols, _, dd = store.scan("t")
    assert dd["s"].values[-1] == "dd"
    assert len(cols["k"]) == 100


def test_partition_reads_parse_manifest_and_footer_once(tmp_path, schema,
                                                        monkeypatch):
    """A tile feed reads each partition on its own, statement after
    statement: the manifest's schema and each file's footer are parsed
    once a file, and a new version is parsed anew."""
    import json

    store = TableStore(str(tmp_path))
    d1, dicts = _data(500)
    store.append("t", d1, schema, dicts, rows_per_partition=200)
    parts = store.read_manifest("t")["partitions"]
    parsed = []
    loads = json.loads
    monkeypatch.setattr(json, "loads",
                        lambda raw, **kw: parsed.append(1) or loads(raw))
    for _ in range(3):
        cols, _ = store.read_partitions("t", parts[:1], ["k", "v"])
        np.testing.assert_array_equal(cols["k"], d1["k"][:200])
    assert len(parsed) == 2           # the manifest and one footer
    store.read_partitions("t", parts[1:2], ["k"])
    assert len(parsed) == 3           # the second file's footer
    d2, _ = _data(100, seed=1)
    d2["s"] = dicts["s"].encode(np.asarray(["aa"] * 100))
    store.append("t", d2, schema, dicts)
    parsed.clear()
    store.read_partitions("t", parts[:1], ["k"])
    assert len(parsed) == 1           # version 2's manifest
