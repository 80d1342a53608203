import numpy as np
import pandas as pd

from cloudberry_tpu.columnar import ColumnBatch, StringDictionary
from cloudberry_tpu.types import DType, Schema


def test_dictionary_roundtrip():
    d = StringDictionary()
    # a call's new values take their codes in sorted order (two loads of
    # data drawn alike then end with the same codes: ISSUE 32); a later
    # call's come after them, whatever their order
    codes = d.encode(np.array(["b", "a", "b", "c"]))
    assert codes.tolist() == [1, 0, 1, 2]
    assert d.decode(codes).tolist() == ["b", "a", "b", "c"]
    assert d.code_of("a") == 0
    assert d.code_of("zzz") == -1
    assert d.encode(["d", "aa", "b"]).tolist() == [4, 3, 1]
    assert d.values == ["a", "b", "c", "aa", "d"]


def test_dictionary_like_and_rank():
    d = StringDictionary(["apple", "banana", "cherry"])
    t = d.like_table("%an%")
    assert t.tolist() == [False, True, False]
    r = d.rank_table()
    assert r.tolist() == [0, 1, 2]
    d2 = StringDictionary(["z", "a", "m"])
    r2 = d2.rank_table()
    assert r2[1] < r2[2] < r2[0]


def test_batch_from_pandas_roundtrip():
    df = pd.DataFrame({
        "k": np.array([1, 2, 3], dtype=np.int64),
        "v": np.array([1.5, 2.5, 3.5]),
        "s": ["x", "y", "x"],
        "d": pd.to_datetime(["1995-01-01", "1996-06-15", "1992-12-31"]),
    })
    b = ColumnBatch.from_pandas(df, capacity=8)
    assert b.capacity == 8
    assert b.num_rows() == 3
    assert b.columns["s"].dtype == np.int32
    out = b.to_pandas()
    assert out["k"].tolist() == [1, 2, 3]
    assert out["s"].tolist() == ["x", "y", "x"]
    assert str(out["d"].iloc[1])[:10] == "1996-06-15"


def test_schema_of():
    s = Schema.of(a=DType.INT64, b=DType.STRING)
    assert s.names == ["a", "b"]
    assert "a" in s and "c" not in s
