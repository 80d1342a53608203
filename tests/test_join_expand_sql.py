"""Every caller of ``Lowerer._expand_pairs`` against pandas (ISSUE 35:
the pair buffer's slot map serves them all, not Q13's outer join alone):
a LEFT and a FULL many-to-many join, a semi and an anti join with a
residual, over seeded tables at one segment, once at a capacity the
pairs fit and once where a hot key overflows the estimate and the
statement is answered after ONE ``grow_expansion`` retry."""

from __future__ import annotations

import numpy as np
import pandas as pd
import pytest

import cloudberry_tpu as cb
from cloudberry_tpu.config import Config

QUERIES = {
    "left": "select p.k as pk, b.k as bk, x, y "
            "from p left join b on p.k = b.k",
    "full": "select p.k as pk, b.k as bk, x, y "
            "from p full join b on p.k = b.k",
    "semi": "select k as pk, x from p where exists "
            "(select 1 from b where b.k = p.k and b.y > p.x)",
    "anti": "select k as pk, x from p where not exists "
            "(select 1 from b where b.k = p.k and b.y > p.x)",
}


def _tables(hot: bool) -> tuple[pd.DataFrame, pd.DataFrame]:
    """3,000 probe rows over 500 build rows with duplicate keys on both
    sides and keys only one side has; ``hot``: three probe rows in ten
    and twelve build rows share key 0, more pairs than the planner's
    estimate holds."""
    rng = np.random.default_rng(35)
    n = 3000
    pk = rng.integers(1, 400, n).astype(np.int64)
    if hot:
        pk = np.where(rng.random(n) < 0.3, 0, pk)
    bk = np.concatenate([np.zeros(12, np.int64),
                         rng.integers(1, 300, 500).astype(np.int64)])
    return (pd.DataFrame({"k": pk, "x": rng.integers(0, 100, n)}),
            pd.DataFrame({"k": bk, "y": rng.integers(0, 100, len(bk))}))


def _pandas(kind: str, p: pd.DataFrame, b: pd.DataFrame) -> pd.DataFrame:
    if kind in ("left", "full"):
        m = p.rename(columns={"k": "pk"}).assign(j=p.k).merge(
            b.rename(columns={"k": "bk"}).assign(j=b.k),
            how="left" if kind == "left" else "outer", on="j")
        return m[["pk", "bk", "x", "y"]]
    pairs = p.reset_index().merge(b, on="k")
    hit = p.index.isin(pairs[pairs.y > pairs.x]["index"])
    return p[hit if kind == "semi" else ~hit].rename(columns={"k": "pk"})


def _rows(df: pd.DataFrame) -> list:
    """Rows as sorted tuples, a NULL as -1 (every value is >= 0)."""
    df = df.astype("float64").fillna(-1).astype("int64")
    return sorted(map(tuple, df.to_numpy().tolist()))


@pytest.mark.parametrize("hot", [False, True],
                         ids=["pairs-fit", "one-retry"])
@pytest.mark.parametrize("kind", sorted(QUERIES))
def test_expansion_join_equals_pandas(kind, hot):
    p, b = _tables(hot)
    s = cb.Session(Config(n_segments=1))
    s.sql("create table p (k bigint, x bigint) distributed by (k)")
    s.sql("create table b (k bigint, y bigint) distributed by (k)")
    s.catalog.table("p").set_data({c: p[c].to_numpy() for c in p}, {})
    s.catalog.table("b").set_data({c: b[c].to_numpy() for c in b}, {})
    got = s.sql(QUERIES[kind]).to_pandas()
    want = _pandas(kind, p, b)
    assert _rows(got[list(want.columns)]) == _rows(want)
    # the join was an expansion, and grew exactly when the hot key came
    assert s.growth_events == (1 if hot else 0)
    assert s.stmt_log.counter("launch_joins_expand") == (2 if hot else 1)
