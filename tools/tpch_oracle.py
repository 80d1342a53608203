"""pandas reference implementations of TPC-H queries — the expected-output
oracle for correctness tests (the pg_regress expected-file analog, computed
rather than stored so it tracks the generator)."""

from __future__ import annotations

import numpy as np
import pandas as pd


def d(s: str) -> np.datetime64:
    return np.datetime64(s)


def assert_frames_match(got: pd.DataFrame, exp: pd.DataFrame, name: str):
    """An engine answer against an oracle frame, column by position:
    floats within rtol 1e-9 / atol 1e-2 (money is exact cents; averages
    are doubles), everything else exactly, NULLs in the same places.
    Raises AssertionError (never a bare ``assert``: chip_smoke.py's
    verdict must survive ``python -O``)."""
    if len(got) != len(exp):
        raise AssertionError(
            f"{name}: row count {len(got)} != {len(exp)}")
    if len(got.columns) != len(exp.columns):
        raise AssertionError(f"{name}: column count {list(got.columns)} "
                             f"vs {list(exp.columns)}")
    for gcol, ecol in zip(got.columns, exp.columns):
        g, e = got[gcol].to_numpy(), exp[ecol].to_numpy()
        if g.dtype.kind == "f" or e.dtype.kind == "f":
            np.testing.assert_allclose(
                g.astype(np.float64), e.astype(np.float64),
                rtol=1e-9, atol=1e-2, err_msg=f"{name}.{gcol}")
        elif g.dtype == object or e.dtype == object:
            gn, en = pd.isna(g), pd.isna(e)
            np.testing.assert_array_equal(
                gn, en, err_msg=f"{name}.{gcol} (null mask)")
            np.testing.assert_array_equal(
                g[~gn], e[~en], err_msg=f"{name}.{gcol}")
        else:
            np.testing.assert_array_equal(g, e, err_msg=f"{name}.{gcol}")


def q1(t):
    li = t["lineitem"]
    m = li[li.l_shipdate <= d("1998-09-02")].copy()
    m["disc_price"] = m.l_extendedprice * (1 - m.l_discount)
    m["charge"] = m.disc_price * (1 + m.l_tax)
    g = m.groupby(["l_returnflag", "l_linestatus"], as_index=False).agg(
        sum_qty=("l_quantity", "sum"),
        sum_base_price=("l_extendedprice", "sum"),
        sum_disc_price=("disc_price", "sum"),
        sum_charge=("charge", "sum"),
        avg_qty=("l_quantity", "mean"),
        avg_price=("l_extendedprice", "mean"),
        avg_disc=("l_discount", "mean"),
        count_order=("l_quantity", "size"),
    )
    return g.sort_values(["l_returnflag", "l_linestatus"]).reset_index(drop=True)


def q3(t):
    li, od, cu = t["lineitem"], t["orders"], t["customer"]
    j = od.merge(cu[cu.c_mktsegment == "BUILDING"],
                 left_on="o_custkey", right_on="c_custkey")
    j = li.merge(j, left_on="l_orderkey", right_on="o_orderkey")
    j = j[(j.o_orderdate < d("1995-03-15")) & (j.l_shipdate > d("1995-03-15"))]
    j["revenue"] = j.l_extendedprice * (1 - j.l_discount)
    g = j.groupby(["l_orderkey", "o_orderdate", "o_shippriority"],
                  as_index=False)["revenue"].sum()
    g = g.sort_values(["revenue", "o_orderdate"], ascending=[False, True],
                      kind="stable").head(10)
    return g[["l_orderkey", "revenue", "o_orderdate", "o_shippriority"]] \
        .reset_index(drop=True)


def q5(t):
    li, od, cu = t["lineitem"], t["orders"], t["customer"]
    su, na, re = t["supplier"], t["nation"], t["region"]
    j = li.merge(od, left_on="l_orderkey", right_on="o_orderkey")
    j = j.merge(cu, left_on="o_custkey", right_on="c_custkey")
    j = j.merge(su, left_on="l_suppkey", right_on="s_suppkey")
    j = j[j.c_nationkey == j.s_nationkey]
    j = j.merge(na, left_on="s_nationkey", right_on="n_nationkey")
    j = j.merge(re, left_on="n_regionkey", right_on="r_regionkey")
    j = j[(j.r_name == "ASIA") & (j.o_orderdate >= d("1994-01-01"))
          & (j.o_orderdate < d("1995-01-01"))]
    j["revenue"] = j.l_extendedprice * (1 - j.l_discount)
    g = j.groupby("n_name", as_index=False)["revenue"].sum()
    return g.sort_values("revenue", ascending=False).reset_index(drop=True)


def q6(t):
    li = t["lineitem"]
    m = (li.l_shipdate >= d("1994-01-01")) & (li.l_shipdate < d("1995-01-01")) \
        & (li.l_discount >= 0.05) & (li.l_discount <= 0.07) & (li.l_quantity < 24)
    return pd.DataFrame({
        "revenue": [(li[m].l_extendedprice * li[m].l_discount).sum()]})


def q10(t):
    li, od, cu, na = t["lineitem"], t["orders"], t["customer"], t["nation"]
    j = li[li.l_returnflag == "R"].merge(
        od[(od.o_orderdate >= d("1993-10-01"))
           & (od.o_orderdate < d("1994-01-01"))],
        left_on="l_orderkey", right_on="o_orderkey")
    j = j.merge(cu, left_on="o_custkey", right_on="c_custkey")
    j = j.merge(na, left_on="c_nationkey", right_on="n_nationkey")
    j["revenue"] = j.l_extendedprice * (1 - j.l_discount)
    g = j.groupby(["c_custkey", "c_name", "c_acctbal", "c_phone", "n_name",
                   "c_address", "c_comment"], as_index=False)["revenue"].sum()
    g = g.sort_values("revenue", ascending=False, kind="stable").head(20)
    return g[["c_custkey", "c_name", "revenue", "c_acctbal", "n_name",
              "c_address", "c_phone", "c_comment"]].reset_index(drop=True)


def q12(t):
    li, od = t["lineitem"], t["orders"]
    m = li[li.l_shipmode.isin(["MAIL", "SHIP"])
           & (li.l_commitdate < li.l_receiptdate)
           & (li.l_shipdate < li.l_commitdate)
           & (li.l_receiptdate >= d("1994-01-01"))
           & (li.l_receiptdate < d("1995-01-01"))]
    j = m.merge(od, left_on="l_orderkey", right_on="o_orderkey")
    hi = j.o_orderpriority.isin(["1-URGENT", "2-HIGH"])
    g = j.assign(high=hi.astype(int), low=(~hi).astype(int)).groupby(
        "l_shipmode", as_index=False).agg(
        high_line_count=("high", "sum"), low_line_count=("low", "sum"))
    return g.sort_values("l_shipmode").reset_index(drop=True)


def q14(t):
    li, pa = t["lineitem"], t["part"]
    j = li[(li.l_shipdate >= d("1995-09-01"))
           & (li.l_shipdate < d("1995-10-01"))].merge(
        pa, left_on="l_partkey", right_on="p_partkey")
    rev = j.l_extendedprice * (1 - j.l_discount)
    promo = rev.where(j.p_type.str.startswith("PROMO"), 0.0)
    return pd.DataFrame({
        "promo_revenue": [100.0 * promo.sum() / rev.sum()]})


def q19(t):
    li, pa = t["lineitem"], t["part"]
    j = li.merge(pa, left_on="l_partkey", right_on="p_partkey")
    base = j.l_shipmode.isin(["AIR", "AIR REG"]) \
        & (j.l_shipinstruct == "DELIVER IN PERSON")

    def branch(brand, containers, qlo, qhi, slo, shi):
        return ((j.p_brand == brand) & j.p_container.isin(containers)
                & (j.l_quantity >= qlo) & (j.l_quantity <= qhi)
                & (j.p_size >= slo) & (j.p_size <= shi))

    m = base & (
        branch("Brand#12", ["SM CASE", "SM BOX", "SM PACK", "SM PKG"], 1, 11, 1, 5)
        | branch("Brand#23", ["MED BAG", "MED BOX", "MED PKG", "MED PACK"], 10, 20, 1, 10)
        | branch("Brand#34", ["LG CASE", "LG BOX", "LG PACK", "LG PKG"], 20, 30, 1, 15))
    return pd.DataFrame({
        "revenue": [(j[m].l_extendedprice * (1 - j[m].l_discount)).sum()]})


ORACLES = {"q1": q1, "q3": q3, "q5": q5, "q6": q6, "q10": q10, "q12": q12,
           "q14": q14, "q19": q19}


def q2(t):
    pa, su, ps, na, re = (t["part"], t["supplier"], t["partsupp"],
                          t["nation"], t["region"])
    eu = na.merge(re, left_on="n_regionkey", right_on="r_regionkey")
    eu = eu[eu.r_name == "EUROPE"]
    s_eu = su.merge(eu, left_on="s_nationkey", right_on="n_nationkey")
    j = ps.merge(s_eu, left_on="ps_suppkey", right_on="s_suppkey")
    mincost = j.groupby("ps_partkey")["ps_supplycost"].min().rename("mc")
    p = pa[(pa.p_size == 15) & pa.p_type.str.endswith("BRASS")]
    j2 = j.merge(p, left_on="ps_partkey", right_on="p_partkey")
    j2 = j2.merge(mincost, left_on="ps_partkey", right_index=True)
    j2 = j2[j2.ps_supplycost == j2.mc]
    j2 = j2.sort_values(["s_acctbal", "n_name", "s_name", "p_partkey"],
                        ascending=[False, True, True, True],
                        kind="stable").head(100)
    return j2[["s_acctbal", "s_name", "n_name", "p_partkey", "p_mfgr",
               "s_address", "s_phone", "s_comment"]].reset_index(drop=True)


def q4(t):
    od, li = t["orders"], t["lineitem"]
    late = li[li.l_commitdate < li.l_receiptdate].l_orderkey.unique()
    m = od[(od.o_orderdate >= d("1993-07-01"))
           & (od.o_orderdate < d("1993-10-01"))
           & od.o_orderkey.isin(late)]
    g = m.groupby("o_orderpriority", as_index=False).size()
    g.columns = ["o_orderpriority", "order_count"]
    return g.sort_values("o_orderpriority").reset_index(drop=True)


def q7(t):
    li, od, cu, su, na = (t["lineitem"], t["orders"], t["customer"],
                          t["supplier"], t["nation"])
    j = li.merge(od, left_on="l_orderkey", right_on="o_orderkey")
    j = j.merge(cu, left_on="o_custkey", right_on="c_custkey")
    j = j.merge(su, left_on="l_suppkey", right_on="s_suppkey")
    j = j.merge(na.add_prefix("s1_"), left_on="s_nationkey",
                right_on="s1_n_nationkey")
    j = j.merge(na.add_prefix("c2_"), left_on="c_nationkey",
                right_on="c2_n_nationkey")
    j = j[(((j.s1_n_name == "FRANCE") & (j.c2_n_name == "GERMANY"))
           | ((j.s1_n_name == "GERMANY") & (j.c2_n_name == "FRANCE")))
          & (j.l_shipdate >= d("1995-01-01"))
          & (j.l_shipdate <= d("1996-12-31"))]
    j["l_year"] = j.l_shipdate.dt.year
    j["volume"] = j.l_extendedprice * (1 - j.l_discount)
    g = j.groupby(["s1_n_name", "c2_n_name", "l_year"],
                  as_index=False)["volume"].sum()
    g.columns = ["supp_nation", "cust_nation", "l_year", "revenue"]
    return g.sort_values(["supp_nation", "cust_nation", "l_year"]) \
        .reset_index(drop=True)


def q8(t):
    li, od, cu, su, pa, na, re = (t["lineitem"], t["orders"], t["customer"],
                                  t["supplier"], t["part"], t["nation"],
                                  t["region"])
    j = li.merge(pa[pa.p_type == "ECONOMY ANODIZED STEEL"],
                 left_on="l_partkey", right_on="p_partkey")
    j = j.merge(od, left_on="l_orderkey", right_on="o_orderkey")
    j = j[(j.o_orderdate >= d("1995-01-01")) & (j.o_orderdate <= d("1996-12-31"))]
    j = j.merge(cu, left_on="o_custkey", right_on="c_custkey")
    j = j.merge(na.add_prefix("c1_"), left_on="c_nationkey",
                right_on="c1_n_nationkey")
    j = j.merge(re, left_on="c1_n_regionkey", right_on="r_regionkey")
    j = j[j.r_name == "AMERICA"]
    j = j.merge(su, left_on="l_suppkey", right_on="s_suppkey")
    j = j.merge(na.add_prefix("s2_"), left_on="s_nationkey",
                right_on="s2_n_nationkey")
    j["o_year"] = j.o_orderdate.dt.year
    j["volume"] = j.l_extendedprice * (1 - j.l_discount)
    j["bra"] = j.volume.where(j.s2_n_name == "BRAZIL", 0.0)
    g = j.groupby("o_year", as_index=False).agg(b=("bra", "sum"),
                                                v=("volume", "sum"))
    g["mkt_share"] = g.b / g.v
    return g[["o_year", "mkt_share"]].sort_values("o_year") \
        .reset_index(drop=True)


def q9(t):
    li, od, su, pa, ps, na = (t["lineitem"], t["orders"], t["supplier"],
                              t["part"], t["partsupp"], t["nation"])
    j = li.merge(pa[pa.p_name.str.contains("green")],
                 left_on="l_partkey", right_on="p_partkey")
    j = j.merge(ps, left_on=["l_partkey", "l_suppkey"],
                right_on=["ps_partkey", "ps_suppkey"])
    j = j.merge(su, left_on="l_suppkey", right_on="s_suppkey")
    j = j.merge(od, left_on="l_orderkey", right_on="o_orderkey")
    j = j.merge(na, left_on="s_nationkey", right_on="n_nationkey")
    j["o_year"] = j.o_orderdate.dt.year
    j["amount"] = (j.l_extendedprice * (1 - j.l_discount)
                   - j.ps_supplycost * j.l_quantity)
    g = j.groupby(["n_name", "o_year"], as_index=False)["amount"].sum()
    g.columns = ["nation", "o_year", "sum_profit"]
    return g.sort_values(["nation", "o_year"], ascending=[True, False]) \
        .reset_index(drop=True)


def q11(t):
    ps, su, na = t["partsupp"], t["supplier"], t["nation"]
    j = ps.merge(su, left_on="ps_suppkey", right_on="s_suppkey")
    j = j.merge(na[na.n_name == "GERMANY"], left_on="s_nationkey",
                right_on="n_nationkey")
    j["value"] = j.ps_supplycost * j.ps_availqty
    total = j.value.sum() * 0.0001
    g = j.groupby("ps_partkey", as_index=False)["value"].sum()
    g = g[g.value > total]
    return g.sort_values("value", ascending=False).reset_index(drop=True)


def q13(t):
    cu, od = t["customer"], t["orders"]
    o = od[~od.o_comment.str.contains("special.*requests", regex=True)]
    cnt = o.groupby("o_custkey").size()
    c_count = cu.c_custkey.map(cnt).fillna(0).astype(int)
    g = c_count.value_counts().rename_axis("c_count") \
        .reset_index(name="custdist")
    return g.sort_values(["custdist", "c_count"], ascending=[False, False]) \
        .reset_index(drop=True)


def q15(t):
    li, su = t["lineitem"], t["supplier"]
    m = li[(li.l_shipdate >= d("1996-01-01")) & (li.l_shipdate < d("1996-04-01"))]
    rev = m.assign(r=m.l_extendedprice * (1 - m.l_discount)) \
        .groupby("l_suppkey", as_index=False)["r"].sum()
    mx = rev.r.max()
    j = su.merge(rev[rev.r == mx], left_on="s_suppkey", right_on="l_suppkey")
    j = j.sort_values("s_suppkey")
    out = j[["s_suppkey", "s_name", "s_address", "s_phone", "r"]].copy()
    out.columns = ["s_suppkey", "s_name", "s_address", "s_phone",
                   "total_revenue"]
    return out.reset_index(drop=True)


def q16(t):
    ps, pa, su = t["partsupp"], t["part"], t["supplier"]
    bad = su[su.s_comment.str.contains("Customer.*Complaints", regex=True)] \
        .s_suppkey
    p = pa[(pa.p_brand != "Brand#45")
           & ~pa.p_type.str.startswith("MEDIUM POLISHED")
           & pa.p_size.isin([49, 14, 23, 45, 19, 3, 36, 9])]
    j = ps.merge(p, left_on="ps_partkey", right_on="p_partkey")
    j = j[~j.ps_suppkey.isin(bad)]
    g = j.groupby(["p_brand", "p_type", "p_size"])["ps_suppkey"] \
        .nunique().reset_index(name="supplier_cnt")
    return g.sort_values(["supplier_cnt", "p_brand", "p_type", "p_size"],
                         ascending=[False, True, True, True]) \
        .reset_index(drop=True)


def q17(t):
    li, pa = t["lineitem"], t["part"]
    p = pa[(pa.p_brand == "Brand#23") & (pa.p_container == "MED BOX")]
    avg_q = li.groupby("l_partkey")["l_quantity"].mean() * 0.2
    j = li.merge(p, left_on="l_partkey", right_on="p_partkey")
    j = j[j.l_quantity < j.l_partkey.map(avg_q)]
    # SQL: sum() over zero rows is NULL, not 0 (pandas' .sum() default)
    total = j.l_extendedprice.sum() / 7.0 if len(j) else float("nan")
    return pd.DataFrame({"avg_yearly": [total]})


def q18(t):
    cu, od, li = t["customer"], t["orders"], t["lineitem"]
    big = li.groupby("l_orderkey")["l_quantity"].sum()
    big = big[big > 300].index
    j = od[od.o_orderkey.isin(big)].merge(cu, left_on="o_custkey",
                                          right_on="c_custkey")
    j = li.merge(j, left_on="l_orderkey", right_on="o_orderkey")
    g = j.groupby(["c_name", "c_custkey", "o_orderkey", "o_orderdate",
                   "o_totalprice"], as_index=False)["l_quantity"].sum()
    g.columns = ["c_name", "c_custkey", "o_orderkey", "o_orderdate",
                 "o_totalprice", "total_qty"]
    g = g.sort_values(["o_totalprice", "o_orderdate"],
                      ascending=[False, True], kind="stable").head(100)
    return g.reset_index(drop=True)


def q20(t):
    su, na, ps, pa, li = (t["supplier"], t["nation"], t["partsupp"],
                          t["part"], t["lineitem"])
    forest = pa[pa.p_name.str.startswith("forest")].p_partkey
    m = li[(li.l_shipdate >= d("1994-01-01")) & (li.l_shipdate < d("1995-01-01"))]
    half = m.groupby(["l_partkey", "l_suppkey"])["l_quantity"].sum() * 0.5
    j = ps[ps.ps_partkey.isin(forest)].copy()
    key = list(zip(j.ps_partkey, j.ps_suppkey))
    j["thresh"] = [half.get(k, np.nan) for k in key]
    j = j[j.ps_availqty > j.thresh]  # NaN comparison false = SQL NULL false
    sk = j.ps_suppkey.unique()
    out = su[su.s_suppkey.isin(sk)].merge(
        na[na.n_name == "CANADA"], left_on="s_nationkey",
        right_on="n_nationkey")
    return out.sort_values("s_name")[["s_name", "s_address"]] \
        .reset_index(drop=True)


def q21(t):
    su, li, od, na = t["supplier"], t["lineitem"], t["orders"], t["nation"]
    l1 = li[li.l_receiptdate > li.l_commitdate]
    nsupp = li.groupby("l_orderkey")["l_suppkey"].nunique()
    late_nsupp = l1.groupby("l_orderkey")["l_suppkey"].nunique()
    j = l1.merge(od[od.o_orderstatus == "F"], left_on="l_orderkey",
                 right_on="o_orderkey")
    j = j.merge(su, left_on="l_suppkey", right_on="s_suppkey")
    j = j.merge(na[na.n_name == "SAUDI ARABIA"], left_on="s_nationkey",
                right_on="n_nationkey")
    # exists: order has another supplier; not exists: no OTHER supplier late
    j = j[(j.l_orderkey.map(nsupp) > 1)]
    other_late = [
        (late_nsupp.get(ok, 0) - 1 if is_late else late_nsupp.get(ok, 0)) > 0
        for ok, is_late in zip(j.l_orderkey, [True] * len(j))]
    j = j[~np.asarray(other_late)]
    g = j.groupby("s_name", as_index=False).size()
    g.columns = ["s_name", "numwait"]
    g = g.sort_values(["numwait", "s_name"], ascending=[False, True],
                      kind="stable").head(100)
    return g.reset_index(drop=True)


def q22(t):
    cu, od = t["customer"], t["orders"]
    codes = ["13", "31", "23", "29", "30", "18", "17"]
    cc = cu.c_phone.str[:2]
    pool = cu[cc.isin(codes)]
    avg_bal = pool[pool.c_acctbal > 0.0].c_acctbal.mean()
    m = pool[(pool.c_acctbal > avg_bal)
             & ~pool.c_custkey.isin(od.o_custkey)]
    g = m.assign(cntrycode=m.c_phone.str[:2]).groupby(
        "cntrycode", as_index=False).agg(numcust=("c_acctbal", "size"),
                                         totacctbal=("c_acctbal", "sum"))
    return g.sort_values("cntrycode").reset_index(drop=True)


ORACLES.update({"q2": q2, "q4": q4, "q7": q7, "q8": q8, "q9": q9,
                "q11": q11, "q13": q13, "q15": q15, "q16": q16, "q17": q17,
                "q18": q18, "q20": q20, "q21": q21, "q22": q22})
