import numpy as np
import pytest

import cloudberry_tpu as cb
from cloudberry_tpu.plan.binder import BindError


@pytest.fixture
def sess():
    return cb.Session()


def test_create_insert_select(sess):
    sess.sql("""create table items (id bigint not null, price decimal(10,2),
                name text, sold date) distributed by (id)""")
    sess.sql("""insert into items values
                (1, 9.99, 'apple', '2024-01-05'),
                (2, 12.50, 'pear', '2024-02-01'),
                (3, 0.99, 'fig', '2024-01-20')""")
    out = sess.sql("select name, price from items where price > 5 order by price desc")
    df = out.to_pandas()
    assert df["name"].tolist() == ["pear", "apple"]
    assert df["price"].tolist() == [12.50, 9.99]


def test_group_and_having(sess):
    sess.sql("create table s (k text, v int) distributed randomly")
    sess.sql("insert into s values ('a',1),('a',2),('b',5),('b',7),('c',1)")
    df = sess.sql("""select k, sum(v) as total, count(*) as n from s
                     group by k having sum(v) > 2 order by total desc""").to_pandas()
    assert df["k"].tolist() == ["b", "a"]
    assert df["total"].tolist() == [12, 3]
    assert df["n"].tolist() == [2, 2]


def test_string_order_by_uses_collation(sess):
    sess.sql("create table t (s text) distributed randomly")
    sess.sql("insert into t values ('pear'),('apple'),('zebra'),('fig')")
    df = sess.sql("select s from t order by s").to_pandas()
    assert df["s"].tolist() == ["apple", "fig", "pear", "zebra"]


def test_distinct(sess):
    sess.sql("create table d (x int) distributed randomly")
    sess.sql("insert into d values (3),(1),(3),(2),(1)")
    df = sess.sql("select distinct x from d order by x").to_pandas()
    assert df["x"].tolist() == [1, 2, 3]


def test_case_expression(sess):
    sess.sql("create table c (v int) distributed randomly")
    sess.sql("insert into c values (1),(5),(10)")
    df = sess.sql("""select case when v < 3 then 'small'
                                when v < 8 then 'mid'
                                else 'big' end as bucket
                     from c order by v""").to_pandas()
    assert df["bucket"].tolist() == ["small", "mid", "big"]


def test_drop_and_errors(sess):
    sess.sql("create table gone (x int)")
    sess.sql("drop table gone")
    with pytest.raises(KeyError):
        sess.sql("select * from gone")
    sess.sql("create table there (x int)")
    with pytest.raises(BindError):
        sess.sql("select nosuchcol from there")


def test_decimal_exactness(sess):
    # classic float-sum trap: 0.1 + 0.2 — int64 fixed point stays exact
    sess.sql("create table m (v decimal(10,2))")
    rows = ",".join(["(0.10)"] * 100)
    sess.sql(f"insert into m values {rows}")
    df = sess.sql("select sum(v) as s from m").to_pandas()
    assert df["s"][0] == 10.0  # exactly, no 9.999999...


def test_set_operations(sess):
    sess.sql("create table sa (x int, s text)")
    sess.sql("insert into sa values (1,'a'),(2,'b'),(2,'b'),(3,'c')")
    sess.sql("create table sb (x int, s text)")
    sess.sql("insert into sb values (2,'b'),(4,'d'),(3,'zz')")

    df = sess.sql("select x, s from sa union all select x, s from sb "
                  "order by x, s").to_pandas()
    assert len(df) == 7 and df["x"].tolist() == [1, 2, 2, 2, 3, 3, 4]
    assert df["s"].tolist() == ["a", "b", "b", "b", "c", "zz", "d"]

    df = sess.sql("select x, s from sa union select x, s from sb "
                  "order by x, s").to_pandas()
    assert list(zip(df["x"], df["s"])) == [
        (1, "a"), (2, "b"), (3, "c"), (3, "zz"), (4, "d")]

    df = sess.sql("select x, s from sa intersect select x, s from sb "
                  "order by x").to_pandas()
    assert list(zip(df["x"], df["s"])) == [(2, "b")]

    df = sess.sql("select x, s from sa except select x, s from sb "
                  "order by x").to_pandas()
    assert list(zip(df["x"], df["s"])) == [(1, "a"), (3, "c")]


def test_set_op_type_coercion(sess):
    sess.sql("create table ca (v int)")
    sess.sql("insert into ca values (1),(2)")
    sess.sql("create table cb (v decimal(10,2))")
    sess.sql("insert into cb values (2.5),(1.0)")
    df = sess.sql("select v from ca union all select v from cb "
                  "order by v").to_pandas()
    assert df["v"].tolist() == [1.0, 1.0, 2.0, 2.5]


def test_set_op_arity_error(sess):
    sess.sql("create table e1 (a int, b int)")
    with pytest.raises(BindError):
        sess.sql("select a, b from e1 union select a from e1")


def test_window_functions(sess):
    sess.sql("create table w (g text, o int, v decimal(10,2))")
    sess.sql("""insert into w values
        ('a', 1, 10.0), ('a', 2, 20.0), ('a', 2, 5.0), ('a', 3, 1.0),
        ('b', 1, 100.0), ('b', 2, 50.0)""")
    df = sess.sql("""select g, o, v,
                row_number() over (partition by g order by o, v) as rn,
                rank() over (partition by g order by o) as rk,
                dense_rank() over (partition by g order by o) as dr,
                sum(v) over (partition by g order by o) as running,
                sum(v) over (partition by g) as total,
                count(*) over (partition by g) as n,
                max(v) over (partition by g) as mx
            from w order by g, o, v""").to_pandas()
    assert df["rn"].tolist() == [1, 2, 3, 4, 1, 2]
    assert df["rk"].tolist() == [1, 2, 2, 4, 1, 2]
    assert df["dr"].tolist() == [1, 2, 2, 3, 1, 2]
    # running sum with ORDER BY includes peers (RANGE frame)
    assert df["running"].tolist() == [10.0, 35.0, 35.0, 36.0, 100.0, 150.0]
    assert df["total"].tolist() == [36.0] * 4 + [150.0] * 2
    assert df["n"].tolist() == [4, 4, 4, 4, 2, 2]
    assert df["mx"].tolist() == [20.0] * 4 + [100.0] * 2


def test_window_no_partition(sess):
    sess.sql("create table wn (v int)")
    sess.sql("insert into wn values (3),(1),(2)")
    df = sess.sql("select v, row_number() over (order by v) as rn, "
                  "sum(v) over () as t from wn order by v").to_pandas()
    assert df["rn"].tolist() == [1, 2, 3]
    assert df["t"].tolist() == [6, 6, 6]


def test_window_string_order_collation(sess):
    # dictionary insertion order deliberately != lexical order
    sess.sql("create table wc (s text)")
    sess.sql("insert into wc values ('pear'),('apple'),('zebra')")
    df = sess.sql("select s, row_number() over (order by s) as rn "
                  "from wc order by s").to_pandas()
    assert list(zip(df.s, df.rn)) == [("apple", 1), ("pear", 2), ("zebra", 3)]


def test_intersect_precedence(sess):
    sess.sql("create table p1 (x int)"); sess.sql("insert into p1 values (1)")
    sess.sql("create table p2 (x int)"); sess.sql("insert into p2 values (2)")
    # 1 UNION (2 INTERSECT 2) = {1,2}; left-assoc would give {2}
    df = sess.sql("select x from p1 union select x from p2 "
                  "intersect select x from p2 order by x").to_pandas()
    assert df["x"].tolist() == [1, 2]


def test_except_all_supported(sess):
    sess.sql("create table q1 (x int)")
    sess.sql("insert into q1 values (1), (1), (2)")
    sess.sql("create table q2 (x int)")
    sess.sql("insert into q2 values (1)")
    df = sess.sql("select x from q1 except all "
                  "select x from q2").to_pandas()
    # bag semantics: ONE copy of 1 removed, the other and the 2 remain
    assert sorted(df["x"].tolist()) == [1, 2]


def test_explain_does_not_mutate_dictionary(sess):
    sess.sql("create table da (s text)"); sess.sql("insert into da values ('a')")
    sess.sql("create table db2 (s text)"); sess.sql("insert into db2 values ('zzz')")
    before = list(sess.catalog.table("da").dicts["s"].values)
    sess.explain("select s from da union select s from db2")
    assert sess.catalog.table("da").dicts["s"].values == before


def test_delete(sess):
    sess.sql("create table del_t (k int, v decimal(10,2))")
    sess.sql("insert into del_t values (1,1.0),(2,2.0),(3,3.0),(4,4.0)")
    assert sess.sql("delete from del_t where k > 2") == "DELETE 2"
    df = sess.sql("select k from del_t order by k").to_pandas()
    assert df["k"].tolist() == [1, 2]
    assert sess.sql("delete from del_t") == "DELETE 2"
    assert len(sess.sql("select k from del_t").to_pandas()) == 0


def test_update(sess):
    sess.sql("create table up_t (k int, v decimal(10,2), s text)")
    sess.sql("insert into up_t values (1,1.0,'a'),(2,2.0,'b'),(3,3.0,'c')")
    assert sess.sql("update up_t set v = v * 2 where k >= 2") == "UPDATE 2"
    df = sess.sql("select k, v from up_t order by k").to_pandas()
    assert df["v"].tolist() == [1.0, 4.0, 6.0]
    # string update with a NEW literal value
    assert sess.sql("update up_t set s = 'zzz' where k = 1") == "UPDATE 1"
    df = sess.sql("select s from up_t order by k").to_pandas()
    assert df["s"].tolist() == ["zzz", "b", "c"]
    # unconditional update
    assert sess.sql("update up_t set v = 0.5") == "UPDATE 3"
    assert sess.sql("select sum(v) as t from up_t").to_pandas()["t"][0] == 1.5


def test_insert_select(sess):
    sess.sql("create table src_t (k int, s text)")
    sess.sql("insert into src_t values (1,'x'),(2,'y')")
    sess.sql("create table dst_t (k int, s text)")
    assert sess.sql("insert into dst_t select k * 10, s from src_t") == "INSERT 2"
    assert sess.sql("insert into dst_t select k, s from src_t where k = 1") == "INSERT 1"
    df = sess.sql("select k, s from dst_t order by k").to_pandas()
    assert list(zip(df.k, df.s)) == [(1, "x"), (10, "x"), (20, "y")]


def test_dml_distributed():
    s = cb.Session(cb.Config(n_segments=4))
    s.sql("create table dd (k bigint, v decimal(10,2)) distributed by (k)")
    s.sql("insert into dd values " + ",".join(f"({i},{i}.0)" for i in range(40)))
    assert s.sql("delete from dd where k >= 30") == "DELETE 10"
    assert s.sql("update dd set v = v + 100.0 where k < 10") == "UPDATE 10"
    df = s.sql("select count(*) as n, sum(v) as t from dd").to_pandas()
    assert int(df["n"][0]) == 30
    assert float(df["t"][0]) == sum(i + 100 for i in range(10)) + sum(range(10, 30))


def test_statement_cache_reuse_and_invalidation(sess):
    sess.sql("create table sc (k int)")
    sess.sql("insert into sc values (1),(2),(3)")
    q = "select sum(k) as s from sc"
    assert sess.sql(q).to_pandas()["s"][0] == 6
    runner1 = sess._stmt_cache[q][4]
    assert sess.sql(q).to_pandas()["s"][0] == 6
    assert sess._stmt_cache[q][4] is runner1  # reused, not rebuilt
    # DML bumps the table version -> cache invalidated, result fresh
    sess.sql("insert into sc values (10)")
    assert sess.sql(q).to_pandas()["s"][0] == 16
    assert sess._stmt_cache[q][4] is not runner1


def test_statement_cache_drop_recreate_not_stale(sess):
    sess.sql("create table scd (s text)")
    sess.sql("insert into scd values ('a'),('b'),('b')")
    q = "select count(*) as n from scd where s = 'b'"
    assert int(sess.sql(q).to_pandas()["n"][0]) == 2
    sess.sql("drop table scd")
    sess.sql("create table scd (s text)")
    sess.sql("insert into scd values ('b'),('z'),('z')")
    # recreated table: dictionary codes differ; cache must NOT replay
    assert int(sess.sql(q).to_pandas()["n"][0]) == 1


def test_views(sess):
    sess.sql("create table vt (k int, v decimal(10,2))")
    sess.sql("insert into vt values (1,10.0),(2,20.0),(1,5.0)")
    sess.sql("create view vsum as select k, sum(v) as total from vt group by k")
    df = sess.sql("select k, total from vsum where total > 12 order by k").to_pandas()
    assert list(zip(df.k, df.total)) == [(1, 15.0), (2, 20.0)]
    # views track base-table changes (re-bound per statement)
    sess.sql("insert into vt values (2, 1.0)")
    df = sess.sql("select total from vsum where k = 2").to_pandas()
    assert df["total"].tolist() == [21.0]
    # view joins a table
    df = sess.sql("""select a.k from vsum a, vt b
                     where a.k = b.k and b.v = 5.0""").to_pandas()
    assert df["k"].tolist() == [1]
    sess.sql("drop view vsum")
    with pytest.raises(Exception):
        sess.sql("select * from vsum")


def test_view_ddl_invalidates_cache(sess):
    sess.sql("create table vb1 (x int)"); sess.sql("insert into vb1 values (1)")
    sess.sql("create table vb2 (x int)"); sess.sql("insert into vb2 values (2)")
    sess.sql("create view vv as select x from vb1")
    q = "select x from vv"
    assert sess.sql(q).to_pandas()["x"].tolist() == [1]
    sess.sql("drop view vv")
    sess.sql("create view vv as select x from vb2")
    assert sess.sql(q).to_pandas()["x"].tolist() == [2]  # not the stale plan
    with pytest.raises(BindError):
        sess.sql("create view vv as select 1")  # no OR REPLACE
    with pytest.raises(BindError):
        sess.sql("drop view no_such_view")
    with pytest.raises(BindError):
        sess.sql("create table vv (y int)")  # view shadow guard


def test_create_table_as_select(sess):
    sess.sql("create table base (k int, s text, v decimal(10,2))")
    sess.sql("insert into base values (1,'a',10.0),(2,'b',20.0),(3,'a',5.0)")
    out = sess.sql("""create table summary distributed by (s) as
                      select s, sum(v) as total, count(*) as n
                      from base group by s""")
    assert out == "SELECT 2"
    df = sess.sql("select s, total, n from summary order by s").to_pandas()
    assert list(zip(df.s, df.total, df.n)) == [("a", 15.0, 2), ("b", 20.0, 1)]
    from cloudberry_tpu.catalog.catalog import DistributionPolicy
    assert sess.catalog.table("summary").policy == DistributionPolicy.hashed("s")
    with pytest.raises(BindError):
        sess.sql("create table bad distributed by (nope) as select s from base")


def test_ctas_trailing_distributed_and_if_not_exists(sess):
    sess.sql("create table cb2 (k int)"); sess.sql("insert into cb2 values (1),(2)")
    # canonical trailing DISTRIBUTED BY form (query ends in a table name)
    sess.sql("create table c2 as select k from cb2 distributed by (k)")
    assert len(sess.sql("select k from c2").to_pandas()) == 2
    # IF NOT EXISTS no-ops on rerun
    out = sess.sql("create table if not exists c2 as select k from cb2")
    assert "skipped" in out
    with pytest.raises(BindError):
        sess.sql("create table c2 as select k from cb2")


def test_copy_from_and_to(sess, tmp_path):
    p = tmp_path / "in.tbl"
    p.write_text("1|9.99|apple|2024-01-05\n"
                 "2|12.50|pear|2024-02-01\n"
                 "3|0.07|fig|2024-01-20\n")
    sess.sql("create table cp (id bigint, price decimal(10,2), name text, d date)")
    out = sess.sql(f"copy cp from '{p}'")
    assert out == "COPY 3"
    df = sess.sql("select id, price, name from cp order by id").to_pandas()
    assert df["price"].tolist() == [9.99, 12.50, 0.07]
    assert df["name"].tolist() == ["apple", "pear", "fig"]
    # append semantics + header + custom delimiter
    p2 = tmp_path / "in2.csv"
    p2.write_text("id,price,name,d\n4,1.25,kiwi,2024-03-01\n")
    assert sess.sql(f"copy cp from '{p2}' with delimiter ',' header") == "COPY 1"
    assert len(sess.sql("select id from cp").to_pandas()) == 4
    # unload round-trip
    p3 = tmp_path / "out.tbl"
    assert sess.sql(f"copy cp to '{p3}'") == "COPY 4"
    sess.sql("create table cp2 (id bigint, price decimal(10,2), name text, d date)")
    assert sess.sql(f"copy cp2 from '{p3}'") == "COPY 4"
    a = sess.sql("select sum(price) as s from cp").to_pandas()["s"][0]
    b = sess.sql("select sum(price) as s from cp2").to_pandas()["s"][0]
    assert a == b


def test_copy_edge_cases(sess, tmp_path):
    sess.sql("create table ce (b boolean, f double, s text)")
    bad = tmp_path / "b.tbl"
    bad.write_text("maybe|1.5|x\n")
    with pytest.raises(BindError):
        sess.sql(f"copy ce from '{bad}'")  # bad boolean rejected
    bad2 = tmp_path / "b2.tbl"
    bad2.write_text("true|oops|x\n")
    with pytest.raises(BindError):
        sess.sql(f"copy ce from '{bad2}'")  # bad double rejected
    # delimiter inside a string value refuses to unload corruptly
    sess.sql("insert into ce values (true, 1.0, 'a|b')")
    with pytest.raises(BindError):
        sess.sql(f"copy ce to '{tmp_path / 'o.tbl'}'")
    # big exact decimal round-trips through COPY TO text
    sess.sql("create table bd (v decimal(18,2))")
    sess.sql("insert into bd values (90071992547409.93)")
    out = tmp_path / "bd.tbl"
    sess.sql(f"copy bd to '{out}'")
    assert out.read_text().strip() == "90071992547409.93"


def test_full_outer_join(sess):
    sess.sql("create table fa (k int, a int)")
    sess.sql("insert into fa values (1,10),(2,20),(3,30)")
    sess.sql("create table fb (k int, b int)")
    sess.sql("insert into fb values (2,200),(3,300),(4,400),(2,201)")
    df = sess.sql("""select fa.k, a, b from fa full join fb on fa.k = fb.k
                     order by a, b""").to_pandas()
    # pairs: (2,20,200),(2,20,201),(3,30,300); probe-only (1,10,-);
    # build-only (-,-,400) — zeros stand in for NULL values, masks track
    assert len(df) == 5
    # IS NULL works on both sides
    df2 = sess.sql("""select a from fa full join fb on fa.k = fb.k
                      where b is null""").to_pandas()
    assert df2["a"].tolist() == [10]
    df3 = sess.sql("""select b from fa full join fb on fa.k = fb.k
                      where a is null""").to_pandas()
    assert df3["b"].tolist() == [400]
    # counts are null-aware on both sides
    df4 = sess.sql("""select count(a) as ca, count(b) as cb, count(*) as n
                      from fa full join fb on fa.k = fb.k""").to_pandas()
    assert (int(df4.ca[0]), int(df4.cb[0]), int(df4.n[0])) == (4, 4, 5)


def test_full_outer_join_distributed():
    s = cb.Session(cb.Config(n_segments=4))
    s.sql("create table fa (k bigint, a bigint) distributed by (k)")
    s.sql("insert into fa values " + ",".join(f"({i},{i})" for i in range(0, 30, 2)))
    s.sql("create table fb (k bigint, b bigint) distributed by (k)")
    s.sql("insert into fb values " + ",".join(f"({i},{i*10})" for i in range(0, 30, 3)))
    got = s.sql("""select count(*) as n, count(a) as ca, count(b) as cb
                   from fa full join fb on fa.k = fb.k""").to_pandas()
    # evens 15, multiples-of-3 10, both (mult of 6) 5 -> union 20 rows
    assert int(got.n[0]) == 20
    assert int(got.ca[0]) == 15 and int(got.cb[0]) == 10


def test_full_join_null_rendering_and_coalesce(sess):
    sess.sql("create table jl (k int, a text)")
    sess.sql("insert into jl values (1,'x'),(2,'y')")
    sess.sql("create table jr (k int, b text)")
    sess.sql("insert into jr values (2,'p'),(3,'q')")
    df = sess.sql("""select coalesce(jl.k, jr.k) as k, a, b
                     from jl full join jr on jl.k = jr.k
                     order by k""").to_pandas()
    def norm(vals):
        return [None if v is None or (isinstance(v, float) and v != v)
                else v for v in vals]

    assert df["k"].tolist() == [1, 2, 3]
    assert norm(df["a"]) == ["x", "y", None]
    assert norm(df["b"]) == [None, "p", "q"]
    # left join renders NULL for unmatched build columns
    df2 = sess.sql("select a, b from jl left join jr on jl.k = jr.k "
                   "order by a").to_pandas()
    assert norm(df2["b"]) == [None, "p"]


def test_coalesce_chains_and_insert_literals(sess):
    sess.sql("create table cbase (k int)")
    sess.sql("insert into cbase values (1),(2),(3)")
    sess.sql("create table cr1 (k int, x bigint)")
    sess.sql("insert into cr1 values (1, 10)")
    sess.sql("create table cr2 (k int, y bigint)")
    sess.sql("insert into cr2 values (3, 300)")
    df = sess.sql("""select cbase.k, coalesce(x, y) as v
                     from cbase left join cr1 on cbase.k = cr1.k
                                left join cr2 on cbase.k = cr2.k
                     order by cbase.k""").to_pandas()
    vals = [None if v is None or (isinstance(v, float) and v != v) else int(v)
            for v in df["v"]]
    assert vals == [10, None, 300]  # all-null row renders NULL, not 0
    # mixed-width coalesce keeps masks through coercion
    sess.sql("create table cw (k int, small integer)")
    sess.sql("insert into cw values (2, 7)")
    df2 = sess.sql("""select coalesce(small, x) as v
                      from cbase left join cw on cbase.k = cw.k
                                 left join cr1 on cbase.k = cr1.k
                      order by cbase.k""").to_pandas()
    v2 = [None if v is None or (isinstance(v, float) and v != v) else int(v)
          for v in df2["v"]]
    assert v2 == [10, 7, None]
    # INSERT literal coercions: rounding + clean errors
    sess.sql("create table ints (x int)")
    sess.sql("insert into ints values (2.5), (1e2)")
    # 2.5 rounds half-away like PostgreSQL -> 3
    assert sorted(sess.sql("select x from ints").to_pandas().x) == [3, 100]
    sess.sql("create table decs (v decimal(10,2))")
    sess.sql("insert into decs values (1.999)")
    assert sess.sql("select v from decs").to_pandas().v[0] == 2.0
    with pytest.raises(BindError):
        sess.sql("insert into ints values ('nope')")


def test_transactions(sess):
    sess.sql("create table tx (k int, s text)")
    sess.sql("insert into tx values (1,'a')")
    assert sess.sql("begin") == "BEGIN"
    sess.sql("insert into tx values (2,'brandnew')")
    sess.sql("update tx set s = 'changed' where k = 1")
    sess.sql("create table tx2 (x int)")
    sess.sql("create view txv as select k from tx")
    # read-your-writes inside the transaction
    assert len(sess.sql("select k from tx").to_pandas()) == 2
    assert sess.sql("rollback") == "ROLLBACK"
    df = sess.sql("select k, s from tx").to_pandas()
    assert list(zip(df.k, df.s)) == [(1, "a")]  # data AND dictionary restored
    with pytest.raises(Exception):
        sess.sql("select * from tx2")  # created table rolled back
    with pytest.raises(Exception):
        sess.sql("select * from txv")  # created view rolled back
    # commit path
    sess.sql("begin transaction")
    sess.sql("delete from tx where k = 1")
    assert sess.sql("commit") == "COMMIT"
    assert len(sess.sql("select k from tx").to_pandas()) == 0
    # protocol errors
    with pytest.raises(BindError):
        sess.sql("commit")
    sess.sql("begin")
    with pytest.raises(BindError):
        sess.sql("begin")
    sess.sql("abort")


def test_review_fixes_star_nested_coalesce_bigint(sess):
    sess.sql("create table ja (k int, a text)")
    sess.sql("insert into ja values (1,'x')")
    sess.sql("create table jb (k int, b text)")
    sess.sql("insert into jb values (2,'q')")
    df = sess.sql("select * from ja full join jb on ja.k = jb.k "
                  "order by ja.k").to_pandas()
    flat = [None if v is None or (isinstance(v, float) and v != v) else v
            for v in df.iloc[:, 1].tolist()]  # 'a' column
    assert None in flat  # star output renders NULLs, not placeholder 'x'

    # nested coalesce falls through to the terminal default
    sess.sql("create table nb (k int)")
    sess.sql("insert into nb values (1),(2)")
    sess.sql("create table n1 (k int, x bigint)")
    sess.sql("insert into n1 values (1, 10)")
    df2 = sess.sql("""select coalesce(coalesce(x, x), 777) as v
                      from nb left join n1 on nb.k = n1.k
                      order by nb.k""").to_pandas()
    assert [int(v) for v in df2.v] == [10, 777]

    # bigint literal beyond 2^53 survives digit-exact
    sess.sql("create table bigv (v bigint)")
    sess.sql("insert into bigv values (9007199254740993)")
    assert int(sess.sql("select v from bigv").to_pandas().v[0]) == 9007199254740993

    # long transaction spellings
    sess.sql("begin work"); sess.sql("commit work")
    sess.sql("begin"); sess.sql("rollback transaction")


def test_string_coalesce_cross_dict(sess):
    sess.sql("create table sc_a (k int, a text)")
    sess.sql("insert into sc_a values (1,'x')")
    sess.sql("create table sc_b (k int, b text)")
    sess.sql("insert into sc_b values (2,'q')")
    df = sess.sql("""select coalesce(a, b) as v
                     from sc_a full join sc_b on sc_a.k = sc_b.k
                     order by v""").to_pandas()
    assert sorted(df.v.tolist()) == ["q", "x"]  # codes re-based, not aliased
    df2 = sess.sql("""select coalesce(a, 'none') as v
                      from sc_a full join sc_b on sc_a.k = sc_b.k
                      order by v""").to_pandas()
    assert sorted(df2.v.tolist()) == ["none", "x"]
    # huge int literal -> clean BindError, not OverflowError
    sess.sql("create table ovf (v bigint)")
    with pytest.raises(BindError):
        sess.sql("insert into ovf values (99999999999999999999)")


def test_mid_cardinality_group_by_sums_exact(sess):
    """GROUP BY over thousands of groups (beyond any dense cell domain:
    the sort path): BIGINT and DECIMAL sums equal numpy's int64 sums bit
    for bit, counts exactly, averages to the last ulp or so."""
    rng = np.random.default_rng(11)
    n, groups = 30_000, 8_000
    data = {"k": rng.integers(0, groups, n),
            "v": rng.integers(-10**12, 10**12, n),
            "amt": rng.integers(0, 10**8, n)}
    sess.sql("create table f (k bigint, v bigint, amt decimal(12,2))")
    sess.catalog.table("f").set_data(dict(data))
    batch = sess.sql("select k, sum(v) as sv, sum(amt) as sa, avg(v) as av, "
                     "count(*) as n from f group by k order by k")
    uk, inv = np.unique(data["k"], return_inverse=True)
    sv, sa = np.zeros(len(uk), np.int64), np.zeros(len(uk), np.int64)
    np.add.at(sv, inv, data["v"])
    np.add.at(sa, inv, data["amt"])
    counts = np.bincount(inv)
    sel = np.asarray(batch.sel)
    got = {c: np.asarray(v)[sel] for c, v in batch.columns.items()}
    assert got["k"].tolist() == uk.tolist()
    assert got["sv"].tolist() == sv.tolist()
    assert got["sa"].tolist() == sa.tolist()      # cents, scale 2
    assert got["n"].tolist() == counts.tolist()
    np.testing.assert_allclose(got["av"], sv / counts, rtol=1e-15)


def test_small_unique_build_join_carries_int64_payload_exactly(sess):
    """A star join against a small unique build (500 rows, some probe
    keys missing from it), grouped by two payload columns of the build:
    every sum and count equals the pandas join's, int64 for int64."""
    import pandas as pd

    rng = np.random.default_rng(4)
    nd, nf = 500, 40_000
    dim = pd.DataFrame({"k": np.arange(nd),
                        "name": [f"n{i % 37}" for i in range(nd)],
                        "grp": rng.integers(0, 9, nd)})
    fact = pd.DataFrame({"k": rng.integers(0, nd + 50, nf),
                         "v": rng.integers(0, 1000, nf),
                         "amt": rng.integers(0, 10**6, nf)})
    sess.sql("create table dim (k bigint, name text, grp bigint) "
             "distributed by (k)")
    sess.sql("create table fact (k bigint, v bigint, amt decimal(12,2)) "
             "distributed by (k)")
    sess.sql("insert into dim values " + ", ".join(
        f"({r.k}, '{r.name}', {r.grp})" for r in dim.itertuples()))
    sess.catalog.table("fact").set_data(
        {c: fact[c].to_numpy() for c in fact})
    batch = sess.sql(
        "select grp, name, sum(v) as sv, sum(amt) as sa, count(*) as n "
        "from fact join dim on fact.k = dim.k "
        "group by grp, name order by grp, name")
    want = (fact.merge(dim, on="k").groupby(["grp", "name"])
            .agg(sv=("v", "sum"), sa=("amt", "sum"), n=("v", "size"))
            .reset_index().sort_values(["grp", "name"]))
    got = batch.to_pandas()
    assert got["grp"].tolist() == want["grp"].tolist()
    assert got["name"].tolist() == want["name"].tolist()
    sel = np.asarray(batch.sel)
    for c in ("sv", "sa", "n"):     # raw: sa in cents, as stored
        assert np.asarray(batch.columns[c])[sel].tolist() == \
            want[c].tolist(), c
