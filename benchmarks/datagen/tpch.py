"""TPC-H tables from a seed, in key-range chunks: the benchmark's own copy.

Copied in shape from ``tools/tpchgen.py`` ("tpchgen-lite"): row counts,
column domains and the derived-column rules (return flags, line statuses,
date chains, prices from part keys) follow the TPC-H specification §4.2;
keys are dense and comments are two to four words from a short list,
which is NOT dbgen's text grammar (``assumed`` in every configuration).
It imports nothing of the engine. Money is generated as whole cents and
dates as days since 1970-01-01, so the plain reference works on exact
integers; :func:`for_engine` renders a chunk the way a loader hands it to
the system (decimals as dollars, strings as objects).

Every chunk has a random stream of its own, keyed by (seed, table, chunk),
so chunks can be made in any order and on any thread. ``--seed`` may be
any whole number: ``numpy.random.default_rng`` takes them all.
"""

from __future__ import annotations

import numpy as np

SEGMENTS = ["AUTOMOBILE", "BUILDING", "FURNITURE", "HOUSEHOLD", "MACHINERY"]
PRIORITIES = ["1-URGENT", "2-HIGH", "3-MEDIUM", "4-NOT SPECIFIED", "5-LOW"]
SHIPMODES = ["REG AIR", "AIR", "RAIL", "SHIP", "TRUCK", "MAIL", "FOB"]
INSTRUCTS = ["DELIVER IN PERSON", "COLLECT COD", "NONE", "TAKE BACK RETURN"]
WORDS = ["carefully", "quickly", "furiously", "slyly", "blithely", "ironic",
         "final", "special", "pending", "regular", "express", "bold",
         "even", "silent", "daring", "unusual", "packages", "deposits",
         "requests", "accounts", "theodolites", "instructions", "platelets",
         "foxes", "ideas", "dependencies", "pinto beans", "warhorses"]

_EPOCH = np.datetime64("1970-01-01", "D")


def days(iso: str) -> int:
    """Days since 1970-01-01 of an ISO date."""
    return int((np.datetime64(iso, "D") - _EPOCH).astype(np.int64))


START, END = days("1992-01-01"), days("1998-08-02")
CURRENT = days("1995-06-17")

# which columns are money (whole cents here, DECIMAL(2) dollars in the
# engine) and which are dates (days here and in the engine)
CENTS = {"lineitem": ("l_quantity", "l_extendedprice", "l_discount", "l_tax"),
         "orders": ("o_totalprice",), "customer": ("c_acctbal",)}
TABLE_ID = {"customer": 3, "orders": 6}
# the key range a chunk covers, in rows of the table that drives it
DRIVER = {"lineitem": "orders", "orders": "orders", "customer": "customer"}


def sizes(scale: float) -> dict:
    """Row counts of the spec's §4.2.5 at a scale factor (lineitem's
    follows from the orders: one to seven lines each)."""
    return {"supplier": max(int(10_000 * scale), 10),
            "customer": max(int(150_000 * scale), 30),
            "part": max(int(200_000 * scale), 40),
            "orders": max(int(1_500_000 * scale), 150)}


def chunk_ranges(table: str, scale: float, chunk_rows: int) -> list:
    """[(chunk index, lo, hi)] over the driving table's keys."""
    total = sizes(scale)[DRIVER[table]]
    return [(i, lo, min(lo + chunk_rows, total))
            for i, lo in enumerate(range(0, total, chunk_rows))]


def _rng(seed: int, table: str, chunk: int):
    return np.random.default_rng([int(seed), 0xC8, TABLE_ID[table], chunk])


def _pick(rng, vocab: list, n: int) -> np.ndarray:
    return np.asarray(vocab, dtype=object)[rng.integers(0, len(vocab), n)]


_PAIRS = np.asarray([a + " " + b for a in WORDS for b in WORDS], dtype=object)


def _comments(rng, n: int, nwords: int) -> np.ndarray:
    """Two or four words: picked as whole pairs, so that a million
    comments cost one indexing pass and not a string add per word."""
    out = _PAIRS[rng.integers(0, len(_PAIRS), n)]
    if nwords == 4:
        out = out + " " + _PAIRS[rng.integers(0, len(_PAIRS), n)]
    return out


def _tag(prefix: str, keys: np.ndarray) -> np.ndarray:
    return np.char.mod(prefix + "#%09d", keys).astype(object)


def customer_chunk(seed: int, chunk: int, lo: int, hi: int,
                   scale: float) -> dict:
    rng = _rng(seed, "customer", chunk)
    ck = np.arange(lo + 1, hi + 1, dtype=np.int64)
    n = len(ck)
    phone = np.char.add(np.char.add(np.char.mod("%d", 10 + ck % 25),
                                    np.char.mod("-%03d", ck % 1000)),
                        np.char.mod("-%04d", ck % 10000)).astype(object)
    return {"customer": {
        "c_custkey": ck, "c_name": _tag("Customer", ck),
        "c_address": _comments(rng, n, 2),
        "c_nationkey": rng.integers(0, 25, n).astype(np.int64),
        "c_phone": phone,
        "c_acctbal": rng.integers(-99999, 999999 + 1, n),
        "c_mktsegment": _pick(rng, SEGMENTS, n),
        "c_comment": _comments(rng, n, 4)}}


def orders_chunk(seed: int, chunk: int, lo: int, hi: int,
                 scale: float) -> dict:
    """Orders lo+1..hi AND their lineitems: statuses and totals follow
    from the chunk's own lines, so every chunk is whole in itself."""
    sz = sizes(scale)
    rng = _rng(seed, "orders", chunk)
    ok = np.arange(lo + 1, hi + 1, dtype=np.int64)
    n_ord = len(ok)
    # customers whose key is a multiple of 3 place no orders (§4.2.3)
    idx = rng.integers(0, sz["customer"] - sz["customer"] // 3, n_ord)
    o_custkey = 3 * (idx // 2) + 1 + (idx % 2)
    o_orderdate = rng.integers(START, END + 1, n_ord).astype(np.int64)
    lines_per = rng.integers(1, 8, n_ord)
    l_ok = np.repeat(ok, lines_per)
    n = len(l_ok)
    l_odate = np.repeat(o_orderdate, lines_per)
    l_ship = l_odate + rng.integers(1, 122, n)
    l_commit = l_odate + rng.integers(30, 91, n)
    l_receipt = l_ship + rng.integers(1, 31, n)
    flag = np.asarray(["R", "A", "N"], dtype=object)[np.where(
        l_receipt <= CURRENT, (rng.random(n) < 0.5).astype(np.int64), 2)]
    shipped = l_ship <= CURRENT
    status = np.asarray(["O", "F"], dtype=object)[shipped.astype(np.int64)]
    qty = rng.integers(1, 51, n)
    l_pk = rng.integers(1, sz["part"] + 1, n).astype(np.int64)
    l_sk = ((l_pk + rng.integers(0, 4, n) * (sz["supplier"] // 4 + 1))
            % sz["supplier"]) + 1
    retail_cents = 90000 + (l_pk % 20001) + 100 * (l_pk % 1000)
    price_cents = qty * retail_cents

    first = np.cumsum(lines_per) - lines_per      # each order's first line
    n_f = np.add.reduceat(shipped.astype(np.int64), first)
    o_status = np.full(n_ord, "P", dtype=object)
    o_status[n_f == lines_per] = "F"
    o_status[n_f == 0] = "O"
    orders = {
        "o_orderkey": ok, "o_custkey": o_custkey, "o_orderstatus": o_status,
        "o_totalprice": np.add.reduceat(price_cents, first),
        "o_orderdate": o_orderdate,
        "o_orderpriority": _pick(rng, PRIORITIES, n_ord),
        "o_clerk": _tag("Clerk", rng.integers(
            1, max(sz["orders"] // 1000, 2), n_ord)),
        "o_shippriority": np.zeros(n_ord, dtype=np.int32),
        "o_comment": _comments(rng, n_ord, 4)}
    lineitem = {
        "l_orderkey": l_ok, "l_partkey": l_pk,
        "l_suppkey": l_sk.astype(np.int64),
        "l_linenumber": (np.arange(n) - np.repeat(first, lines_per)
                         + 1).astype(np.int32),
        "l_quantity": qty * 100, "l_extendedprice": price_cents,
        "l_discount": rng.integers(0, 11, n),
        "l_tax": rng.integers(0, 9, n),
        "l_returnflag": flag, "l_linestatus": status,
        "l_shipdate": l_ship, "l_commitdate": l_commit,
        "l_receiptdate": l_receipt,
        "l_shipinstruct": _pick(rng, INSTRUCTS, n),
        "l_shipmode": _pick(rng, SHIPMODES, n),
        "l_comment": _comments(rng, n, 2)}
    return {"orders": orders, "lineitem": lineitem}


CHUNK_FN = {"orders": orders_chunk, "customer": customer_chunk}


def for_engine(table: str, cols: dict) -> dict:
    """A chunk as a loader hands it over: DECIMAL(2) columns as dollars
    (the engine's ``encode_column`` rounds value x 100 back to the same
    whole cents), everything else as it is."""
    money = CENTS.get(table, ())
    return {c: (v / 100.0 if c in money else v) for c, v in cols.items()}
