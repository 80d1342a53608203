"""TPC-H Q13 (spec §2.4.13), the plain reference: counts over the
generator's arrays. Parameters (§2.4.13.3): ``word1``, the index of WORD1
in (special, pending, unusual, express), and ``word2``, that of WORD2 in
(packages, requests, accounts, deposits); validation values special, 0,
and requests, 1. A mix's grid holds whole numbers only, so both are
numbered here and written out by :func:`bind`.

The generator's comments are words of a short list, not dbgen's text
grammar (``assumed`` in the configuration): the pattern matches a comment
in which the first word comes before the second, found here by plain
string search.

One row for each number of orders a customer can have placed (customers
with none included: the outer join's), with the customers that placed so
many; most customers first, then the higher number."""

import numpy as np

TABLES = ("customer", "orders")
COLUMNS = {"customer": ("c_custkey",),
           "orders": ("o_orderkey", "o_custkey", "o_comment")}
WORD1 = ("special", "pending", "unusual", "express")
WORD2 = ("packages", "requests", "accounts", "deposits")


def _words(params: dict) -> tuple:
    return WORD1[int(params["word1"])], WORD2[int(params["word2"])]


def bind(params: dict) -> dict:
    """What the statement's text takes from one draw of parameters."""
    w1, w2 = _words(params)
    return {"word1": w1, "word2": w2}


def like(comments: np.ndarray, w1: str, w2: str) -> np.ndarray:
    """``comment LIKE '%w1%w2%'``: w1 somewhere, w2 somewhere after its
    end. Searched once for each distinct comment."""
    distinct, code = np.unique(comments, return_inverse=True)
    hit = np.fromiter(
        ((at := c.find(w1)) >= 0 and c.find(w2, at + len(w1)) >= 0
         for c in distinct.tolist()), dtype=bool, count=len(distinct))
    return hit[code]


def answer(tables: dict, params: dict, acc=np.int64) -> dict:
    """Rows as the wire carries them: orders placed, customers. ``acc``
    is the type the counts are taken in: int64 is the reference; the
    control passes a narrower one, which counts of this size survive."""
    cu, od = tables["customer"], tables["orders"]
    counted = ~like(od["o_comment"], *_words(params))
    per_customer = np.zeros(int(max(cu["c_custkey"].max(),
                                    od["o_custkey"].max())) + 1, dtype=acc)
    np.add.at(per_customer, od["o_custkey"][counted], acc(1))
    c_count = per_customer[cu["c_custkey"]].astype(np.int64)
    values, custdist = np.unique(c_count, return_counts=True)
    order = np.lexsort((-values, -custdist))
    return {"columns": ["c_count", "custdist"],
            "rows": [[int(values[i]), int(custdist[i])] for i in order]}
