"""The comparison that decides ``correct``: what the window's own sends
returned against the plain reference over the generator's arrays.

Once the window has closed, a sample of the (statement, parameters) draws
it answered is taken from the seed, ``compare.draws_per_statement`` of each
statement; the reference is computed once for each draw and EVERY answer
the window gave to that draw is compared with it. Beside the sample, all
answers of the window to one draw must be identical to each other.

Numbers compared, each with a limit of its own (the configuration's
``limits``):

``unanswered``     sends of the window that came back as an error
``wrong_values``   strings, integers, NULLs, column names or row counts
                   that differ from the reference (exact: limit 0)
``sum_gap_ulps``   the widest gap between a float the wire carried and
                   the reference's, in units of the reference's last
                   place, over the columns that are exact in the engine
                   (DECIMAL sums: int64 fixed point until they are rendered)
``avg_gap_ulps``   the same over the columns a reference lists under
                   ``RATIOS``: quotients the engine takes in float64 on the
                   device, where a TPU emulates float64
``draws_differ``   draws whose answers in the window were not all alike
``not_compared``   1 when no answer could be compared, else 0
"""

from __future__ import annotations

import json
import math

import numpy as np


def draw_key(stmt: str, params: dict) -> str:
    return stmt + " " + json.dumps(params, sort_keys=True)


def gap(got: dict | None, ref: dict) -> tuple:
    """(wrong exact values, {column: widest float gap in ulps}) of one
    answer."""
    if got is None or list(got.get("columns", [])) != list(ref["columns"]) \
            or len(got.get("rows", [])) != len(ref["rows"]):
        return 1, {}
    wrong, ulps = 0, {}
    for grow, rrow in zip(got["rows"], ref["rows"]):
        if len(grow) != len(rrow):
            wrong += 1
            continue
        for col, g, r in zip(ref["columns"], grow, rrow):
            if isinstance(r, float) and isinstance(g, (int, float)) \
                    and not isinstance(g, bool):
                if math.isnan(g) or math.isinf(g):
                    wrong += 1
                else:
                    u = abs(float(g) - r) / float(np.spacing(abs(r)))
                    ulps[col] = max(ulps.get(col, 0.0), u)
            elif g != r or type(g) is not type(r):
                wrong += 1
    return wrong, ulps


def sample_draws(sends: list, per_statement: int, seed: int) -> list:
    """Up to ``per_statement`` distinct draws of each statement, among
    those the window answered, chosen from the seed."""
    by_stmt: dict = {}
    for s in sends:
        if s.error is None:
            by_stmt.setdefault(s.stmt, {})[draw_key(s.stmt, s.params)] = \
                (s.stmt, s.params)
    out = []
    for i, stmt in enumerate(sorted(by_stmt)):
        keys = sorted(by_stmt[stmt])
        rng = np.random.default_rng([int(seed), 0xC0, i])
        pick = rng.permutation(len(keys))[:per_statement]
        out += [by_stmt[stmt][keys[j]] for j in sorted(pick)]
    return out


def compare(sends: list, references: dict, tables: dict, limits: dict,
            per_statement: int, seed: int) -> dict:
    """``references``: statement -> module with ``answer(tables, params)``.
    Returns {"correct": bool, "compared": {name: [number, limit]},
    "answers_compared": n, "draws_compared": n}."""
    by_draw: dict = {}
    for s in sends:
        if s.error is None:
            by_draw.setdefault(draw_key(s.stmt, s.params), []).append(s)
    differ = sum(
        1 for group in by_draw.values()
        if any(g.answer.get("rows") != group[0].answer.get("rows")
               for g in group[1:]))
    wrong, by_column, n_answers = 0, {}, 0
    draws = sample_draws(sends, per_statement, seed)
    for stmt, params in draws:
        ref = references[stmt].answer(tables, params)
        for s in by_draw[draw_key(stmt, params)]:
            w, cols = gap(s.answer, ref)
            wrong, n_answers = wrong + w, n_answers + 1
            for col, u in cols.items():
                key = f"{stmt}.{col}"
                by_column[key] = max(by_column.get(key, 0.0), u)
    ratios = {f"{stmt}.{col}" for stmt, ref in references.items()
              for col in getattr(ref, "RATIOS", ())}
    numbers = {"unanswered": sum(1 for s in sends if s.error is not None),
               "wrong_values": wrong,
               "sum_gap_ulps": max((u for c, u in by_column.items()
                                    if c not in ratios), default=0.0),
               "avg_gap_ulps": max((u for c, u in by_column.items()
                                    if c in ratios), default=0.0),
               "draws_differ": differ,
               "not_compared": 0 if n_answers else 1}
    compared = {k: [v, limits[k]] for k, v in numbers.items()}
    return {"correct": all(v <= lim for v, lim in compared.values()),
            "compared": compared, "answers_compared": n_answers,
            "draws_compared": len(draws), "gap_by_column": by_column}
