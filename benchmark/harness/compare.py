"""The comparison that decides `correct`: served rows against the reference.

Decimals travel as exact canonical text, keys and counts as ints, dates as
dates, flags as text: those must be EQUAL. Doubles may differ by the relative
tolerance the configuration states. Rows are compared in order (every query
here has an ORDER BY or one row).
"""
import datetime
from decimal import Decimal


def canon(d):
    """Exact decimal text, no exponent, no trailing zeros."""
    return format(Decimal(d).normalize(), "f")


def dec(scaled, scale):
    """Exact text of scaled / 10^scale."""
    return canon(Decimal(int(scaled)).scaleb(-scale))


def typed(rows, description):
    """Wire rows -> comparable values, by the column kinds the cursor gives."""
    kinds = [d[1] for d in description]
    out = []
    for row in rows:
        vals = []
        for v, kind in zip(row, kinds):
            if v is not None and kind == "decimal":
                v = canon(v)
            elif v is not None and kind == "date":
                v = datetime.date.fromisoformat(v)
            vals.append(v)
        out.append(tuple(vals))
    return out


def compare_rows(got, want):
    """-> (cells that are not equal, widest relative gap of a double)."""
    if len(got) != len(want):
        return max(len(got), len(want), 1), 0.0
    wrong, gap = 0, 0.0
    for g_row, w_row in zip(got, want):
        if len(g_row) != len(w_row):
            wrong += max(len(g_row), len(w_row))
            continue
        for g, w in zip(g_row, w_row):
            if isinstance(w, float):
                if not isinstance(g, float) or g != g:
                    wrong += 1
                else:
                    gap = max(gap, abs(g - w) / max(abs(w), 1e-300))
            elif type(g) is not type(w) or g != w:
                wrong += 1
    return wrong, gap


def judge(answers, expected, rel_tol):
    """answers: [(query, rows or None)], every answer of the window.
    expected: {query: reference rows}. -> (numbers with limits, failed count).
    An answer is wrong when it never came, a cell is not equal, or a double
    lies outside the tolerance."""
    wrong_answers, wrong_cells, gap = 0, 0, 0.0
    memo = {}
    for query, rows in answers:
        if rows is None:
            wrong_answers += 1
            continue
        key = (query, tuple(rows))
        if key not in memo:
            memo[key] = compare_rows(rows, expected[query])
        cells, rel = memo[key]
        wrong_cells += cells
        gap = max(gap, rel)
        if cells or rel > rel_tol:
            wrong_answers += 1
    numbers = {
        "answers_compared": {"value": len(answers), "limit": 1, "must": ">="},
        "answers_wrong": {"value": wrong_answers, "limit": 0, "must": "<="},
        "cells_unequal": {"value": wrong_cells, "limit": 0, "must": "<="},
        "double_rel_gap": {"value": gap, "limit": rel_tol, "must": "<="},
    }
    return numbers, wrong_answers


def within(numbers):
    return all(n["value"] >= n["limit"] if n["must"] == ">=" else
               n["value"] <= n["limit"] for n in numbers.values())
