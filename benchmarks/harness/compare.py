"""What decides `correct`: each answer the window returned against the
plain reference's answer on the same generated data. Numbers, each with a
limit; the limits of a query are in its own file under queries/."""

import numpy as np


def answer_gap(got, ref):
    """{number: value} for one answer: shape and names, cells of exact
    columns that differ, and the widest relative gap of a float cell."""
    out = {"columns_differ": 0, "rows_differ": 0, "exact_cells_differ": 0,
           "float_rel_gap": 0.0}
    if got is None:
        out["rows_differ"] = len(ref)
        return out
    if list(got.columns) != list(ref.columns):
        out["columns_differ"] = len(set(got.columns) ^ set(ref.columns)) or 1
        return out
    if len(got) != len(ref):
        out["rows_differ"] = abs(len(got) - len(ref))
        return out
    for c in ref.columns:
        g, r = got[c].to_numpy(), ref[c].to_numpy()
        if r.dtype.kind == "f" or g.dtype.kind == "f":
            g, r = g.astype(np.float64), r.astype(np.float64)
            both_nan = np.isnan(g) & np.isnan(r)
            scale = np.maximum(np.abs(r), np.finfo(np.float64).tiny)
            gap = np.where(both_nan, 0.0, np.abs(g - r) / scale)
            gap = np.where(np.isnan(gap), np.inf, gap)
            if len(gap):
                out["float_rel_gap"] = max(out["float_rel_gap"],
                                           float(gap.max()))
        elif g.dtype.kind in "biu" and r.dtype.kind in "biu":
            out["exact_cells_differ"] += int(
                (g.astype(np.int64) != r.astype(np.int64)).sum())
        else:
            out["exact_cells_differ"] += int(
                (g.astype(object) != r.astype(object)).sum())
    return out


EXACT = ("answers_missing", "columns_differ", "rows_differ",
         "exact_cells_differ")


def judge(per_answer, missing, limits):
    """Worst of each number over all answers, beside its limit.
    Returns (correct, [{"name", "value", "limit"}...])."""
    worst = {**dict.fromkeys(EXACT, 0), "float_rel_gap": 0.0,
             "answers_missing": int(missing)}
    for gap in per_answer:
        for k, v in gap.items():
            worst[k] = max(worst[k], v)
    compared, ok = [], True
    for k, v in worst.items():
        limit = 0 if k in EXACT else limits[k]
        compared.append({"name": k, "value": v, "limit": limit})
        ok = ok and v <= limit
    if not per_answer:
        ok = False
    return ok, compared
