"""Milliseconds per Krylov iteration: the window's host seconds over every
iteration its solves ran."""


def read(rec):
    total = sum(s["iters"] for s in rec["solves"])
    if not total:
        return None
    return rec["window_s"] * 1e3 / total
