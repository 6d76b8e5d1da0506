"""Krylov iterations per solve: ``CGResult.iters`` summed over the window's
solves, over the number of solves."""


def read(rec):
    solves = rec["solves"]
    if not solves:
        return None
    return sum(s["iters"] for s in solves) / len(solves)
