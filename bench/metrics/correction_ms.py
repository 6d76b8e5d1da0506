"""Milliseconds a solve spends in its final correction: the seconds inside
the program's ``solve.correction`` host spans, read from the profile
(``bench/scope_reduce.py``), over the window's solves."""


def read(rec):
    tr = rec.get("trace")
    span = (tr or {}).get("spans", {}).get("solve.correction")
    if not span or not rec["solves"]:
        return None
    return 1e3 * span["seconds"] / len(rec["solves"])
