"""Share of the HBM roofline that a whole Krylov iteration reaches, per
chip: the least bytes of the window's iterations (``bench/work/<kind>.py``,
each iteration at the tag it ran) over the device busy time times the
chip's peak HBM bandwidth (``bench/peaks.json``).

A solve's iterations split by tag as ``CGResult.switch_iters`` says, and
the iterations of the final correction run at tag 3.  Where the number of
correction iterations is not known and the split depends on it, there is
nothing to read.
"""


def tag_iterations(solve) -> dict:
    """``{tag: iterations}`` of one solve, the monitor starting at tag 1."""
    total = solve["iters"]
    corr = solve.get("correction_iters")
    s2, s3 = solve["switch_iters"]
    if corr is None:
        if solve["tag"] == 3 and s3 < 0:
            return None
        corr = 0
    first = total - corr
    end1 = s2 if s2 >= 0 else (s3 if s3 >= 0 else first)
    end2 = s3 if s3 >= 0 else first
    return {1: end1, 2: max(end2 - end1, 0) if s2 >= 0 else 0,
            3: (first - s3 if s3 >= 0 else 0) + corr}


def read(rec):
    tr = rec.get("trace")
    if not tr or not rec["solves"]:
        return None
    total = 0
    for solve in rec["solves"]:
        split = tag_iterations(solve)
        if split is None:
            return None
        total += sum(n * rec["work"].iteration_bytes(rec["shape"], tag)
                     for tag, n in split.items())
    devs = tr["devices"].values()
    busy = sum(d["busy_s"] for d in devs) / len(devs)
    if busy <= 0:
        return None
    per_chip = total / rec["shape"]["chips"]
    return 100.0 * per_chip / (busy * rec["peak"]["hbm_bytes_per_s"])
