"""Share of the traced window in which the busiest device ran no
operation."""


def read(rec):
    tr = rec.get("trace")
    if not tr or tr["window_s"] <= 0:
        return None
    busy = max(d["busy_s"] for d in tr["devices"].values())
    return 100.0 * (1.0 - busy / tr["window_s"])
