"""Share of the traced window that the chips spend in collective
operations (the halo all-gather, which lowers to all-reduce ops, and the
``psum`` dots), averaged over the chips."""


def read(rec):
    tr = rec.get("trace")
    if not tr or tr["window_s"] <= 0:
        return None
    devs = tr["devices"].values()
    coll = sum(d["collective_s"] for d in devs) / len(devs)
    if coll <= 0:
        return None
    return 100.0 * coll / tr["window_s"]
