"""Share of the device's scoped time that the preconditioner takes: the
seconds under ``precond`` and its children (``precond/smooth``,
``precond/residual``, ``precond/transfer``) over the seconds under every
scope, ``unscoped`` included, from the scope reduction
(``bench/scope_reduce.py``) of the traced window."""


def read(rec):
    scopes = (rec.get("trace") or {}).get("scopes")
    if not scopes:
        return None
    total = sum(v["seconds"] for v in scopes.values())
    mg = sum(v["seconds"] for k, v in scopes.items()
             if k.split("/")[0] == "precond")
    if total <= 0 or mg <= 0:
        return None
    return 100.0 * mg / total
