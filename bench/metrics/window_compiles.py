"""XLA compilations inside the measured window, persistent-cache loads
included: ``jax.monitoring`` backend-compile events between the window's
first solve and the end of its last."""


def read(rec):
    return float(rec["window_compiles"])
