"""Test configuration.

NOTE: we deliberately do NOT set XLA_FLAGS / host device count here --
smoke tests and benchmarks must see the single real CPU device.  Only
launch/dryrun.py (and the distributed tests that spawn subprocesses) use
placeholder device counts.

float64 is enabled because the paper's reference arithmetic is FP64; model
code passes explicit dtypes everywhere so this does not perturb LM tests.
"""
import jax

jax.config.update("jax_enable_x64", True)
# Tests never read or write the persistent compile cache, even where a
# module they import (benchmarks.common) points it at the checkout.
jax.config.update("jax_enable_compilation_cache", False)
