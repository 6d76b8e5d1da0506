"""Share of the HBM roofline that a whole MG-PCG iteration reaches, the
V-cycle's smoothing sweeps, residual and transfers with the CG iteration:
``iter_roofline.py``'s reading, the least bytes of the window's iterations
(``bench/work/pcg_mg.py``, by tag) over the device busy time times the
chip's peak HBM bandwidth."""
from bench.metrics.iter_roofline import read  # noqa: F401
