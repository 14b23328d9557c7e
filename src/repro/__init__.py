"""repro -- ParaLiNGAM on TPU: causal structure learning + LM substrate.

A production-grade JAX framework reproducing and extending

    Shahbazinia, Salehkaleybar, Hashemi,
    \"ParaLiNGAM: Parallel Causal Structure Learning for Linear
     non-Gaussian Acyclic Models\" (2021).

Subpackages: core (the paper), kernels (Pallas + oracles), models,
configs, data, train, serve, dist, launch, utils.
"""

__version__ = "1.0.0"

# Stable public surface. These five names (plus __version__) are the
# supported API; everything else is internal and may move between releases.
from repro.core.paralingam import (
    ParaLiNGAMConfig,
    ParaLiNGAMResult,
    fit,
    fit_batch,
)
from repro.serve.async_engine import AsyncLingamEngine

__all__ = [
    "AsyncLingamEngine",
    "ParaLiNGAMConfig",
    "ParaLiNGAMResult",
    "__version__",
    "fit",
    "fit_batch",
]
