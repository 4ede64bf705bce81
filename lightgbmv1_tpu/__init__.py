"""lightgbmv1_tpu — a TPU-native gradient-boosted decision tree framework.

A from-scratch re-design of LightGBM (the reference at
dreaming-panda/LightGBMv1) for TPU hardware: histograms on the MXU via
one-hot matmuls and Pallas kernels, on-device leaf-wise tree growth under
jit, and multi-chip data/feature parallelism via jax.sharding + shard_map
with XLA collectives over ICI — no sockets, no MPI.

The Python API mirrors the reference's python-package (Dataset / Booster /
train / cv / sklearn wrappers) so existing LightGBM scripts port with an
import change.
"""

import os

from .config import Config
from .utils.log import LightGBMError, register_callback, set_verbosity

__version__ = "0.1.0"


def _place_compile_cache() -> None:
    """Give JAX's persistent compilation cache a home every entry point
    shares.  ``JAX_COMPILATION_CACHE_DIR`` set from outside wins and this
    sets nothing (JAX reads the variable itself).  Otherwise the cache
    lives at ``<checkout>/.jax_cache``, derived from this file's own
    location: the directory is part of the cache key, so it must be the
    same for every process of every run — never a temp dir, a pid or a
    time.  A config update only; no device is touched."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    import jax

    checkout = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    jax.config.update("jax_compilation_cache_dir",
                      os.path.join(checkout, ".jax_cache"))


_place_compile_cache()

__all__ = [
    "Config",
    "LightGBMError",
    "register_callback",
    "set_verbosity",
    "Dataset",
    "Booster",
    "train",
    "cv",
    "CVBooster",
    "LGBMModel",
    "LGBMRegressor",
    "LGBMClassifier",
    "LGBMRanker",
    "early_stopping",
    "log_evaluation",
    "record_evaluation",
    "reset_parameter",
    "plot_importance",
    "plot_metric",
    "plot_split_value_histogram",
    "plot_tree",
    "create_tree_digraph",
]


def __getattr__(name):
    # lazy imports keep `import lightgbmv1_tpu` light and avoid cycles
    if name in ("Dataset", "Booster"):
        from . import basic

        return getattr(basic, name)
    if name in ("train", "cv", "CVBooster"):
        from . import engine

        return getattr(engine, name)
    if name in ("LGBMModel", "LGBMRegressor", "LGBMClassifier", "LGBMRanker"):
        from . import sklearn

        return getattr(sklearn, name)
    if name in ("early_stopping", "log_evaluation", "print_evaluation",
                "record_evaluation", "reset_parameter"):
        from . import callback

        return getattr(callback, name)
    if name in ("plot_importance", "plot_metric", "plot_split_value_histogram",
                "plot_tree", "create_tree_digraph"):
        from . import plotting

        return getattr(plotting, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
