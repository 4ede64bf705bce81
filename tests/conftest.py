"""Test configuration: force the CPU backend with 8 virtual devices so
multi-chip sharding tests run on any host (SURVEY.md §4 lesson — multi-chip
parity is a first-class CI test here, unlike the reference)."""

import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=8 " + os.environ.get("XLA_FLAGS", "")
)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
# The package places a persistent compilation cache at <checkout>/.jax_cache
# (lightgbmv1_tpu/__init__.py).  The CPU suite turns the cache off — through
# the environment, so the worker processes the tests spawn inherit it:
# tier-1 must not write into the checkout, and XLA:CPU entries must not be
# there for the chip tool to copy onto a machine with other CPU features.
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

import jax

jax.config.update("jax_platforms", "cpu")

import numpy as np
import pytest

# Tier-1 wall-budget accounting (tools/tier1_budget.py): when
# LGBMV1_T1_DURATIONS names a file, every test phase's duration is
# appended as one JSON line, so the budget tool can project the tier-1
# wall against the driver's 870 s budget and rank the worst offenders
# without re-running the suite.
_DUR_PATH = os.environ.get("LGBMV1_T1_DURATIONS")


def pytest_runtest_logreport(report):
    if _DUR_PATH:
        import json

        with open(_DUR_PATH, "a") as fh:
            fh.write(json.dumps({
                "nodeid": report.nodeid, "when": report.when,
                "duration": round(report.duration, 4),
                "outcome": report.outcome,
            }) + "\n")


@pytest.fixture
def rng():
    return np.random.RandomState(42)


def make_binary_problem(n=1500, f=8, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, f)
    logit = 1.5 * X[:, 0] - X[:, 1] + 0.8 * X[:, 2] * X[:, 3] + 0.5 * np.sin(X[:, 4])
    y = (logit + rng.randn(n) * 0.4 > 0).astype(np.float64)
    return X, y


def make_regression_problem(n=1500, f=8, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, f)
    y = 2.0 * X[:, 0] - X[:, 1] + 0.5 * X[:, 2] * X[:, 3] + rng.randn(n) * 0.1
    return X, y
