"""Shared fixtures.  NOTE: no XLA_FLAGS here — tests must see 1 CPU device
(the 512-device override belongs exclusively to repro/launch/dryrun.py)."""
from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp

# ``hypothesis`` is a [test] extra, not a hard requirement: on a bare
# environment the property-based tests must degrade to skips instead of
# killing collection.  The stub installs a minimal fake into sys.modules
# before any test module runs its own ``from hypothesis import ...``.
try:
    from hypothesis import HealthCheck, settings
except ModuleNotFoundError:
    from _hypothesis_stub import install as _install_hypothesis_stub

    _install_hypothesis_stub()
    from hypothesis import HealthCheck, settings

# jit compilation makes individual examples slow; disable deadlines globally
settings.register_profile(
    "repro",
    deadline=None,
    max_examples=25,
    suppress_health_check=[HealthCheck.too_slow, HealthCheck.data_too_large],
)
settings.load_profile("repro")


@pytest.fixture(scope="session")
def paper_problem():
    """The §5.1 setup: 230 nodes, degree 3..6, weights mean 5, K=5, mu=8."""
    from repro.core.problem import make_problem
    from repro.graphs.generators import random_degree_graph, random_weights

    adj = random_degree_graph(230, seed=0)
    b, c = random_weights(adj, seed=1, mean=5.0)
    prob = make_problem(c, b, [0.1, 0.2, 0.3, 0.3, 0.1], mu=8.0)
    return adj, prob


def small_problem(n=24, k=3, seed=0, mu=4.0):
    from repro.core.problem import make_problem
    from repro.graphs.generators import random_degree_graph, random_weights

    adj = random_degree_graph(n, seed=seed, dmin=2, dmax=4)
    b, c = random_weights(adj, seed=seed + 1, mean=5.0)
    speeds = np.random.default_rng(seed + 2).uniform(0.5, 2.0, size=k)
    return adj, make_problem(c, b, speeds, mu=mu)


@pytest.fixture(scope="session")
def simultaneous_ref():
    """Checker against the recorded outputs of the §4.5 simultaneous mode
    (``tests/data/refine_simultaneous_ref.npz``, keyed by case prefix):
    ``check(prefix, result, (c0s, ct0s, active))`` asserts the final
    assignment, loads, move/turn/sweep counts and per-sweep potentials
    and ``active`` flags equal the recorded ones bitwise."""
    from pathlib import Path

    ref = np.load(Path(__file__).parent / "data"
                  / "refine_simultaneous_ref.npz")

    def check(prefix, result, outs):
        c0s, ct0s, active = outs
        got = dict(assignment=result.assignment, loads=result.loads,
                   num_moves=result.num_moves, num_turns=result.num_turns,
                   num_sweeps=result.num_sweeps, c0s=c0s, ct0s=ct0s,
                   active=active)
        for field, value in got.items():
            want = ref[f"{prefix}/{field}"]
            value = np.asarray(value)
            assert value.dtype == want.dtype, (prefix, field, value.dtype)
            np.testing.assert_array_equal(value, want,
                                          err_msg=f"{prefix}/{field}")

    return check
