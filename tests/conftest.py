"""Shared fixtures.  NOTE: no XLA_FLAGS here — smoke tests and benches see
the real single CPU device; only the dry-run (and explicit subprocess
tests) force 512/8 host devices."""
import os
import sys

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")
if SRC not in sys.path:
    sys.path.insert(0, SRC)

# pytest-xdist workers share the machine's cores.  A multithreaded OpenBLAS
# in every worker spins against the others and slows the planner's small
# LP solves ~10x, past the wall-time budget (bb_time_s) that some fixtures
# plan under.  numpy is already loaded here, so an environment variable
# would come too late: cap the loaded BLAS at one thread per worker.
try:
    from threadpoolctl import threadpool_limits
except ImportError:  # pragma: no cover - only the thread cap is lost
    pass
else:
    threadpool_limits(limits=1, user_api="blas")


@pytest.fixture(scope="session")
def null_policy():
    from repro.sharding.policy import ShardingPolicy
    return ShardingPolicy(mesh=None)


@pytest.fixture(scope="session")
def social_profiler():
    from repro.core.apps import get_app
    from repro.core.profiler import Profiler
    g = get_app("social_media")
    return g, Profiler(g)


@pytest.fixture(scope="session")
def traffic_profiler():
    from repro.core.apps import get_app
    from repro.core.profiler import Profiler
    g = get_app("traffic_analysis")
    return g, Profiler(g)
