"""Shared fixtures. NOTE: XLA_FLAGS device-count forcing must NOT be set here
(smoke tests and benches see the single real CPU device; only launch/dryrun.py
forces 512 placeholder devices, in its own process)."""
import os

# keep XLA quiet and single-threaded compile deterministic-ish on the 1-core box
os.environ.setdefault("JAX_PLATFORMS", "cpu")

# ambient static analysis ON under the test suite: every PassManager run,
# every compiled netlist and every evaluated population is verified
# (repro.verify). Production sweeps leave REPRO_VERIFY unset and pay
# nothing. Export REPRO_VERIFY=0 to profile the unverified paths.
os.environ.setdefault("REPRO_VERIFY", "1")

import jax
import numpy as np
import pytest


def pytest_report_header(config):
    """Tier-1 must collect on a bare interpreter: property-based modules
    import hypothesis through tests/_hypothesis_compat.py, which downgrades
    @given tests to clean skips when it is absent."""
    try:
        import hypothesis
        return f"hypothesis: {hypothesis.__version__} (property tests active)"
    except ImportError:
        return ("hypothesis: NOT INSTALLED — property-based tests will be "
                "skipped (pip install -r requirements-dev.txt)")


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)


@pytest.fixture()
def key():
    return jax.random.PRNGKey(0)


@pytest.fixture(scope="session")
def own_pack():
    """Runner for evaluations that must score netlists of their own: it
    empties `batch_eval`'s pack cache, calls ``fn(*a, **kw)`` and checks
    that no packed node table was read back from the cache (the
    ``netlist_sim.pack_hits`` counter did not move). Two evaluations of
    one spec compared through it simulate two independently packed
    netlists, not one cached table."""
    from repro.core import batch_eval as BE
    from repro.obs import metrics as MT

    def run(fn, *a, **kw):
        BE._PACK_CACHE.clear()
        hits = MT.counter("netlist_sim.pack_hits").value
        out = fn(*a, **kw)
        assert MT.counter("netlist_sim.pack_hits").value == hits
        return out
    return run
