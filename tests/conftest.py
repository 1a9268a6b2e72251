# NOTE: no XLA_FLAGS here on purpose — smoke tests and benches must see the
# real (1-device) CPU topology; only launch/dryrun.py forces 512 devices.
import numpy as np
import pytest


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)


@pytest.fixture(autouse=True)
def _isolated_obs():
    """Observability hygiene: every test starts with the no-op tracer and
    a fresh metrics registry, and leaves none of its spans/series behind
    for the next test (mirrors ``_isolated_calibration``)."""
    from repro import obs

    prev_tracer = obs.set_tracer(None)
    prev_registry = obs.set_registry(None)
    yield
    obs.set_tracer(prev_tracer)
    obs.set_registry(prev_registry)


@pytest.fixture(autouse=True)
def _isolated_calibration():
    """Cost-model calibration hygiene: the module-level default store is
    emptied around every test, so one test's recorded ms/image can never
    flip another test's ``plan(model="auto")`` decision. (Index-scoped
    stores are per-instance and need no guard.)"""
    from repro.core.engine import costmodel

    costmodel.reset_default_calibration()
    yield
    costmodel.reset_default_calibration()
