"""Shared fixtures: small synthetic references/read sets.

NOTE: no XLA_FLAGS device-count overrides here — smoke tests and benches
must see the single real CPU device.  Tests of mesh paths force host
devices in subprocesses of their own.
"""
import numpy as np
import pytest

from repro.core import MarsConfig, build_index
from repro.signal import simulate


@pytest.fixture(scope="session")
def small_ref():
    return simulate.make_reference(50_000, seed=3)


@pytest.fixture(scope="session")
def medium_ref():
    return simulate.make_reference(200_000, seed=7)


@pytest.fixture(scope="session")
def cfg_fixed():
    return MarsConfig().with_mode("ms_fixed")


@pytest.fixture(scope="session")
def cfg_float():
    return MarsConfig().with_mode("ms_float")


@pytest.fixture(scope="session")
def cfg_rh2():
    return MarsConfig().with_mode("rh2")


@pytest.fixture(scope="session")
def small_index(small_ref, cfg_fixed):
    return build_index(small_ref.events_concat, small_ref.n_events, cfg_fixed)


@pytest.fixture(scope="session")
def small_reads(small_ref, cfg_fixed):
    return simulate.sample_reads(small_ref, 16,
                                 signal_len=cfg_fixed.signal_len, seed=4,
                                 junk_frac=0.125)
