import numpy as np
import pytest
from hypothesis import settings

# fast hypothesis profile: CI-sized example counts
settings.register_profile("repro", max_examples=25, deadline=None)
settings.load_profile("repro")


@pytest.fixture
def rng():
    return np.random.default_rng(0)
