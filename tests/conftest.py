"""Shared fixtures: forcing one of the fast engines' two streams."""
import pytest

from repro.core import engines
from repro.core._native import native_available


@pytest.fixture
def no_native(monkeypatch):
    """The fast engines stream on the pure-Python bitmask loop: the engine
    table observes no C kernel, as under REPRO_NO_NATIVE=1."""
    monkeypatch.setattr(engines, "native_engine", lambda: None)


@pytest.fixture(params=[
    "python",
    pytest.param("native", marks=pytest.mark.skipif(
        not native_available(), reason="no C compiler available")),
])
def fast_engine(request):
    """Each fast stream in turn under `backend="fast"`: "python" forces
    the bitmask loop, "native" runs the C kernel."""
    if request.param == "python":
        request.getfixturevalue("no_native")
    return request.param
