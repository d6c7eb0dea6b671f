import os
import sys

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


import pytest  # noqa: E402


@pytest.fixture
def small_program(monkeypatch):
    """The stack builds the program at the configuration's own sizes."""
    from chipbench import stack
    from small_cells import small_arch
    monkeypatch.setattr(stack, "arch_of", small_arch)
