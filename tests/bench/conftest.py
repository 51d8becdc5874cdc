"""The benchmark's tests: the repository root (for ``bench``) and this
directory (for ``tiny``) on the import path, and no persistent
compilation cache (a CPU test writes nothing a chip run could read)."""

import os
import sys

import pytest

_HERE = os.path.dirname(__file__)
for _p in (os.path.abspath(os.path.join(_HERE, "..", "..")), _HERE):
    if _p not in sys.path:
        sys.path.insert(0, _p)


@pytest.fixture
def no_cache(monkeypatch):
    from bench import run as R
    monkeypatch.setattr(R, "enable_cache", lambda: None)
    return R
