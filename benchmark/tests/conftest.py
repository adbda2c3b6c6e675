"""CPU rehearsals of the benchmark: `python -m pytest benchmark/tests -q`."""
import json
import os
import sys

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")

from benchmark.tests.util import REPO  # noqa: E402

if REPO not in sys.path:
    sys.path.insert(0, REPO)


@pytest.fixture(scope="session")
def manifest():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)
