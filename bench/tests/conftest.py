"""CPU tests of the benchmark (outside the repository's ``testpaths``):

    JAX_PLATFORMS=cpu python -m pytest bench/tests -q

from the root of a checkout."""

import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
for p in (REPO, os.path.join(REPO, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
