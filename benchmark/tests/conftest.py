"""benchmark/tests: CPU rehearsals of the harness and checks of its
arithmetic.  Run by hand, not part of tier-1:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (os.path.dirname(os.path.abspath(__file__)), REPO):
    if p not in sys.path:
        sys.path.insert(0, p)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
