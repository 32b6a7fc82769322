"""These tests rehearse the harness on the CPU backend. They are not
part of tier-1 (``pytest tests/``); run them with
``JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q``. Nothing
here touches a TPU library at import."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
