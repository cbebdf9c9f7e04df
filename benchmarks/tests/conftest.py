"""The benchmark's own tests run on the CPU: pinned before anything
initialises a JAX backend, as the repository's tests/conftest.py does."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from ratis_tpu.util.jaxenv import pin_cpu

pin_cpu()
