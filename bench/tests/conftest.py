"""The self-tests run on the CPU: the platform from ``src``, JAX on its
CPU backend unless the caller chose another."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))), "src"))
