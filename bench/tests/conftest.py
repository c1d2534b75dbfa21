import os
import sys

# the benchmark's tests run on the CPU, at smoke sizes
os.environ.setdefault("JAX_PLATFORMS", "cpu")
_SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "src")
if _SRC not in sys.path:
    sys.path.insert(0, _SRC)
