"""CPU rehearsal of the benchmark: ``pytest benchmark/tests`` from the root.

Held to the CPU before JAX starts; the persistent compile cache is off so a
test run leaves nothing behind and reads nothing stale."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
