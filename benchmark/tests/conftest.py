"""The benchmark's own tests run on the CPU, like the suite under tests/:
pinned before any backend starts, with the persistent compile cache off
unless a directory was placed from outside."""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

_BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (_BENCH, os.path.dirname(_BENCH)):
    if _p not in sys.path:
        sys.path.insert(0, _p)
