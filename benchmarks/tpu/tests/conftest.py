"""CPU tests of the benchmark (run explicitly; they sit outside the repo's
``tests`` tree): ``JAX_PLATFORMS=cpu python -m pytest benchmarks/tpu/tests``."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# four CPU devices for the tensor-parallel tests, fixed before JAX starts
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=4").strip()
os.environ["REPRO_AUTOTUNE"] = "0"
HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(BENCH)), "src"))
DATA = os.path.join(HERE, "data")
