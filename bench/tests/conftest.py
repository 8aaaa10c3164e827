"""The benchmark's own tests run on the CPU, with four virtual devices for
the data-parallel cases:

    python -m pytest -q bench/tests
"""
import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=4").strip()

import helpers  # noqa: E402,F401  (puts bench/ and src/ on sys.path)
