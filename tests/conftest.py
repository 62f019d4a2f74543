"""Process-level test environment knobs (imported before any test module).

XLA:CPU's parallel LLVM codegen splits each module into up to 32 parts
compiled concurrently. The suite compiles several hundred small
executables per worker process, on hosts whose cores the parallel test
workers already occupy, so splitting buys nothing there and a long-lived
process has been seen to crash inside ``backend_compile`` with it on.
One codegen part per module keeps the suite's compile time flat and its
runs stable. Appended so job-level ``XLA_FLAGS`` (e.g. the multidevice
job's ``--xla_force_host_platform_device_count=8``) are preserved.
"""

import os

_FLAG = "--xla_cpu_parallel_codegen_split_count=1"
if _FLAG not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") + " " + _FLAG).strip()
