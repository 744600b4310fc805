"""The benchmark's tests run on the CPU: ``python -m pytest bench/tests``.

They import the harness as ``bench/run.py`` does: ``bench/`` and the
program's ``src/`` on the path.  The CPU shows four devices, so the
four-chip cell's rehearsal can place one replica on each."""
import os
import pathlib
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")

BENCH = pathlib.Path(__file__).resolve().parents[1]
for p in (BENCH, BENCH.parent / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))
