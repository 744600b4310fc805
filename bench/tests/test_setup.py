"""A second run finds every program of its set-up in the compile cache."""
import os
import subprocess
import sys

import harness


def test_second_run_compiles_nothing_new(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    env.pop("PYTHONHASHSEED", None)
    counts = []
    for seed in (5, 6):
        subprocess.run(
            [sys.executable, "bench/run.py", "--workload", "shipdet-scene",
             "--seed", str(seed), "--seconds", "1", "--trace", "0",
             "--rehearse-cpu"], cwd=harness.ROOT, env=env, check=True,
            capture_output=True, timeout=600)
        counts.append(len(os.listdir(tmp_path)))
    assert counts[0] > 0 and counts[1] == counts[0]
