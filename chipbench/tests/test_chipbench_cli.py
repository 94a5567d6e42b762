"""The command refuses to measure without a TPU: non-zero exit, and no
result line on stdout."""
import json
import os
import subprocess
import sys

from chipbench import manifest


def test_no_tpu_exits_nonzero_without_a_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload",
         "g500s12-a2-revalue", "--seed", "3000000000", "--seconds", "1",
         "--trace", "0"],
        cwd=manifest.ROOT, env=env, capture_output=True, text=True,
        timeout=120)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    for line in proc.stdout.splitlines():
        try:
            json.loads(line)
        except ValueError:
            continue
        raise AssertionError(f"printed a result: {line}")
