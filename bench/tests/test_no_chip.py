"""Without a TPU the benchmark exits non-zero and prints no result; a
device kind that the peaks table lacks is an error."""
import os
import subprocess
import sys
from pathlib import Path

import pytest

import tiny  # noqa: F401  (puts bench/ on the path)
from lib import spec

RUN = Path(__file__).resolve().parents[1] / "run.py"


def test_no_tpu_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(RUN), "--workload",
                        "chatglm3-6b.rag_single", "--seed", "3000000000",
                        "--seconds", "1", "--trace", "0"],
                       capture_output=True, text=True, env=env, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_unknown_device_kind_is_an_error():
    assert spec.peaks("TPU v5 lite")["flops_bf16"] == 197e12
    with pytest.raises(KeyError):
        spec.peaks("TPU v9 imaginary")
