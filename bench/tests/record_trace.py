"""Record the small profiler trace that ``test_trace.py`` reduces.

    python bench/tests/record_trace.py <out.xplane.pb>

On one TPU: the clock marker, then 3 paged decode calls and 2 flash
prefill calls at chatglm3-6b's attention widths (32 query heads, 2 KV
heads, head_dim 128), with a host sleep of 50 ms between the two groups
so the trace holds one long idle gap.
"""
import glob
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT / "bench"))

from lib import trace as T              # noqa: E402
from repro.kernels import ops           # noqa: E402

H, KV, D, PAGE = 32, 2, 128, 16


def main(out: str) -> None:
    rng = np.random.default_rng(0)

    def normal(*shape):
        return jnp.asarray(rng.standard_normal(shape, np.float32),
                           jnp.bfloat16)

    b, pages, nmax = 16, 1281, 80
    tab = jnp.asarray(rng.permutation(pages - 1)[:b * nmax]
                      .reshape(b, nmax) + 1, jnp.int32)
    kv_len = jnp.full((b,), 1100, jnp.int32)
    q, kp, vp = normal(b, H, D), normal(pages, PAGE, KV, D), \
        normal(pages, PAGE, KV, D)
    paged = jax.jit(ops.paged_decode_attention)
    fq, fk, fv = normal(1, 1024, H, D), normal(1, 1024, KV, D), \
        normal(1, 1024, KV, D)
    flash = jax.jit(lambda a, b_, c: ops.flash_attention(a, b_, c,
                                                         causal=True))
    jax.block_until_ready((paged(q, kp, vp, tab, kv_len),
                           flash(fq, fk, fv)))
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        with jax.profiler.TraceAnnotation(T.MARKER):
            pass
        for _ in range(3):
            jax.block_until_ready(paged(q, kp, vp, tab, kv_len))
        time.sleep(0.05)
        for _ in range(2):
            jax.block_until_ready(flash(fq, fk, fv))
        jax.profiler.stop_trace()
        shutil.copy(T.find(d), out)
    print(out, os.path.getsize(out))


if __name__ == "__main__":
    main(sys.argv[1])
