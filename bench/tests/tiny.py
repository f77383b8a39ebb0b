"""A tiny stand-in of each cell for the CPU: the same data layout as
``bench/configs`` and ``bench/traffic``, at sizes a test can hold.  Used
only by the tests; never a cell."""
from __future__ import annotations

import copy
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

from lib import spec  # noqa: E402

SMALL_MODEL = dict(d_model=64, num_heads=4, head_dim=16, d_ff=128,
                   vocab_size=503, num_layers=3)


def conf(name: str) -> dict:
    c = copy.deepcopy(spec.config(name))
    c["model"].update(SMALL_MODEL)
    c["model"]["num_kv_heads"] = min(c["model"]["num_kv_heads"], 2)
    return c


def mix(name: str, **over) -> dict:
    m = copy.deepcopy(spec.traffic(name))
    m.update(slots=4, ctx_len=32, page_size=8, query_pool=64,
             preroll_s=1.0, trace_s=1.0)
    m["corpus"] = dict(m["corpus"], rows=2048, dim=64, partitions=8,
                       spilled=4)
    m["answer"] = dict(m["answer"], max=min(m["answer"]["max"], 6),
                       min=min(m["answer"]["min"], 2))
    m["clients"] = 6
    m["check"] = {"requests": 8, "tokens": 48}
    m.update(over)
    return m


def build_kw(streamed: bool) -> dict:
    from repro.core.costmodel import TPU_V5E_HOST
    # a limit that holds the tiny model whole, or only part of it
    return dict(hw=TPU_V5E_HOST,
                limit_bytes=1_560_000 if streamed else 10 ** 8)


def cell(name: str) -> dict:
    return copy.deepcopy(spec.cell(name))
