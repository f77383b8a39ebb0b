"""A tiny stand-in of each cell for the CPU: the same data layout as
``bench/configs`` and ``bench/traffic``, at sizes a test can hold.  Used
only by the tests; never a cell.

A configuration file may carry a ``"small"`` section: its stand-in's
overrides (nested sections too, such as ``{"model": {"mla": {...}}}``),
deep-merged over the file and over ``SMALL``, and ``"streamed"``, whether
the stand-in streams layers from host memory."""
from __future__ import annotations

import copy
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

from lib import spec  # noqa: E402

# the stand-in of a configuration whose file has no ``"small"`` section,
# or whose section leaves these out: a small dense model, resident
SMALL = {"streamed": False,
         "model": dict(d_model=64, num_heads=4, num_kv_heads=2, head_dim=16,
                       d_ff=128, vocab_size=503, num_layers=3)}


def merged(base: dict, over: dict) -> dict:
    """``base`` with ``over`` deep-merged over it: nested sections merge
    key by key, any other value replaces."""
    out = copy.deepcopy(base)
    for k, v in over.items():
        if isinstance(v, dict) and isinstance(out.get(k), dict):
            out[k] = merged(out[k], v)
        else:
            out[k] = copy.deepcopy(v)
    return out


def small(name: str) -> dict:
    """Configuration ``name``'s ``"small"`` section over ``SMALL``."""
    return merged(SMALL, spec.config(name).get("small", {}))


def conf(name: str) -> dict:
    """Configuration ``name`` with its stand-in's overrides merged in."""
    c = spec.config(name)
    c.pop("small", None)
    over = small(name)
    del over["streamed"]
    return merged(c, over)


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


def build_kw(name: str) -> dict:
    """``build_served``'s arguments for configuration ``name``'s
    stand-in: a memory limit that holds the tiny model whole, or, where
    its ``"small"`` section says ``"streamed": true``, only part of it."""
    from repro.core.costmodel import TPU_V5E_HOST
    streamed = small(name)["streamed"]
    return dict(hw=TPU_V5E_HOST,
                limit_bytes=1_560_000 if streamed else 10 ** 8)


def cell(name: str) -> dict:
    return copy.deepcopy(spec.cell(name))
