"""Per-layer metric readers, found by name.

A per-layer metric ``<name>`` of ``BENCHMARK.json`` is read by
``bench/metrics/<name>.py`` or, where there is none, by the reader of
its family, ``bench/metrics/<base>.py`` for a name ``<base>.<part>``
(``mfu.py`` reads ``mfu.session`` and any later ``mfu.<part>``: one
quantity split by the end-to-end metric it moves). A reader declares
``UNIT``, ``LAYER`` and ``SOURCE``, and a reader of one metric also
``MOVES``; what it declares must agree with ``BENCHMARK.json``. It
defines ``read(ctx)``. ``ctx`` is the reduced trace (trace.py) with the
gateway's counters over the traced slice and the whole window, the
configuration (``cfg``, ``conf``) and its work calls (``work``:
``prefill``, ``inject``, ``slate``, ``scatter``, ``row_flops``, as
cell.config_code gives them), the peaks of the device and the rows
served in the slice.
A reader that finds nothing to read returns None, and the metric is left
out of the result line.
"""
from __future__ import annotations

import os
from typing import Any, Dict, List

BENCH = os.path.dirname(os.path.abspath(__file__))


def _applies(entry: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in entry or cell in entry["workloads"]


def end_to_end_names(cell: str, bm: Dict[str, Any]) -> List[str]:
    return [m["name"] for m in bm["end_to_end"] if _applies(m, cell)]


def reader_path(name: str, bench_dir: str = BENCH) -> str:
    own = os.path.join(bench_dir, "metrics", name + ".py")
    if os.path.exists(own) or "." not in name:
        return own
    return os.path.join(bench_dir, "metrics", name.split(".")[0] + ".py")


def load_reader(name: str, bench_dir: str = BENCH):
    from bench.cell import load_module
    return load_module(reader_path(name, bench_dir), "bench_metric_" + name)


def read_all(cell: str, ctx: Dict[str, Any], bm: Dict[str, Any],
             bench_dir: str = BENCH) -> Dict[str, Dict[str, Any]]:
    out = {}
    for m in bm["per_layer"]:
        if not _applies(m, cell):
            continue
        r = load_reader(m["name"], bench_dir)
        for key in ("unit", "layer", "moves", "source"):
            if getattr(r, key.upper(), m[key]) != m[key]:
                raise ValueError(f"metric {m['name']}: {key} "
                                 f"{getattr(r, key.upper())!r} in its reader, "
                                 f"{m[key]!r} in BENCHMARK.json")
        v = r.read(ctx)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def module_time(ctx: Dict[str, Any], pattern: str):
    r"""(device seconds, runs) of the programs whose label matches
    ``pattern`` (a regular expression) in the traced slice. Labels are
    ``<module>@<layer span>`` (trace.py); the program's own jits are
    modules ``jit__<function>``, while eager operations a call launches
    beside them are ``jit_<operation>``, which the readers' patterns
    (``^jit__\w*@...``) leave out."""
    import re
    rx = re.compile(pattern)
    t = sum(v for k, v in ctx["module_s"].items() if rx.search(k))
    n = sum(v for k, v in ctx["module_n"].items() if rx.search(k))
    return t, n
