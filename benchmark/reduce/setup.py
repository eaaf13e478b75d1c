"""Where set-up's seconds went (PR 54).

The program keeps one small host record of every program JAX compiled
or loaded (``kind: "compile"``: ``fun_name``, ``trace_s``, ``lower_s``,
``backend_s``, ``cache`` = hit / miss / none), of every side compile of
``device_stats.instrument`` (``"harvest"``) and of every phase of an
engine's build (``"phase"``), each with its ``perf_counter`` stamps and
the span that caused it (``ray_tpu/_private/telemetry.py
setup_records``).  A driver keeps none of that, so the records are asked
of the process, as the launch records are (``launches.py``), and cut at
the window's opening: ``run.ctx.t_start + run.setup_s`` is the same
``perf_counter`` the records are stamped on.

Three of the five sums are disjoint by construction -- the compiles no
harvest caused, the harvests whole (their compiles inside), the
``params`` phase less the compiles it caused -- so together they are at
most ``setup_s``.

Against a program without the records (the parent of PR 54), or a run
that kept no context, every reader returns None.  `reduce` works on
plain dicts.
"""

from __future__ import annotations

import collections
from typing import Dict, List, Optional

from benchmark.harness import say
from benchmark.reduce import program

#: a compile the cache should have served: from a warm cache none of
#: these is left (JAX writes what took a second or more to compile)
SLOW_S = 1.0


def setup_records() -> Optional[List[dict]]:
    """This process's set-up records, oldest first; None against a
    program that keeps none."""
    try:
        from ray_tpu._private.telemetry import setup_records as read
    except ImportError:
        return None
    return read() or None


def compile_seconds(record: dict) -> float:
    return record["trace_s"] + record["lower_s"] + record["backend_s"]


def _cause(record: dict, key: str):
    return (record.get("cause") or {}).get(key)


def cause_text(record: dict) -> str:
    """``params`` / ``serve.decode#0:harvest`` / ``-``: a cause in a
    few characters, for the printed line."""
    c = record.get("cause") or {}
    text = "/".join(str(c[k]) for k in ("phase", "program") if c.get(k))
    if c.get("signature") is not None:
        text += f"#{c['signature']}"
    if c.get("part"):
        text += f":{c['part']}"
    return text or "-"


def reduce(records: List[dict], t_open: float) -> Dict:
    """The five sums over the records closed by `t_open` and what the
    printed line says of them.  ``lost`` is how many records the ring
    had dropped (its oldest ``seq``): the readers then give nothing, as
    a count that misses some is no count."""
    lost = min(r["seq"] for r in records)
    before = [r for r in records if r["t1"] <= t_open]
    by_kind = collections.defaultdict(list)
    for r in before:
        by_kind[r["kind"]].append(r)
    compiles = by_kind["compile"]
    own = [r for r in compiles if _cause(r, "part") != "harvest"]
    params = [r for r in by_kind["phase"] if r["phase"] == "params"]
    by_phase: Dict[str, List[float]] = {}
    for r in own:
        cell = by_phase.setdefault(str(_cause(r, "phase")), [0, 0.0, 0.0])
        cell[0] += 1
        cell[1] += compile_seconds(r)
        cell[2] += r["backend_s"] if r["cache"] != "hit" else 0.0
    # the compiles of instrumented programs by which part asked: the
    # side compile or the executing call
    by_part: Dict[str, List[float]] = {}
    for r in compiles:
        if _cause(r, "part"):
            cell = by_part.setdefault(_cause(r, "part"), [0, 0.0])
            cell[0] += 1
            cell[1] += compile_seconds(r)
    # the programs a warm cache should have served and did not, by
    # name and cause: how many and their backend seconds
    slow: Dict[tuple, List[float]] = {}
    for r in compiles:
        if r["cache"] != "hit" and r["backend_s"] >= SLOW_S:
            cell = slow.setdefault((r["fun_name"], cause_text(r)), [0, 0.0])
            cell[0] += 1
            cell[1] += r["backend_s"]
    phases: Dict[str, float] = collections.defaultdict(float)
    for r in by_kind["phase"]:
        phases[r["phase"]] += r["t1"] - r["t0"]
    harvests: Dict[str, float] = collections.defaultdict(float)
    for r in by_kind["harvest"]:
        harvests[r["program"]] += r["t1"] - r["t0"]
    return {
        "lost": lost,
        "setup_compiles": len(compiles),
        "setup_compile_s": sum(compile_seconds(r) for r in own),
        "setup_compile_uncached_s": sum(
            r["backend_s"] for r in own if r["cache"] != "hit"),
        "setup_harvest_s": sum(harvests.values()),
        "setup_params_s": None if not params else (
            phases["params"] - sum(
                compile_seconds(r) for r in compiles
                if _cause(r, "phase") == "params")),
        "by_cache": dict(collections.Counter(
            r["cache"] for r in compiles)),
        "by_phase": {k: [n, round(s, 3), round(u, 3)]
                     for k, (n, s, u) in by_phase.items()},
        "by_part": {k: [n, round(s, 3)] for k, (n, s) in by_part.items()},
        # what a harvest takes beside the compiles inside it: the
        # compiled text's print, the scope map's parse, the cost summary
        "harvest_beside_compiles_s": sum(harvests.values())
        - by_part.get("harvest", [0, 0.0])[1],
        "largest": [[r["fun_name"], cause_text(r),
                     round(compile_seconds(r), 3), r["cache"]]
                    for r in sorted(compiles, key=compile_seconds,
                                    reverse=True)[:5]],
        "slow_uncached": [[name, why, n, round(seconds, 3)]
                          for (name, why), (n, seconds) in slow.items()],
        "phases": {k: round(v, 3) for k, v in phases.items()},
        "harvests": {k: round(v, 3) for k, v in harvests.items()},
        "after_open": [[r["fun_name"], cause_text(r)]
                       for r in records if r["kind"] == "compile"
                       and r["t1"] > t_open][:5],
    }


def setup_table(run) -> Optional[Dict]:
    """`reduce` of one run's records, once; printed as the line
    ``[setup_records]``."""
    t_start = getattr(getattr(run, "ctx", None), "t_start", None)
    if t_start is None or getattr(run, "setup_s", None) is None:
        return None

    def make():
        records = setup_records()
        if records is None:
            return None
        table = reduce(records, t_start + run.setup_s)
        say("setup_records", **{
            k: (round(v, 3) if isinstance(v, float) else v)
            for k, v in table.items()})
        return table

    return program._cached(run, "setup", make)


def _reader(name: str):
    def read(run) -> Optional[float]:
        table = setup_table(run)
        if table is None or table["lost"] or table[name] is None:
            return None
        return float(table[name])
    return read


read_setup_compiles = _reader("setup_compiles")
read_setup_compile_s = _reader("setup_compile_s")
read_setup_compile_uncached_s = _reader("setup_compile_uncached_s")
read_setup_harvest_s = _reader("setup_harvest_s")
read_setup_params_s = _reader("setup_params_s")
