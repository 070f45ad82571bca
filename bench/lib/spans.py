"""The device's idle time put down to the program's own spans.

The program's ``repro.obs`` spans (``round/...``, ``client/...``) enter
``jax.profiler.TraceAnnotation``s, so a traced run finds them among the
host spans that ``trace.load`` returns, on the device's clock.
``program`` splits the window between them: every nanosecond of the
window, and every nanosecond of it in which no operation ran on the
device, goes to the innermost program span covering it (the one that
started last), or to ``-`` where none covers it.  Intervals are
intersected exactly.  ``split`` turns that table into the numbers that
say which layer of the program holds the idle time.
"""
from __future__ import annotations

import bisect
from typing import Dict, List, Optional, Tuple

from lib.trace import merge

PROGRAM = ("round/", "client/")
STEP = "client/"
AGG = ("round/edge_agg", "round/cloud_agg")
NONE = "-"


def span_name(name: str) -> str:
    """A host event's name without TraceMe metadata (``name#k=v#``)."""
    return name.split("#", 1)[0]


def idle_holes(trace: Dict[str, list], window: Tuple[float, float]
               ) -> List[Tuple[float, float]]:
    """The intervals of ``window`` in which no device operation ran."""
    w0, w1 = window
    busy = merge([(max(o[0], w0), min(o[1], w1)) for o in trace["ops"]
                  if o[1] > w0 and o[0] < w1])
    holes, cur = [], w0
    for s, e in busy + [[w1, w1]]:
        if s > cur:
            holes.append((cur, s))
        cur = max(cur, e)
    return holes


def program(trace: Dict[str, list], window: Tuple[float, float]
            ) -> Dict[str, Dict[str, float]]:
    """Per program span name inside ``window`` (ns): ``n`` spans,
    ``host_s`` (their summed duration), ``self_s`` (that less the part
    child program spans cover) and ``idle_s`` (device-idle time whose
    innermost covering program span is one of them); under ``-`` the
    window's time and idle time that no program span covers.  Summed
    over the table, ``self_s`` is the window and ``idle_s`` its idle
    time."""
    w0, w1 = window
    spans = sorted(((max(s, w0), min(e, w1), span_name(n))
                    for s, e, n in trace["spans"]
                    if e > w0 and s < w1
                    and span_name(n).startswith(PROGRAM)),
                   key=lambda sp: (sp[0], -sp[1]))
    holes = idle_holes(trace, window)
    starts = [a for a, _ in holes]
    done = [0.0]                     # idle time before each hole
    for a, b in holes:
        done.append(done[-1] + (b - a))

    def idle_before(t):
        i = bisect.bisect_right(starts, t)
        if i == 0:
            return 0.0
        a, b = holes[i - 1]
        return done[i - 1] + (min(t, b) - a)

    table = {n: {"n": 0, "host_s": 0.0, "self_s": 0.0, "idle_s": 0.0}
             for n in [sp[2] for sp in spans] + [NONE]}
    for s, e, n in spans:
        table[n]["n"] += 1
        table[n]["host_s"] += (e - s) / 1e9
    # sweep the span boundaries; between two of them the innermost open
    # span (the latest start) owns the time and the idle time
    cuts = sorted({w0, w1} | {t for s, e, _ in spans for t in (s, e)})
    open_: List[tuple] = []
    i = 0
    for a, b in zip(cuts, cuts[1:]):
        while i < len(spans) and spans[i][0] <= a:
            open_.append(spans[i])
            i += 1
        open_ = [sp for sp in open_ if sp[1] > a]
        owner = table[open_[-1][2] if open_ else NONE]
        owner["self_s"] += (b - a) / 1e9
        owner["idle_s"] += (idle_before(b) - idle_before(a)) / 1e9
    return table


def split(trace: Dict[str, list], window: Tuple[float, float]
          ) -> Optional[Dict[str, float]]:
    """The idle time of the window in per cent of it, under the local
    step's ``client/*`` spans (``idle_in_step``), under the edge and
    cloud aggregation (``idle_in_agg``) and elsewhere
    (``idle_in_other``: no program span, or the self time of
    ``round/dispatch`` and the other round phases); the mean
    ``client/step`` in ms (``step_host_ms``); the aggregations' host
    seconds per ``bench/round`` span in the window (``agg_host_s``).
    Nothing where the window holds no program span."""
    table = program(trace, window)
    if len(table) == 1:
        return None
    w0, w1 = window
    pct = 100.0 / ((w1 - w0) / 1e9)
    step = sum(v["idle_s"] for k, v in table.items() if k.startswith(STEP))
    agg = sum(table[k]["idle_s"] for k in AGG if k in table)
    total = sum(v["idle_s"] for v in table.values())
    rounds = sum(1 for s, e, n in trace["spans"]
                 if span_name(n) == "bench/round" and e > w0 and s < w1)
    steps = table.get("client/step")
    return {
        "idle_in_step": pct * step,
        "idle_in_agg": pct * agg,
        "idle_in_other": pct * (total - step - agg),
        "step_host_ms": (1e3 * steps["host_s"] / steps["n"]
                         if steps else None),
        "agg_host_s": (sum(table[k]["host_s"] for k in AGG if k in table)
                       / rounds if rounds else None),
    }
