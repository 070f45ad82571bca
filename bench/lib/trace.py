"""From a profiler trace to device busy time, idle gaps and the time of
the operations that implement the U-Net's GEMMs.

``load`` turns the ``.xplane.pb`` that ``jax.profiler`` writes into two
plain lists, so that ``reduce`` can be checked on a small recorded
trace (``bench/tests/data``):

- ``ops``: ``[start_ns, end_ns, name, kind]`` of every operation on the
  first accelerator plane's op line, ``kind`` being ``gemm`` or
  ``other`` (``classify``);
- ``spans``: ``[start_ns, end_ns, name]`` of host events, the
  benchmark's own ``bench/...`` annotations among them.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

# An operation implements a GEMM when XLA emitted it as a convolution
# or dot, alone or as the root of an output fusion (the TPU compiler's
# ``kind=kOutput``: a convolution or dot with its epilogue fused), or
# when it is one of the program's Pallas matmul / attention kernels.
# On a TPU the op's name is its HLO text.
_GEMM = re.compile(r"kind=kOutput|^%?(convolution|dot)[.\s]|"
                   r"block_masked_matmul|masked_matmul|flash_attention")
_LAYOUT = re.compile(r"\{[^{}]*\}")


def classify(name: str, stats: Dict[str, object]) -> str:
    text = " ".join([name] + [str(stats.get(k, "")) for k in
                              ("hlo_category", "long_name")])
    return "gemm" if _GEMM.search(text) else "other"


def short_name(text: str) -> str:
    """``%fusion.5 = f32[32,16,16,256] fusion kOutput`` from an op's HLO
    text: the name, the result's shape without layouts, the opcode and
    the fusion kind."""
    head, _, rest = text.partition(" = ")
    if not rest:
        return text[:160]
    rest = _LAYOUT.sub("", rest)
    shape = rest
    if rest.startswith("("):            # a tuple result
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                shape, rest = rest[:i + 1], rest[i + 1:]
                break
        shape = "(...)"
    else:
        shape, _, rest = rest.partition(" ")
    op = rest.strip().split("(", 1)[0]
    kind = re.search(r"kind=(k\w+)", rest)
    return (f"{head} = {shape} {op}"
            + (f" {kind.group(1)}" if kind else ""))[:200]


def _stats(ev) -> Dict[str, object]:
    try:
        return {k: v for k, v in ev.stats}
    except (TypeError, ValueError):
        return {}


def load(logdir: str) -> Dict[str, list]:
    """Read the newest trace under ``logdir``."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(
        logdir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    pd = ProfileData.from_file(files[-1])
    ops, spans = [], []
    device_done = False
    for plane in pd.planes:
        if plane.name.startswith("/device:") and not device_done:
            lines = list(plane.lines)
            pick = [ln for ln in lines if ln.name == "XLA Ops"] or lines
            named: Dict[str, Tuple[str, str]] = {}
            for ln in pick:
                for ev in ln.events:
                    if ev.name not in named:
                        named[ev.name] = (short_name(ev.name),
                                          classify(ev.name, _stats(ev)))
                    s = int(ev.start_ns)
                    ops.append([s, s + int(ev.duration_ns),
                                *named[ev.name]])
            device_done = bool(ops)
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for ev in ln.events:
                    if ev.duration_ns > 0:
                        s = int(ev.start_ns)
                        spans.append([s, s + int(ev.duration_ns), ev.name])
    return {"ops": ops, "spans": spans}


def merge(intervals: Sequence[Tuple[float, float]]) -> List[List[float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(iv, w0, w1):
    return [(max(s, w0), min(e, w1)) for s, e in iv if e > w0 and s < w1]


def _labels(mids: Sequence[float], spans) -> List[str]:
    """For each time in ``mids`` (ascending): the innermost ``bench/``
    host span covering it, joined with the innermost other host event
    covering it.  One sweep over the spans sorted by start."""
    order = sorted(spans, key=lambda s: s[0])
    active: List[list] = []
    out, i = [], 0
    for m in mids:
        while i < len(order) and order[i][0] <= m:
            active.append(order[i])
            i += 1
        active = [s for s in active if s[1] >= m]
        bench = [s for s in active if s[2].startswith("bench/")]
        other = [s for s in active if not s[2].startswith("bench/")]
        name = min(bench, key=lambda s: s[1] - s[0])[2] if bench \
            else "bench/-"
        if other:
            name += " > " + min(other, key=lambda s: s[1] - s[0])[2]
        out.append(name)
    return out


def reduce(trace: Dict[str, list], window: Tuple[float, float],
           top: int = 10) -> Dict[str, object]:
    """Busy and idle time of the device inside ``window`` (ns), the GEMM
    operations' summed device time, the ``top`` operations by time and
    the idle time by what the host was doing."""
    w0, w1 = window
    ops = [o for o in trace["ops"] if o[1] > w0 and o[0] < w1]
    busy_iv = merge(_clip([(o[0], o[1]) for o in ops], w0, w1))
    busy = sum(e - s for s, e in busy_iv)
    gemm = sum(e - s for s, e in merge(_clip(
        [(o[0], o[1]) for o in ops if o[3] == "gemm"], w0, w1)))
    by_op: Dict[str, float] = {}
    for o in ops:
        s, e = max(o[0], w0), min(o[1], w1)
        by_op[o[2]] = by_op.get(o[2], 0.0) + (e - s)
    holes, cur = [], w0
    for s, e in busy_iv + [[w1, w1]]:
        if s > cur:
            holes.append((cur, s))
        cur = max(cur, e)
    gaps: Dict[str, float] = {}
    labels = _labels([0.5 * (a + b) for a, b in holes], trace["spans"])
    for (a, b), lab in zip(holes, labels):
        gaps[lab] = gaps.get(lab, 0.0) + (b - a)
    rank = lambda d: [[k, v / 1e9] for k, v in
                      sorted(d.items(), key=lambda kv: -kv[1])[:top]]
    return {"window_s": (w1 - w0) / 1e9, "busy_s": busy / 1e9,
            "gemm_s": gemm / 1e9, "ops": len(ops),
            "device_ops": rank(by_op), "idle_gaps": rank(gaps)}


def window_of(trace: Dict[str, list], name: str = "bench/window"
              ) -> Optional[Tuple[float, float]]:
    hits = [s for s in trace["spans"] if s[2] == name]
    return (hits[0][0], hits[0][1]) if hits else None
