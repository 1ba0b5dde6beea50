"""Durations of the program's own spans and its counters, which the program
records while the profiler runs (``repro.spans``): what the readers of its
phases share.

A span's self time is its duration less the part of it that its child
spans cover.  A program without ``repro.spans`` records nothing, and then
every reader here returns None.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple


def snapshot() -> Optional[Tuple[list, Dict[str, float]]]:
    """``(spans, counters)`` of the latest traced window, or None."""
    try:
        from repro import spans
    except ImportError:
        return None
    return spans.snapshot()


def self_ns(spans: Sequence) -> List[float]:
    """Each span's duration less the union of its children's intervals
    (clipped to it), in nanoseconds."""
    children: Dict[int, List[Tuple[int, int]]] = {}
    for s in spans:
        if s.parent is not None:
            children.setdefault(s.parent, []).append((s.start_ns, s.end_ns))
    out = []
    for i, s in enumerate(spans):
        covered, reach = 0, s.start_ns
        for c0, c1 in sorted(children.get(i, ())):
            c0, c1 = max(c0, reach), min(c1, s.end_ns)
            if c1 > c0:
                covered += c1 - c0
                reach = c1
        out.append(float(s.end_ns - s.start_ns - covered))
    return out


def by_name(spans: Sequence) -> Dict[str, Dict[str, float]]:
    """Per span name: how many (``n``), the mean duration (``mean_ms``) and
    the mean self time (``self_ms``)."""
    own = self_ns(spans)
    acc: Dict[str, List[float]] = {}
    for s, t in zip(spans, own):
        a = acc.setdefault(s.name, [0, 0.0, 0.0])
        a[0] += 1
        a[1] += s.end_ns - s.start_ns
        a[2] += t
    return {name: {"n": n, "mean_ms": total / n * 1e-6,
                   "self_ms": self_total / n * 1e-6}
            for name, (n, total, self_total) in acc.items()}


def less_descendants_ns(spans: Sequence, name: str, less: str
                        ) -> List[float]:
    """The duration of each span named ``name`` less its descendants named
    ``less`` (the outermost of them), in nanoseconds."""
    taken: Dict[int, float] = {}
    for s in spans:
        if s.name != less:
            continue
        p = s.parent
        while p is not None and spans[p].name not in (name, less):
            p = spans[p].parent
        if p is not None and spans[p].name == name:
            taken[p] = taken.get(p, 0.0) + (s.end_ns - s.start_ns)
    return [float(s.end_ns - s.start_ns) - taken.get(i, 0.0)
            for i, s in enumerate(spans) if s.name == name]


def mean_ms(name: str, less: Optional[str] = None) -> Optional[float]:
    """Mean milliseconds of the latest window's spans named ``name``, each
    less its ``less`` descendants when given; None if there are none."""
    snap = snapshot()
    if snap is None:
        return None
    spans = snap[0]
    if less is None:
        found = by_name(spans).get(name)
        return None if found is None else found["mean_ms"]
    times = less_descendants_ns(spans, name, less)
    return sum(times) / len(times) * 1e-6 if times else None


def ratio(numerator: str, denominator: str) -> Optional[float]:
    """One counter of the latest window over another; None if either is
    missing or the denominator is 0."""
    snap = snapshot()
    if snap is None:
        return None
    counters = snap[1]
    den = counters.get(denominator)
    if not den or numerator not in counters:
        return None
    return counters[numerator] / den
