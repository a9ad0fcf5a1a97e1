"""Attribution of a JAX profiler trace to the program's own spans.

While its profiler annotations are enabled, the program opens every live
span of its tracer as a host annotation of the span's name carrying the
stat ``repro_span`` (``repro.obs.trace.PROGRAM_SPAN_STAT``, the span's
id).  That stat alone tells a program span from JAX's own host events.

* device time: each program execution inside the window (the ``XLA
  Modules`` line of a device plane) is linked to its launch through the
  host event that carries the same ``run_id`` stat, the earliest one
  (``DoEnqueueProgram`` on a TPU v5e), and charged to the program spans
  open at that launch;
* idle time: each gap between the device's program executions inside the
  window is charged to the program spans that cover the gap's midpoint.

Charges are kept per stack of span names, outermost first, so a reader can
ask for the innermost span or the innermost span of a given set.  Time
under no program span goes to ``OUTSIDE``; a program whose launch is not
in the trace, to ``UNLINKED``.  Times are averaged over the device planes,
as ``bench.trace.reduce`` does for ``busy_s``.
"""

from __future__ import annotations

import bisect
from pathlib import Path
from typing import Callable, Dict, Iterable, List, Optional, Tuple

from .trace import HOST_PLANE, WINDOW, _union

PROGRAM_SPAN_STAT = "repro_span"
OUTSIDE = "(outside program spans)"
UNLINKED = "(launch not found)"
# where bench/harness/runner.py writes the traced run's profile
TRACE_DIR = ".bench_trace"

Stack = Tuple[str, ...]


def _stats(ev) -> Dict[str, object]:
    return dict(ev.stats)


def is_program_span(name: str, stats: Dict[str, object]) -> bool:
    """A host event is a program span when it carries the marker stat."""
    return PROGRAM_SPAN_STAT in stats


def _stacks_at(spans: List[Tuple[float, float, str]],
               times: List[float]) -> List[Stack]:
    """For each time, the names of the spans with start <= t < end,
    outermost first (earlier start, then longer span)."""
    order = sorted(range(len(times)), key=times.__getitem__)
    by_start = sorted(spans, key=lambda s: (s[0], -s[1]))
    starts = [s[0] for s in by_start]
    out: List[Stack] = [()] * len(times)
    active: List[Tuple[float, float, str]] = []
    nxt = 0
    for i in order:
        t = times[i]
        hi = bisect.bisect_right(starts, t)
        if hi > nxt:
            active.extend(by_start[nxt:hi])
            nxt = hi
        active = [s for s in active if s[1] > t]
        active.sort(key=lambda s: (s[0], -s[1]))
        out[i] = tuple(s[2] for s in active)
    return out


def innermost(stack: Stack, among: Optional[Iterable[str]] = None) -> str:
    """The innermost span name of a stack (of those in ``among`` when
    given), or ``OUTSIDE``."""
    names = set(among) if among is not None else None
    for name in reversed(stack):
        if names is None or name in names:
            return name
    return OUTSIDE


def attribute(path, window: str = WINDOW,
              is_span: Callable[[str, Dict[str, object]], bool]
              = is_program_span) -> dict:
    """Device and idle time of the window that the host annotation
    ``window`` marks, by the program spans (host events for which
    ``is_span`` holds) they fall under.

    Returns ``window_s``, ``busy_s``, ``spans`` (program spans inside the
    window), ``device_by_stack`` and ``idle_by_stack`` (seconds per stack
    of span names; a program with no launch event is charged to
    ``(UNLINKED,)``, one under no span to ``()``), and ``device_by_span``
    and ``idle_by_span``: the ten largest innermost charges, as
    ``[name, seconds]`` pairs."""
    import jax
    pd = jax.profiler.ProfileData.from_file(str(path))
    win = None
    spans: List[Tuple[float, float, str]] = []
    launch: Dict[int, float] = {}
    devices = []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "CUSTOM" not in plane.name:
            devices.append(plane)
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            for ev in line.events:
                a, b = ev.start_ns, ev.start_ns + ev.duration_ns
                if ev.name == window:
                    win = (a, b) if win is None else (min(win[0], a),
                                                      max(win[1], b))
                st = _stats(ev)
                if is_span(ev.name, st):
                    spans.append((a, b, ev.name))
                run = st.get("run_id")
                if run is not None and (run not in launch
                                        or a < launch[run]):
                    launch[run] = a
    if win is None:
        raise ValueError(f"no {window!r} annotation in {path}")
    if not devices:
        raise ValueError(f"no device plane in {path}")
    lo, hi = win
    programs: List[Tuple[float, float, Optional[int]]] = []
    gaps: List[Tuple[float, float]] = []
    for plane in devices:
        mods = next((list(ln.events) for ln in plane.lines
                     if ln.name == "XLA Modules"), [])
        ivs = []
        for m in mods:
            a, b = max(m.start_ns, lo), min(m.start_ns + m.duration_ns, hi)
            if b > a:
                ivs.append((a, b, _stats(m).get("run_id")))
        ivs.sort()
        covered = lo
        for a, b, run in ivs:            # the union, charged once
            a = max(a, covered)
            if b > a:
                programs.append((a, b, run))
                covered = b
        busy = _union([(a, b) for a, b, _ in ivs])
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        gaps += [(edges[i], edges[i + 1])
                 for i in range(0, len(edges), 2) if edges[i + 1] > edges[i]]
    n_dev = len(devices)
    linked = [p for p in programs if p[2] in launch]
    stacks = _stacks_at(spans, [launch[p[2]] for p in linked]
                        + [(a + b) / 2 for a, b in gaps])
    device: Dict[Stack, float] = {}
    idle: Dict[Stack, float] = {}
    for (a, b, _), st in zip(linked, stacks):
        device[st] = device.get(st, 0.0) + (b - a) / 1e9 / n_dev
    unlinked = sum(b - a for a, b, run in programs if run not in launch)
    if unlinked:
        device[(UNLINKED,)] = unlinked / 1e9 / n_dev
    for (a, b), st in zip(gaps, stacks[len(linked):]):
        idle[st] = idle.get(st, 0.0) + (b - a) / 1e9 / n_dev
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(b - a for a, b, _ in programs) / 1e9 / n_dev,
        "spans": sum(1 for a, b, _ in spans if b > lo and a < hi),
        "device_by_stack": device,
        "idle_by_stack": idle,
        "device_by_span": _top(device),
        "idle_by_span": _top(idle),
    }


def _top(by_stack: Dict[Stack, float], n: int = 10) -> List[list]:
    out: Dict[str, float] = {}
    for st, t in by_stack.items():
        name = innermost(st)
        out[name] = out.get(name, 0.0) + t
    return sorted(([k, v] for k, v in out.items()), key=lambda x: -x[1])[:n]


def seconds_under(by_stack: Dict[Stack, float], name: str,
                  among: Iterable[str]) -> float:
    """Seconds whose innermost span of ``among`` is ``name``."""
    among = tuple(among)
    return sum(t for st, t in by_stack.items()
               if innermost(st, among) == name)


def charged_share(by_stack: Dict[Stack, float]) -> Optional[float]:
    """Share of the time charged to a named program span, in %."""
    total = sum(by_stack.values())
    if total <= 0:
        return None
    named = sum(t for st, t in by_stack.items()
                if st and st != (UNLINKED,))
    return 100.0 * named / total


def locate(root: Path) -> Optional[Path]:
    """The newest profile a traced run left under ``root``."""
    found = sorted(Path(root, TRACE_DIR).glob("**/*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    return found[-1] if found else None


_cache: Dict[Tuple[str, float], Optional[dict]] = {}


def of_run(ctx, root: Path) -> Optional[dict]:
    """The attribution of the traced run that ``ctx`` describes: None when
    the run was not traced, its profile is not the one ``ctx.trace`` was
    reduced from (or has no window or device plane), or the program
    opened no span annotations in the window."""
    if not getattr(ctx, "trace", None):
        return None
    path = locate(root)
    if path is None:
        return None
    key = (str(path), path.stat().st_mtime)
    if key not in _cache:
        _cache.clear()
        try:
            _cache[key] = attribute(path)
        except ValueError:
            _cache[key] = None
    att = _cache[key]
    if att is None or not att["spans"] \
            or abs(att["window_s"] - ctx.trace["window_s"]) > 1e-9:
        return None
    return att
