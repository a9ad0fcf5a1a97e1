"""Reduction of a JAX profiler trace (``.xplane.pb``) to device metrics.

* busy: the union of the device's program executions (the ``XLA Modules``
  line of each ``/device:...`` plane) inside the window, which is the
  host annotation ``bench.window``; idle share = 1 - busy / window;
* kernel time: the durations of a kernel's program executions; its work is
  counted from the shapes in the program's main operation, by the
  algorithm, so it reads the same whatever implements the kernel;
* breakdown: the programs that took most device time, and the device's
  idle time by the host activity that covers each gap.

Times in the trace are in nanoseconds from the start of the session.
"""

from __future__ import annotations

import bisect
import json
import re
from pathlib import Path
from typing import List, Optional, Tuple

WINDOW = "bench.window"
HOST_PLANE = "/host:CPU"
_TYPE = re.compile(r"\b(pred|s8|u8|s16|u16|s32|u32|s64|u64|bf16|f16|f32|f64)"
                   r"\[([0-9,]*)\]")
_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
          "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
          "f64": 8}

# device programs that are segment-sums: XLA's scatter-add and the Pallas
# segment-sum kernels of the sparse executor
SEGSUM_PROGRAMS = ("jit_scatter-add", "jit__ones_segment_sum",
                   "jit__edge_segment_sum")


def load_peaks(kind: str, path: Optional[Path] = None) -> dict:
    path = path or Path(__file__).with_name("peaks.json")
    table = json.loads(Path(path).read_text())["devices"]
    if kind not in table:
        raise KeyError(f"device kind {kind!r} has no entry in {path}")
    return table[kind]


def base_name(name: str) -> str:
    """A program's name without JAX's hash suffix."""
    return name.split("(", 1)[0]


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def _clip(iv, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in iv if b > lo and a < hi]


def shapes(hlo: str) -> List[Tuple[str, int]]:
    """(dtype, element count) of every array type in an HLO instruction,
    the result first."""
    out = []
    for dt, dims in _TYPE.findall(hlo):
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        out.append((dt, n))
    return out


def segsum_work(hlo: str) -> Optional[Tuple[float, float]]:
    """(operations, bytes) of one segment-sum from its main instruction:
    ``out[seg[e]] += values[e]`` reads E ids and E*W values and writes S*W
    sums, E*W additions.  None when the instruction is not one."""
    arrs = shapes(hlo)
    if len(arrs) < 3:
        return None
    (odt, out_n), operands = arrs[0], arrs[1:]
    ids = [n for dt, n in operands if dt in ("s32", "s64", "u32")]
    vals = [(dt, n) for dt, n in operands if dt.startswith(("f", "bf"))
            and n != out_n]
    if not ids or not vals:
        return None
    e = max(ids)
    vdt, vn = max(vals, key=lambda x: x[1])
    if e == 0 or vn % e:
        return None
    w = vn // e
    ops = float(e * w)
    nbytes = float(e * _BYTES.get("s32") + vn * _BYTES[vdt]
                   + out_n * _BYTES[odt])
    return ops, nbytes


def reduce(path: str, peaks: dict, window: str = WINDOW) -> dict:
    """Device busy time, window, per-program time, segment-sum roofline
    share and idle-gap attribution of the window that the host annotation
    ``window`` marks."""
    import jax
    pd = jax.profiler.ProfileData.from_file(str(path))
    host, devices = None, []
    for plane in pd.planes:
        if plane.name == HOST_PLANE:
            host = plane
        elif plane.name.startswith("/device:") and "CUSTOM" not in plane.name:
            devices.append(plane)
    win = None
    host_events = []
    if host is not None:
        for line in host.lines:
            for ev in line.events:
                a, b = ev.start_ns, ev.start_ns + ev.duration_ns
                if ev.name == window:
                    win = (a, b) if win is None else (min(win[0], a),
                                                      max(win[1], b))
                elif b > a:
                    host_events.append((a, b, ev.name))
    if win is None:
        raise ValueError(f"no {window!r} annotation in {path}")
    lo, hi = win
    busy_total, progs, seg_ops, seg_bytes, seg_time = 0.0, {}, 0.0, 0.0, 0.0
    per_device_busy = []
    gaps = []
    for plane in devices:
        lines = {ln.name: list(ln.events) for ln in plane.lines}
        mods = lines.get("XLA Modules", [])
        ops = sorted(lines.get("XLA Ops", []), key=lambda e: e.start_ns)
        starts = [e.start_ns for e in ops]
        ivs = []
        for m in mods:
            a, b = m.start_ns, m.start_ns + m.duration_ns
            if b <= lo or a >= hi:
                continue
            ivs.append((a, b))
            name = base_name(m.name)
            progs[name] = progs.get(name, 0.0) + min(b, hi) - max(a, lo)
            if name not in SEGSUM_PROGRAMS or a < lo or b > hi:
                continue              # a kernel counts when wholly inside
            i = bisect.bisect_left(starts, a)
            inner = []
            while i < len(ops) and ops[i].start_ns < b:
                inner.append(ops[i])
                i += 1
            if not inner:
                continue
            work = segsum_work(max(inner, key=lambda e: e.duration_ns).name)
            if work is None:
                continue
            seg_ops += work[0]
            seg_bytes += work[1]
            seg_time += m.duration_ns
        busy = _union(_clip(ivs, lo, hi))
        per_device_busy.append(sum(b - a for a, b in busy))
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        gaps += [(edges[i], edges[i + 1])
                 for i in range(0, len(edges), 2) if edges[i + 1] > edges[i]]
    if not per_device_busy:
        raise ValueError(f"no device plane in {path}")
    busy_s = sum(per_device_busy) / len(per_device_busy) / 1e9
    window_s = (hi - lo) / 1e9
    idle_by = {}
    for a, b in gaps:
        cover = [(min(b, e1) - max(a, e0), e1 - e0, n)
                 for e0, e1, n in host_events if e1 > a and e0 < b]
        inner = [c for c in cover if c[0] >= 0.5 * (b - a)]
        if inner:
            name = min(inner, key=lambda c: c[1])[2]
        elif cover:
            name = max(cover, key=lambda c: c[0])[2]
        else:
            name = "(no host activity)"
        idle_by[name] = idle_by.get(name, 0.0) + (b - a) / 1e9
    out = {
        "busy_s": busy_s,
        "window_s": window_s,
        "device_ops": sorted(([n, t / 1e9] for n, t in progs.items()),
                             key=lambda x: -x[1])[:10],
        "idle_gaps": sorted(([n, t] for n, t in idle_by.items()),
                            key=lambda x: -x[1])[:10],
    }
    if seg_time > 0:
        floor_s = max(seg_bytes / peaks["hbm_bytes_per_s"],
                      seg_ops / peaks["bf16_flops_per_s"])
        out["segsum"] = {"seconds": seg_time / 1e9, "ops": seg_ops,
                         "bytes": seg_bytes, "floor_s": floor_s,
                         "roofline_pct": 100.0 * floor_s / (seg_time / 1e9)}
    return out


def capture_options():
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0      # host annotations, not every call
    return opts


def find_xplane(directory: Path) -> Path:
    found = sorted(Path(directory).glob("**/*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return found[-1]
