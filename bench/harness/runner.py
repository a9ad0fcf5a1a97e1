"""Run one cell once: set-up, the measured window, the checks, the line.

``run_cell`` is the whole of a run.  ``bench/run.py`` calls it with
``require_tpu=True``; the CPU tests call it with ``require_tpu=False`` on
tiny configurations.
"""

from __future__ import annotations

import gc
import shutil
import time
from pathlib import Path
from types import SimpleNamespace
from typing import Dict, Optional

import numpy as np

from . import checks, device, spec, system


def _window_annotation():
    import jax
    from ..trace import WINDOW
    return jax.profiler.TraceAnnotation(WINDOW)


class Profiler:
    """Start/stop hooks around the traced part of a window."""

    def __init__(self, directory: Path):
        self.dir = directory
        self.ann = None
        self.active = False

    def __call__(self, what: str) -> None:
        import jax
        from ..trace import capture_options
        if what == "start" and not self.active:
            shutil.rmtree(self.dir, ignore_errors=True)
            self.dir.mkdir(parents=True, exist_ok=True)
            jax.profiler.start_trace(str(self.dir),
                                     profiler_options=capture_options())
            self.ann = _window_annotation()
            self.ann.__enter__()
            self.active = True
        elif what == "stop" and self.active:
            self.ann.__exit__(None, None, None)
            jax.effects_barrier()
            jax.profiler.stop_trace()
            self.active = False


def _fmt(v: float) -> str:
    return repr(float(v))


def run_cell(root: Path, workload: str, seed: int, seconds: float,
             trace: bool, require_tpu: bool = True,
             t_process: Optional[float] = None,
             config_overrides: Optional[dict] = None, log=print) -> dict:
    """One run of one cell; returns the result line as a dict (its last
    key, ``checks``, holds each compared number beside its limit)."""
    t_process = time.perf_counter() if t_process is None else t_process
    cell = spec.load_cell(root, workload)
    config = dict(cell.config, **(config_overrides or {}))
    devices = device.check_devices(cell.chips, require_tpu)
    cache_dir = device.enable_compile_cache(root)
    compiles = device.CompileCounter()
    log(f"[bench] {workload} seed {seed}: {device.describe(devices)}, "
        f"compile cache {cache_dir}")

    from ..data.generate import generate
    data = generate(config["schema"], seed)
    db = system.build_db(data)
    tracer = None
    if trace:
        from repro.obs import profile
        from repro.obs.trace import Tracer
        profile.enable()
        tracer = Tracer(capacity=1 << 20, slow_threshold_s=None)
    prof = Profiler(Path(root) / ".bench_trace" / workload) if trace \
        else None
    kind = cell.traffic["kind"]
    rng = np.random.default_rng([seed, 2])
    ctx = SimpleNamespace(kind=kind, trace=None)
    metrics: Dict[str, float] = {}

    if kind == "discover_jobs":
        from .jobs import DiscoverLoop
        loop = DiscoverLoop(config, db, tracer)
        warm = loop.warm(compiles, int(cell.traffic["warm_jobs_min"]),
                         int(cell.traffic["warm_jobs_max"]))
        c_setup = compiles.n
        setup_s = time.perf_counter() - t_process
        log(f"[bench] set-up {setup_s:.3f} s, {len(warm)} warm-up jobs")
        for j in warm:
            log(f"[bench]   warm-up job {j.summary()}")
        if tracer is not None:
            tracer.clear()
        win = loop.window(seconds, compiles, on_first_job=prof)
        done = win.completed
        peak = device.peak_bytes(devices)
        log(f"[bench] window: {len(done)} jobs completed, "
            f"{len(win.jobs) - len(done)} ran past the close, "
            f"{win.compiles} compiles in the window")
        for j in win.jobs:
            log(f"[bench]   job {j.summary()}")
        metrics["setup_s"] = setup_s
        if done:
            metrics["discovery_s"] = (done[-1].t1 - win.t_start) / len(done)
        ctx.window, ctx.jobs = win, done
        attempted, failed = len(win.jobs), 0
        job = win.jobs[int(rng.integers(0, len(win.jobs)))]
        job.calls = [(p, k, system.table_array(t)) for p, k, t in job.calls]
        del loop
        gc.collect()
        t = time.perf_counter()
        values = checks.discover_checks(job, data, float(config["ess"]),
                                        int(config["max_parents"]))
        log(f"[bench] reference check of job {win.jobs.index(job)} "
            f"({len(job.calls)} tables, {len(job.result.models)} models) "
            f"in {time.perf_counter() - t:.3f} s")
    else:
        raise spec.SpecError(f"unknown traffic kind {kind!r}")

    if trace:
        from ..trace import find_xplane, load_peaks, reduce
        peaks = load_peaks(devices[0].device_kind)
        ctx.trace = reduce(find_xplane(prof.dir), peaks)
        ctx.spans = tracer.records()
    # a configuration's limits cover its numbers in any cell; a traffic
    # mix adds the limits of the numbers only its cells compare
    limits = dict(config.get("limits", {}), **cell.traffic.get("limits", {}))
    verdict = checks.judge(values, limits)
    correct = all(v["ok"] for v in verdict) and failed == 0
    unit = {m["name"]: m["unit"] for m in cell.end_to_end + cell.per_layer}
    out_metrics = {}
    if not trace:
        for m in cell.end_to_end:
            if m["name"] in metrics:
                out_metrics[m["name"]] = {"value": metrics[m["name"]],
                                          "unit": m["unit"]}
    else:
        for m in cell.per_layer:
            v = cell.layer_reader(m["name"])(ctx)
            if v is not None:
                out_metrics[m["name"]] = {"value": float(v),
                                          "unit": unit[m["name"]]}
    dev = dict(device.describe(devices), memory_peak_bytes=peak)
    line = {"correct": correct, "attempted": attempted, "failed": failed,
            "metrics": out_metrics, "device": dev}
    if trace:
        dev["busy_s"] = ctx.trace["busy_s"]
        dev["window_s"] = ctx.trace["window_s"]
        line["breakdown"] = {"device_ops": ctx.trace["device_ops"],
                             "idle_gaps": ctx.trace["idle_gaps"]}
    line["checks"] = {v["name"]: {"value": v["value"], "limit": v["limit"]}
                      for v in verdict}
    line["_check_lines"] = [
        f"check {v['name']} = {_fmt(v['value'])} (limit {_fmt(v['limit'])})"
        f" {'ok' if v['ok'] else 'FAIL'}" for v in verdict]
    return line
