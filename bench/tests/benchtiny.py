"""Tiny cells for rehearsing the harness on the CPU, defined only by files
in a directory of their own, as a later cell would be."""

import json
import shutil
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]

TINY_CHAIN2 = {"prefix": "imdb", "entity_types": 3, "entities_per_type": 30,
               "attrs_per_entity": 2, "attr_card": 3, "relationships": 3,
               "edges": [60, 50, 40], "correlation": 0.7}
TINY_RING = {"prefix": "vg", "entity_types": 4, "entities_per_type": 40,
             "attrs_per_entity": 1, "attr_card": 3, "relationships": 8,
             "edges": [80] * 8, "correlation": 0.7, "base_seed": 12}

LAYER = '''"""Jobs completed in the window (a reader that only this cell has)."""


def read(ctx):
    return float(len(ctx.jobs)) if ctx.kind == "discover_jobs" else None
'''


def make_root(tmp: Path) -> Path:
    """A benchmark root with two tiny configurations, a traffic mix and one
    layer reader of its own, plus the repository's readers."""
    b = tmp / "bench"
    (b / "configs").mkdir(parents=True)
    (b / "traffic").mkdir()
    shutil.copytree(BENCH / "layers", b / "layers")
    (b / "layers" / "jobs_seen.py").write_text(LAYER)
    base = json.loads((BENCH / "configs" / "vg-full.json").read_text())
    for name, schema, chain in (("tiny-c2", TINY_CHAIN2, 2),
                                ("tiny-ring", TINY_RING, 1)):
        cfg = dict(base, name=name, schema=schema, max_chain_length=chain)
        (b / "configs" / f"{name}.json").write_text(json.dumps(cfg))
    (b / "traffic" / "jobs_tiny.json").write_text(json.dumps(
        {"kind": "discover_jobs", "warm_jobs_min": 1, "warm_jobs_max": 3}))
    cells = (("t-discover", "tiny-c2"), ("t-ring", "tiny-ring"))
    bench = {
        "command": ["python3", "bench/run.py"], "paths": ["bench"],
        "run_seconds": 2,
        "configs": [{"name": n, "source": "test", "reduced": [],
                     "file": f"bench/configs/{n}.json", "why": "test"}
                    for n in ("tiny-c2", "tiny-ring")],
        "workloads": [{"name": w, "config": c, "traffic": "jobs_tiny",
                       "chips": 1, "why": "test"} for w, c in cells],
        "end_to_end": [
            {"name": "setup_s", "unit": "s", "better": "lower",
             "bound": 0.25, "source": "host_clock"},
            {"name": "discovery_s", "unit": "s", "better": "lower",
             "bound": 0.25, "source": "host_clock",
             "workloads": [w for w, _ in cells]}],
        "per_layer": [
            {"name": "jobs_seen", "unit": "jobs", "better": "higher",
             "source": "host_clock", "layer": "test", "moves": "discovery_s",
             "workloads": ["t-discover"]},
            {"name": "families_per_job.discovery", "unit": "families",
             "better": "lower", "source": "program_counter",
             "layer": "discovery and search", "moves": "discovery_s",
             "workloads": [w for w, _ in cells]}]}
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp


def restore_jax_config():
    """Undo what a run set process-wide: the persistent compile cache and
    the profiler annotations."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    from repro.obs import profile
    jax.config.update("jax_compilation_cache_dir", None)
    compilation_cache.reset_cache()
    profile.disable()
