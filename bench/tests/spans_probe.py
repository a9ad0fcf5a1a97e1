"""Record the small trace ``data/spans_v5e.xplane.pb``: three device
programs under two nested program spans and one sibling span, with idle
time under each and outside them, inside a ``bench.window`` annotation.

    python3 bench/tests/spans_probe.py <output directory>

On the chip, the profile lands under the output directory; copy its
``.xplane.pb`` to ``bench/tests/data/spans_v5e.xplane.pb``.  Each program
runs once before the trace starts, so nothing compiles inside it.  The
idle stretches are tens of milliseconds, far above the skew between the
host's and the device's clocks.
"""

import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

# what the probe does, in seconds of host sleep after each program
IDLE = {"outside": 0.02, "strategy.prepare": 0.02, "count.positive": 0.04,
        "count.negative": 0.01}


def prepare_work(x):
    return (x * 2.0).sum()


def positive_work(x):
    return (x[:, None] * x[None, :1024]).sum(axis=0)


def negative_work(x):
    return x - x[::-1]


def main(out: str) -> int:
    import jax
    import jax.numpy as jnp
    from repro.obs import Tracer, profile
    from bench.trace import WINDOW, capture_options, find_xplane

    tracer = Tracer()
    profile.enable()
    fns = {f.__name__: jax.jit(f)
           for f in (prepare_work, positive_work, negative_work)}
    x = jnp.arange(1 << 16, dtype=jnp.float32) / (1 << 16)
    for f in fns.values():
        jax.block_until_ready(f(x))
    jax.profiler.start_trace(out, profiler_options=capture_options())
    with jax.profiler.TraceAnnotation(WINDOW):
        time.sleep(IDLE["outside"])
        with tracer.span("strategy.prepare"):
            jax.block_until_ready(fns["prepare_work"](x))
            time.sleep(IDLE["strategy.prepare"])
            with tracer.span("count.positive"):
                jax.block_until_ready(fns["positive_work"](x))
                jax.block_until_ready(fns["positive_work"](x))
                time.sleep(IDLE["count.positive"])
        with tracer.span("count.negative"):
            jax.block_until_ready(fns["negative_work"](x))
            time.sleep(IDLE["count.negative"])
    jax.effects_barrier()
    jax.profiler.stop_trace()
    path = find_xplane(Path(out))
    print(f"{path} {path.stat().st_size} bytes, "
          f"{len(tracer.records())} spans", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
