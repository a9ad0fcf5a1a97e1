"""Device time of the traced discovery job under the negative phase, s:
programs whose innermost phase span at launch is ``count.negative`` (the
Möbius join)."""

from pathlib import Path

from bench import spans

ROOT = Path(__file__).resolve().parents[2]
PHASES = ("count.positive", "count.negative")


def read(ctx):
    att = spans.of_run(ctx, ROOT)
    if att is None:
        return None
    return spans.seconds_under(att["device_by_stack"], "count.negative",
                               PHASES)
