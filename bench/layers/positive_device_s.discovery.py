"""Device time of the traced discovery job under the positive phase, s:
programs whose innermost phase span at launch is ``count.positive`` (the
pre-count's and the post-count's contractions from data)."""

from pathlib import Path

from bench import spans

ROOT = Path(__file__).resolve().parents[2]
PHASES = ("count.positive", "count.negative")


def read(ctx):
    att = spans.of_run(ctx, ROOT)
    if att is None:
        return None
    return spans.seconds_under(att["device_by_stack"], "count.positive",
                               PHASES)
