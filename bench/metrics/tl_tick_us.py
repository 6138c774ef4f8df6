"""Mean host time of one TL tick: spotlights and control deltas
(``repro.tl.tick``)."""

from bench import spans


def read(record):
    return spans.mean_us(spans.load(record), "repro.tl.tick")
