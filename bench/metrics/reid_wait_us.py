"""Mean host time VA spends blocked on a re-ID answer
(``repro.va.reid_wait``)."""

from bench import spans


def read(record):
    return spans.mean_us(spans.load(record), "repro.va.reid_wait")
