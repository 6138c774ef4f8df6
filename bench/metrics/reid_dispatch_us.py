"""Mean host time of one re-ID dispatch (``repro.reid.dispatch``)."""

from bench import spans


def read(record):
    return spans.mean_us(spans.load(record), "repro.reid.dispatch")
