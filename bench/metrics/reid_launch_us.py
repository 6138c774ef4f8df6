"""Host time of the device launches one re-ID dispatch makes: the
operand puts, the matcher's call and the answer's slices
(``repro.reid.put``/``call``/``slice``), per ``repro.reid.dispatch``."""

from bench import spans

PARTS = ("repro.reid.put", "repro.reid.call", "repro.reid.slice")


def read(record):
    s = spans.load(record)
    n = spans.count(s, "repro.reid.dispatch")
    if not n:
        return None
    return 1e6 * sum(spans.total(s, p) for p in PARTS) / n
