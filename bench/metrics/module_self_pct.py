"""Share of the window spent in module logic (``repro.module.*``) and
in nothing nested inside it."""

from bench import spans


def read(record):
    return spans.self_share(spans.load(record),
                            lambda n: n.startswith("repro.module."))
