"""Share of the window spent in the DES event loop itself: routing,
transits, budgets and frame sourcing, outside every span nested in
``repro.des.run`` / ``repro.des.drain``."""

from bench import spans


def read(record):
    return spans.self_share(spans.load(record),
                            lambda n: n in ("repro.des.run", "repro.des.drain"))
