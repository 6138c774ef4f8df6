"""Share of the window spent in the budget and drop plane: drop point 2
(``repro.drop.*``), the batcher's auto-submit (``repro.batch.*``) and the
reject and accept fan-outs (``repro.budget.*``), each outside every span
nested in it."""

from bench import spans

PLANE = ("repro.drop.", "repro.batch.", "repro.budget.")


def read(record):
    return spans.self_share(spans.load(record), lambda n: n.startswith(PLANE))
