"""Self-tests of the benchmark harness, on the CPU."""

import json
import os

SIZES = os.path.join(os.path.dirname(os.path.abspath(__file__)), "sizes")


def cpu_size(cell: str) -> dict:
    """The scenario overrides that cut ``cell`` to a size a CPU test run
    holds: ``sizes/<cell>.json``."""
    with open(os.path.join(SIZES, cell + ".json")) as f:
        return json.load(f)
