"""Cameras the union spotlight kept lit, per frame tick of the window."""


def read(record):
    ticks = sum(r["ticks"] for r in record["replays"])
    if not ticks:
        return None
    return sum(r["lit"] for r in record["replays"]) / ticks
