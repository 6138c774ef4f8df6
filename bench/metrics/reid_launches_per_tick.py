"""Device launches of the re-ID matcher per frame tick of the window: how
many launches the dispatch plane's queue answers a tick's dispatches with."""


def read(record):
    launches = record["counters"].get("reid_multi_launches")
    ticks = sum(r["ticks"] for r in record["replays"])
    if launches is None or not ticks:
        return None
    return launches / ticks
