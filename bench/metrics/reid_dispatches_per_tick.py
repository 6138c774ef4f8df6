"""Re-ID dispatches the dispatch plane made per frame tick of the window."""


def read(record):
    calls = record["counters"].get("reid_multi_calls", 0)
    ticks = sum(r["ticks"] for r in record["replays"])
    if not calls or not ticks:
        return None
    return calls / ticks
