"""Share of the window's replays that the fused engine ran on the device;
nothing where the cell does not ask for the engine."""


def read(record):
    if record["engine"] != "megastep" or not record["replays"]:
        return None
    used = [r["engine_used"] for r in record["replays"]]
    return 100.0 * sum(u == "megastep-device" for u in used) / len(used)
