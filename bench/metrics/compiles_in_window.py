"""XLA programs compiled or loaded from the cache inside the window."""


def read(record):
    return float(record["compiles_in_window"])
