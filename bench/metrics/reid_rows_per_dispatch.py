"""Mean gallery rows per re-ID dispatch: how full the VA batches that
reach the dispatch plane are (one row a call with drops off)."""


def read(record):
    shapes = record.get("reid_shapes")
    if not shapes:
        return None
    return sum(s[0] for s in shapes) / len(shapes)
