"""``reid_match_multi``'s share of its roofline: the least time the chip
could take for the window's calls, counted from their real shapes, over
the device time of the re-ID program's events in the trace."""

from bench.kernels import roofline_seconds

PROGRAM = "jit_reid_multi_padded"


def read(record):
    tr = record.get("trace")
    shapes = record.get("reid_shapes")
    if not tr or not shapes or not record.get("peaks"):
        return None
    kernel_s = tr["module_s"].get(PROGRAM, 0.0)
    if kernel_s <= 0:
        return None
    return 100.0 * roofline_seconds(shapes, record["peaks"]) / kernel_s
