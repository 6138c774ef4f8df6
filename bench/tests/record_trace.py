"""Record the small TPU trace that ``test_trace.py`` reduces.

    python3 -m bench.tests.record_trace <out.xplane.pb>

Three re-ID dispatches at 1000-camera shapes (3 rows, 16 queries, 128
features) inside a ``bench.window`` annotation, each after a 2 ms host
sleep annotated ``bench.des``.  Needs a TPU.
"""

import glob
import os
import shutil
import sys
import tempfile
import time

import jax
import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(ROOT, "src"))

from repro.kernels import dispatch  # noqa: E402


def main(out: str) -> int:
    if jax.devices()[0].platform != "tpu":
        print("record_trace: needs a TPU", file=sys.stderr)
        return 2
    rng = np.random.default_rng(0)
    gallery = rng.normal(size=(3, 128)).astype(np.float32)
    queries = rng.normal(size=(16, 128)).astype(np.float32)
    mask = np.ones((3, 16), bool)
    np.asarray(dispatch.reid_match_multi(gallery, queries, mask=mask)[1])
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    with tempfile.TemporaryDirectory() as tmp:
        jax.profiler.start_trace(tmp, profiler_options=opts)
        with jax.profiler.TraceAnnotation("bench.window"):
            for _ in range(3):
                with jax.profiler.TraceAnnotation("bench.des"):
                    time.sleep(0.002)
                with jax.profiler.TraceAnnotation("bench.reid"):
                    np.asarray(dispatch.reid_match_multi(gallery, queries, mask=mask)[1])
        jax.profiler.stop_trace()
        (path,) = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)
        shutil.copy(path, out)
    print(f"wrote {out} ({os.path.getsize(out)} bytes)")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
