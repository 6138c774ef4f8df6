"""Replay-safety analyzer gate: every rule fires on its positive fixture,
stays quiet on the negative one, suppressions and the baseline behave, and
the tree itself scans clean (modulo the committed baseline).

The fixtures are the rule *spec*: if a rule's behaviour changes, these
snippets are the contract that changed.
"""

import json
import os
from pathlib import Path

import pytest

from repro.analysis import (
    filter_baselined,
    load_baseline,
    rule_catalog,
    scan_paths,
    scan_source,
)
from repro.analysis.__main__ import main as cli_main

REPO = Path(__file__).resolve().parents[1]


def rules_of(text, path="core/x.py", **kw):
    return sorted({f.rule for f in scan_source(text, path, **kw)})


def lines_of(text, rule, path="core/x.py", **kw):
    return [f.line for f in scan_source(text, path, **kw) if f.rule == rule]


# --------------------------------------------------------------------- #
# DET001 — unordered set iteration feeding scheduling / accumulation     #
# --------------------------------------------------------------------- #
DET001_POS = """\
def tick(sim, cams):
    for c in set(cams):
        sim.schedule(0.1, c)
    total = 0.0
    for w in {1.0, 2.0, 3.0}:
        total += w
    return total + sum(x for x in set(cams))
"""

DET001_NEG = """\
def tick(sim, cams, table):
    for c in sorted(set(cams)):          # ordered: fine
        sim.schedule(0.1, c)
    for k in table:                      # dict: insertion-ordered, fine
        table[k] += 1.0
    names = {c.name for c in cams}       # set built, never driving order
    return names
"""


def test_det001_fires_on_set_iteration_feeding_schedule():
    assert rules_of(DET001_POS) == ["DET001"]
    assert len(lines_of(DET001_POS, "DET001")) == 3


def test_det001_quiet_on_sorted_and_dict_iteration():
    assert rules_of(DET001_NEG) == []


def test_det001_scoped_to_scheduling_planes():
    # The same code outside core/sim/query (e.g. launch/) is not flagged.
    assert rules_of(DET001_POS, path="launch/x.py") == []


# --------------------------------------------------------------------- #
# DET002 — wall-clock reads                                              #
# --------------------------------------------------------------------- #
DET002_POS = """\
import time
from datetime import datetime

def stamp():
    a = time.time()
    b = datetime.now()
    return a, b
"""

DET002_NEG = """\
import time
from repro.core.clock import monotonic

def stamp():
    return monotonic(), time.perf_counter()
"""


def test_det002_fires_on_wall_clock_reads():
    assert rules_of(DET002_POS) == ["DET002"]
    assert len(lines_of(DET002_POS, "DET002")) == 2


def test_det002_quiet_on_monotonic():
    assert rules_of(DET002_NEG) == []


def test_det002_catches_from_import_alias():
    src = "from time import time as wall\nx = wall()\n"
    assert rules_of(src) == ["DET002"]


# --------------------------------------------------------------------- #
# DET003 — unseeded global RNG                                           #
# --------------------------------------------------------------------- #
DET003_POS = """\
import random
import numpy as np
from random import randint

def jitter():
    return random.random() + np.random.rand() + randint(1, 5)
"""

DET003_NEG = """\
import random
import numpy as np

def jitter(seed):
    rng = random.Random(seed)
    g = np.random.default_rng(seed)
    return rng.random() + g.standard_normal()
"""


def test_det003_fires_on_global_rng():
    assert rules_of(DET003_POS) == ["DET003"]
    assert len(lines_of(DET003_POS, "DET003")) == 3


def test_det003_quiet_on_seeded_generators():
    assert rules_of(DET003_NEG) == []


# --------------------------------------------------------------------- #
# DET004 — id()/hash() sort keys                                         #
# --------------------------------------------------------------------- #
def test_det004_fires_on_identity_sort_keys():
    pos = "a = sorted(xs, key=id)\nxs.sort(key=lambda o: hash(o))\n"
    assert rules_of(pos) == ["DET004"]
    assert len(lines_of(pos, "DET004")) == 2


def test_det004_quiet_on_stable_keys():
    neg = "a = sorted(xs, key=len)\nxs.sort(key=lambda o: o.name)\n"
    assert rules_of(neg) == []


# --------------------------------------------------------------------- #
# JAX001 — jit/pallas constructed outside the bound_jit_cache contract   #
# --------------------------------------------------------------------- #
JAX001_POS = """\
import jax

def dispatch(fn, x):
    step = jax.jit(fn)          # fresh compile cache per call
    return step(x)
"""

JAX001_NEG = """\
import functools
import jax

step = jax.jit(lambda x: x)     # module scope: constructed once

@jax.jit
def f(x):
    return x

@functools.partial(jax.jit, static_argnames=("n",))
def g(x, n):
    return x * n
"""


def test_jax001_fires_on_in_function_jit_construction():
    assert rules_of(JAX001_POS, path="kernels/foo/ops.py") == ["JAX001"]


def test_jax001_quiet_on_module_scope_and_decorators():
    assert rules_of(JAX001_NEG, path="kernels/foo/ops.py") == []


def test_jax001_exempts_bound_jit_cache_modules_and_kernel_defs():
    contract = "from ..dispatch import bound_jit_cache\n" + JAX001_POS
    assert rules_of(contract, path="kernels/foo/ops.py") == []
    pallas = (
        "import jax\nfrom jax.experimental import pallas as pl\n"
        "def run(x, interpret=False):\n"
        "    return pl.pallas_call(x, interpret=interpret)\n"
    )
    assert rules_of(pallas, path="kernels/foo/kernel.py") == []
    assert "JAX001" in rules_of(pallas, path="sim/bad.py")


# --------------------------------------------------------------------- #
# JAX002 — implicit host pulls in traced code                            #
# --------------------------------------------------------------------- #
JAX002_POS = """\
import jax
import numpy as np
from jax import lax

@jax.jit
def f(x):
    return x.item()

def outer(xs):
    def body(c, x):
        return c + float(np.asarray(x)), None
    return lax.scan(body, 0.0, xs)
"""

JAX002_NEG = """\
import jax
import numpy as np

def pad_and_run(fn, x):
    x = np.asarray(x)           # host-side prep before the jit boundary
    y = fn(x)
    return float(y)             # pull after the boundary
"""


def test_jax002_fires_inside_traced_functions():
    got = lines_of(JAX002_POS, "JAX002", path="kernels/foo/ops.py")
    assert len(got) == 3  # .item(), float(...), np.asarray(...)


def test_jax002_quiet_outside_traces():
    assert rules_of(JAX002_NEG, path="kernels/foo/ops.py") == []


# --------------------------------------------------------------------- #
# JAX003 — f32 accumulation under the mega-step f64 contract             #
# --------------------------------------------------------------------- #
JAX003_POS = """\
import jax.numpy as jnp

def books(rows):
    acc = jnp.zeros((4,), dtype=jnp.float32)
    return acc + rows.astype(jnp.float32).sum()
"""


def test_jax003_fires_only_in_the_megastep_plane():
    assert rules_of(JAX003_POS, path="kernels/megastep/ops.py") == ["JAX003"]
    assert rules_of(JAX003_POS, path="core/megastep.py") == ["JAX003"]
    # Other kernels own their dtype (f32 embeddings are the contract there).
    assert rules_of(JAX003_POS, path="kernels/spotlight_ball/ops.py") == []


def test_jax003_quiet_on_f64():
    neg = JAX003_POS.replace("float32", "float64")
    assert rules_of(neg, path="kernels/megastep/ops.py") == []


# --------------------------------------------------------------------- #
# JAX004 — un-shimmed shard_map imports                                  #
# --------------------------------------------------------------------- #
JAX004_EXPERIMENTAL = """\
from jax.experimental.shard_map import shard_map

def wrap(fn, mesh, specs):
    return shard_map(fn, mesh=mesh, check_rep=False, **specs)
"""

JAX004_NEW_API = """\
import jax

def wrap(fn, mesh, specs):
    return jax.shard_map(fn, mesh=mesh, check_vma=False, **specs)
"""

JAX004_SHIMMED = """\
from repro.distributed.compat import shard_map

def wrap(fn, mesh, specs):
    return shard_map(fn, mesh=mesh, check=False, **specs)
"""


def test_jax004_fires_on_unshimmed_shard_map():
    assert "JAX004" in rules_of(
        JAX004_EXPERIMENTAL, path="distributed/context_parallel.py"
    )
    assert "JAX004" in rules_of(
        JAX004_NEW_API, path="kernels/megastep/sharded.py"
    )


def test_jax004_quiet_on_the_shim_and_its_users():
    assert rules_of(JAX004_SHIMMED, path="kernels/megastep/sharded.py") == []
    # The shim itself is the one sanctioned probe site.
    assert "JAX004" not in rules_of(
        JAX004_NEW_API, path="distributed/compat.py"
    )


# --------------------------------------------------------------------- #
# EXC001 — silent broad excepts                                          #
# --------------------------------------------------------------------- #
EXC001_POS = """\
def load(path):
    try:
        return open(path)
    except Exception:
        return None

def tick(fn):
    try:
        fn()
    except:
        pass
"""

EXC001_NEG = """\
def load(path, log):
    try:
        return open(path)
    except OSError:
        return None          # narrow: fine

def tick(fn, log):
    try:
        fn()
    except Exception as e:
        log(e)               # recorded: fine

def strict(fn):
    try:
        fn()
    except Exception:
        raise                # re-raised: fine
"""


def test_exc001_fires_on_silent_broad_excepts():
    assert rules_of(EXC001_POS) == ["EXC001"]
    assert len(lines_of(EXC001_POS, "EXC001")) == 2


def test_exc001_quiet_on_narrow_recorded_or_reraised():
    assert rules_of(EXC001_NEG) == []


# --------------------------------------------------------------------- #
# OBS001 — metric registrations must be literal repro_* names with help   #
# --------------------------------------------------------------------- #
OBS001_POS = """\
def publish(registry, name):
    registry.counter(name, "computed name: invisible to the catalog")
    registry.gauge("bad-name!", "name outside the repro_ namespace")
    registry.histogram("repro_latency_seconds")
"""

OBS001_NEG = """\
def publish(registry):
    c = registry.counter(
        "repro_events_total", "Events by task.", labels=("task",)
    )
    c.inc(3, task="VA")
    registry.gauge("repro_queue_depth", help="Current queue depth.")
    registry.histogram("repro_latency_seconds", "End-to-end latency.")
"""


def test_obs001_fires_on_unauditable_registrations():
    assert rules_of(OBS001_POS) == ["OBS001"]
    # computed name; bad name; missing help — one finding each.
    assert len(lines_of(OBS001_POS, "OBS001")) == 3


def test_obs001_quiet_on_literal_registrations():
    assert rules_of(OBS001_NEG) == []


def test_obs001_exempts_the_metrics_module_itself():
    assert rules_of(OBS001_POS, path="obs/metrics.py") == []


# --------------------------------------------------------------------- #
# Suppressions                                                           #
# --------------------------------------------------------------------- #
def test_noqa_same_line_suppresses():
    src = "import time\nt = time.time()  # repro: noqa[DET002]\n"
    assert rules_of(src) == []


def test_noqa_comment_above_suppresses():
    src = (
        "import time\n"
        "# repro: noqa[DET002] — benchmark wall clock, outside the DES\n"
        "t = time.time()\n"
    )
    assert rules_of(src) == []


def test_noqa_wrong_rule_does_not_suppress():
    src = "import time\nt = time.time()  # repro: noqa[EXC001]\n"
    assert rules_of(src) == ["DET002"]


def test_noqa_list_suppresses_multiple():
    src = (
        "import time, random\n"
        "t = time.time() + random.random()  # repro: noqa[DET002,DET003]\n"
    )
    assert rules_of(src) == []


# --------------------------------------------------------------------- #
# KRN — kernel-contract tree checks                                      #
# --------------------------------------------------------------------- #
GOOD_KERNEL = """\
from jax.experimental import pallas as pl

def foo_pallas(x, *, interpret=False):
    return pl.pallas_call(_kern, interpret=interpret)(x)

def _kern(ref):
    pass
"""
GOOD_REF = "def foo_ref(x):\n    return x\n"
GOOD_OPS = "def foo(x):\n    return x\n"


def _make_kernel_pkg(root, name, kernel=GOOD_KERNEL, ref=GOOD_REF,
                     ops=GOOD_OPS, skip=()):
    pkg = root / "kernels" / name
    pkg.mkdir(parents=True)
    for fname, text in (("kernel.py", kernel), ("ref.py", ref), ("ops.py", ops)):
        if fname not in skip:
            (pkg / fname).write_text(text)
    return pkg


def _krn_scan(root, tests_dir=None):
    return [
        f for f in scan_paths([str(root)], tests_dir=tests_dir)
        if f.rule.startswith("KRN")
    ]


def test_krn_clean_triple_passes(tmp_path):
    _make_kernel_pkg(tmp_path, "foo")
    tests = tmp_path / "tests"
    tests.mkdir()
    (tests / "test_foo.py").write_text("from kernels.foo.ref import foo_ref\n")
    assert _krn_scan(tmp_path, tests_dir=str(tests)) == []


def test_krn001_missing_triple_member(tmp_path):
    _make_kernel_pkg(tmp_path, "foo", skip=("ref.py",))
    found = _krn_scan(tmp_path)
    assert [f.rule for f in found] == ["KRN001"]
    assert "ref.py" in found[0].message


def test_krn002_ref_importing_pallas(tmp_path):
    bad_ref = "from jax.experimental import pallas as pl\ndef foo_ref(x):\n    return x\n"
    _make_kernel_pkg(tmp_path, "foo", ref=bad_ref)
    assert [f.rule for f in _krn_scan(tmp_path)] == ["KRN002"]


def test_krn003_kernel_not_interpret_gated(tmp_path):
    bad_kernel = (
        "from jax.experimental import pallas as pl\n"
        "def foo_pallas(x):\n"
        "    return pl.pallas_call(_kern)(x)\n"
        "def _kern(ref):\n    pass\n"
    )
    _make_kernel_pkg(tmp_path, "foo", kernel=bad_kernel)
    assert [f.rule for f in _krn_scan(tmp_path)] == ["KRN003"]


def test_krn004_unreferenced_kernel(tmp_path):
    _make_kernel_pkg(tmp_path, "foo")
    tests = tmp_path / "tests"
    tests.mkdir()
    (tests / "test_other.py").write_text("def test_nothing():\n    pass\n")
    assert [f.rule for f in _krn_scan(tmp_path, tests_dir=str(tests))] == ["KRN004"]


# --------------------------------------------------------------------- #
# Baseline                                                               #
# --------------------------------------------------------------------- #
def test_baseline_requires_justifications(tmp_path):
    p = tmp_path / "b.json"
    p.write_text(json.dumps([{"rule": "DET002", "path": "x.py", "line": 2}]))
    with pytest.raises(ValueError, match="justification"):
        load_baseline(str(p))


def test_baseline_filters_known_and_reports_stale(tmp_path):
    findings = scan_source(DET002_POS, "core/x.py")
    entry = {
        "rule": findings[0].rule, "path": findings[0].path,
        "line": findings[0].line, "justification": "grandfathered",
    }
    stale_entry = {
        "rule": "DET002", "path": "core/gone.py", "line": 9,
        "justification": "file was deleted",
    }
    new, stale = filter_baselined(findings, [entry, stale_entry])
    assert len(new) == len(findings) - 1
    assert stale == [stale_entry]


# --------------------------------------------------------------------- #
# CLI                                                                    #
# --------------------------------------------------------------------- #
def test_cli_exits_nonzero_on_each_rule_family(tmp_path, capsys):
    cases = {
        "DET001": DET001_POS, "DET002": DET002_POS, "DET003": DET003_POS,
        "EXC001": EXC001_POS,
    }
    for rule, src in cases.items():
        # Place under a fake repro/core/ so package-scoped rules apply.
        f = tmp_path / "repro" / "core" / f"viol_{rule.lower()}.py"
        f.parent.mkdir(parents=True, exist_ok=True)
        f.write_text(src)
        assert cli_main([str(f)]) == 1, rule
        out = capsys.readouterr().out
        assert rule in out


def test_cli_exits_zero_on_clean_tree(tmp_path, capsys):
    f = tmp_path / "clean.py"
    f.write_text("x = 1\n")
    assert cli_main([str(f)]) == 0
    assert "0 new finding(s)" in capsys.readouterr().out


def test_cli_select_restricts_rules(tmp_path, capsys):
    f = tmp_path / "repro" / "core" / "viol.py"
    f.parent.mkdir(parents=True)
    f.write_text(DET002_POS + EXC001_POS)
    assert cli_main([str(f), "--select", "EXC001"]) == 1
    out = capsys.readouterr().out
    assert "EXC001" in out and "DET002" not in out


def test_cli_missing_path_is_usage_error(capsys):
    assert cli_main(["definitely/not/here.py"]) == 2


def test_cli_list_rules_covers_every_family(capsys):
    assert cli_main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    for rid in ("DET001", "DET002", "DET003", "DET004", "JAX001", "JAX002",
                "JAX003", "EXC001", "KRN001", "KRN002", "KRN003", "KRN004"):
        assert rid in out


def test_cli_write_baseline_roundtrip(tmp_path, capsys, monkeypatch):
    f = tmp_path / "repro" / "core" / "viol.py"
    f.parent.mkdir(parents=True)
    f.write_text(DET002_POS)
    b = tmp_path / "baseline.json"
    assert cli_main([str(f), "--baseline", str(b), "--write-baseline"]) == 0
    entries = json.loads(b.read_text())
    assert entries and entries[0]["rule"] == "DET002"
    # Unjustified snapshot entries are rejected until a human fills them in.
    assert cli_main([str(f), "--baseline", str(b)]) == 2
    for e in entries:
        e["justification"] = "fixture: grandfathered for the roundtrip test"
    b.write_text(json.dumps(entries))
    assert cli_main([str(f), "--baseline", str(b)]) == 0


# --------------------------------------------------------------------- #
# Self-scan: the tree itself holds the contract                          #
# --------------------------------------------------------------------- #
def test_self_scan_clean_modulo_committed_baseline():
    src = REPO / "src" / "repro"
    findings = scan_paths([str(src)], tests_dir=str(REPO / "tests"))
    baseline_path = REPO / "ANALYSIS_BASELINE.json"
    baseline = load_baseline(str(baseline_path))
    # Committed findings must be justified; path-normalize to the scan root.
    rel = [
        type(f)(f.rule, os.path.relpath(f.path, REPO).replace(os.sep, "/"),
                f.line, f.message)
        for f in findings
    ]
    new, _stale = filter_baselined(rel, baseline)
    assert new == [], "\n".join(f.render() for f in new)


def test_rule_catalog_is_documented():
    catalog = rule_catalog()
    doc = (REPO / "ANALYSIS.md").read_text()
    for rid in catalog:
        assert rid in doc, f"{rid} missing from ANALYSIS.md rule catalog"
