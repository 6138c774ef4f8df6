"""Partitioning rules: divisibility safety + a real small-mesh lower/compile
(8 emulated CPU devices in a subprocess so jax's device count is fresh)."""

import contextlib
import subprocess
import sys
import textwrap
import warnings

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.config import get_config
from repro.distributed.partitioning import MeshRules, constrain, default_rules, param_specs
from repro.models import init_params, reduced_config


def test_constrain_is_noop_without_rules():
    x = jnp.ones((4, 4))
    y = constrain(x, ("batch", None))
    assert y is x


def test_resolve_drops_non_divisible_axes():
    mesh = jax.make_mesh((1,), ("model",))
    rules = MeshRules(mesh=mesh, rules={"model": "model"})
    # 1-wide axis always divides
    assert rules.resolve(("model",), (7,)) == P("model")

    class FakeMesh:
        shape = {"model": 16}
        axis_names = ("model",)

    rules = MeshRules(mesh=FakeMesh(), rules={"model": "model"})
    with pytest.warns(UserWarning, match="sharding dropped"):
        assert rules.resolve(("model",), (25,)) == P(None)  # 25 heads: replicated
    assert rules.resolve(("model",), (32,)) == P("model")


@contextlib.contextmanager
def warnings_none():
    """Assert the block emits no 'sharding dropped' warnings."""
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        yield
    assert not [w for w in rec if "sharding dropped" in str(w.message)]


def test_non_divisible_drop_is_reported_not_hidden():
    """The silent-sharding bug: a 60-expert stack placed expert-parallel on a
    16-wide axis used to replicate quietly (16x the expected memory).  The
    drop must now bump ``sharding_drops`` and warn once, naming the param
    path and the mesh axis."""
    import jax.numpy as _jnp

    class FakeMesh:
        shape = {"model": 16}
        axis_names = ("model",)

    rules = MeshRules(mesh=FakeMesh(), rules={"model": "model", "expert": "model"})
    params = {"experts": {"w_gate": jax.ShapeDtypeStruct((60, 8, 32), _jnp.float32)}}
    with pytest.warns(UserWarning) as rec:
        specs = param_specs(params, rules)
    # (60, 8, 32) wanted ("expert", None, None): E=60 does not divide 16.
    assert specs["experts"]["w_gate"] == P(None, None, None)
    assert rules.sharding_drops == 1
    assert rules.dropped == [("experts/w_gate", "model", 60)]
    msgs = [str(w.message) for w in rec if "sharding dropped" in str(w.message)]
    assert len(msgs) == 1
    assert "experts/w_gate" in msgs[0] and "'model'" in msgs[0] and "60" in msgs[0]
    # Second resolve of the same (path, axis): counted again, warned once.
    with warnings_none():
        param_specs(params, rules)
    assert rules.sharding_drops == 2


@pytest.mark.parametrize("arch", ["qwen3-8b", "deepseek-v2-lite-16b", "mamba2-1.3b", "hymba-1.5b"])
def test_param_specs_cover_all_leaves(arch):
    class FakeMesh:
        shape = {"data": 16, "model": 16}
        axis_names = ("data", "model")

    cfg = get_config(arch)
    rules = MeshRules(
        mesh=FakeMesh(),
        rules={"batch": ("data",), "model": "model", "fsdp": "data", "vocab": "model"},
    )
    params = jax.eval_shape(
        lambda k: init_params(k, cfg, dtype=jnp.bfloat16), jax.random.PRNGKey(0)
    )
    specs = param_specs(params, rules)
    leaves_p = jax.tree.leaves(params)
    leaves_s = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
    assert len(leaves_p) == len(leaves_s)
    # every spec's sharded dims divide the leaf dims
    for leaf, spec in zip(leaves_p, leaves_s):
        for dim, axis in zip(leaf.shape, tuple(spec) + (None,) * (len(leaf.shape) - len(spec))):
            if axis is None:
                continue
            size = 16 if isinstance(axis, str) else 16 ** len(axis)
            assert dim % size == 0, (arch, leaf.shape, spec)


SMALL_MESH_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.config import get_config
    from repro.distributed.partitioning import default_rules, mesh_rules, param_specs
    from repro.models import init_params, reduced_config
    from repro.training import TrainConfig, init_adamw, make_train_step

    cfg = reduced_config(get_config("llama3.2-1b"))
    mesh = jax.make_mesh((4, 2), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    rules = default_rules(mesh)
    with mesh, mesh_rules(rules):
        params = jax.eval_shape(lambda k: init_params(k, cfg), jax.random.PRNGKey(0))
        p_shard = jax.tree.map(lambda s: NamedSharding(mesh, s),
                               param_specs(params, rules),
                               is_leaf=lambda x: isinstance(x, P))
        opt = jax.eval_shape(init_adamw, params)
        o_shard = type(opt)(step=NamedSharding(mesh, P()), mu=p_shard, nu=p_shard)
        batch = {
            "tokens": jax.ShapeDtypeStruct((8, 64), jnp.int32),
            "labels": jax.ShapeDtypeStruct((8, 64), jnp.int32),
        }
        b_shard = {k: NamedSharding(mesh, P(("data",), None)) for k in batch}
        step = make_train_step(cfg, TrainConfig())
        compiled = jax.jit(
            step, in_shardings=(p_shard, o_shard, b_shard),
            out_shardings=(p_shard, o_shard, None),
        ).lower(params, opt, batch).compile()
        text = compiled.as_text()
        assert "all-reduce" in text or "reduce-scatter" in text, "expected collectives"
        print("SMALL_MESH_OK")
    """
)


def test_small_mesh_train_step_compiles_with_collectives():
    res = subprocess.run(
        [sys.executable, "-c", SMALL_MESH_SCRIPT],
        capture_output=True,
        text=True,
        env={**__import__("os").environ, "PYTHONPATH": "src"},
        timeout=420,
    )
    assert "SMALL_MESH_OK" in res.stdout, res.stdout + res.stderr
