"""Self-tests for the JAX import point (repro.compat).

Each export must resolve and behave as the JAX API it names: shard_map
runs a real program, mesh construction produces meshes with the right
axis names, and tree-path round-trips agree with jax.tree_util.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro import compat


def test_every_export_resolves():
    for name in compat.__all__:
        assert getattr(compat, name, None) is not None, name


# --------------------------------------------------------------------------
# shard_map
# --------------------------------------------------------------------------

def test_shard_map_identity_program():
    mesh = compat.make_mesh((1, 1), ("data", "model"))
    f = compat.shard_map(lambda x: x * 2.0, mesh=mesh, in_specs=P(),
                         out_specs=P(), check_vma=False)
    out = jax.jit(f)(jnp.arange(8, dtype=jnp.float32))
    np.testing.assert_array_equal(np.asarray(out), np.arange(8) * 2.0)


def test_shard_map_decorator_form():
    mesh = compat.make_mesh((1, 1), ("data", "model"))

    @compat.shard_map(mesh=mesh, in_specs=P(), out_specs=P(),
                      check_vma=False)
    def double(x):
        return x + x

    np.testing.assert_array_equal(np.asarray(double(jnp.ones(4))),
                                  np.full(4, 2.0))


def test_shard_map_axis_queries():
    """axis_size + a named-axis collective through the compat shard_map."""
    mesh = compat.make_mesh((1, 1), ("data", "model"))

    def fn(x):
        p = compat.axis_size("model")
        return jax.lax.psum(x, "model") + 0.0 * p

    out = jax.jit(compat.shard_map(fn, mesh=mesh, in_specs=P(),
                                   out_specs=P(), check_vma=False))(
        jnp.ones(3))
    np.testing.assert_array_equal(np.asarray(out), np.ones(3))


# --------------------------------------------------------------------------
# mesh construction
# --------------------------------------------------------------------------

def test_make_mesh_axis_names_and_shape():
    mesh = compat.make_mesh((1, 1), ("data", "model"))
    assert mesh.axis_names == ("data", "model")
    assert mesh.shape["data"] == 1 and mesh.shape["model"] == 1


def test_production_mesh_helper_uses_compat():
    from repro.launch.mesh import make_mesh as launch_make_mesh
    mesh = launch_make_mesh((1, 1, 1), ("pod", "data", "model"))
    assert mesh.axis_names == ("pod", "data", "model")


# --------------------------------------------------------------------------
# tree shims
# --------------------------------------------------------------------------

def test_tree_path_round_trip():
    tree = {"a": {"b": jnp.zeros(2)}, "c": [jnp.ones(1), jnp.ones(3)]}
    flat = compat.tree_leaves_with_path(tree)
    # same leaves in the same order as the plain flatten
    plain = compat.tree_leaves(tree)
    assert len(flat) == len(plain)
    for (_, leaf), ref in zip(flat, plain):
        assert leaf is ref
    # keystr produces the canonical jax.tree_util rendering
    keys = [compat.keystr(path) for path, _ in flat]
    assert keys == [jax.tree_util.keystr(p)
                    for p, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]


def test_tree_flatten_unflatten_structure():
    tree = {"x": [1, 2], "y": (3,)}
    leaves, treedef = compat.tree_flatten(tree)
    assert leaves == [1, 2, 3]
    assert compat.tree_structure(tree) == treedef
    assert compat.tree_unflatten(treedef, leaves) == tree
    doubled = compat.tree_map(lambda v: v * 2, tree)
    assert doubled == {"x": [2, 4], "y": (6,)}


def test_tree_map_with_path():
    tree = {"a": 1, "b": 2}
    tagged = compat.tree_map_with_path(
        lambda p, v: (compat.keystr(p), v), tree)
    assert tagged == {"a": ("['a']", 1), "b": ("['b']", 2)}


def test_grep_discipline_no_direct_version_sensitive_imports():
    """The acceptance-criteria grep, as a test: no module outside compat
    touches the version-sensitive symbols directly."""
    import pathlib
    import re
    root = pathlib.Path(__file__).resolve().parents[1]
    pat = re.compile(r"from jax import shard_map"
                     r"|jax\.sharding import AxisType"
                     r"|jax\.tree\.leaves_with_path")
    offenders = []
    for d in ("src", "tests"):
        for f in (root / d).rglob("*.py"):
            if f.name in ("compat.py", "test_compat.py"):
                continue  # compat itself + this file's pattern literals
            if pat.search(f.read_text()):
                offenders.append(str(f.relative_to(root)))
    assert not offenders, offenders


def test_grep_discipline_codecs_only_constructed_in_core():
    """Compression policy is declarative: every layer above ``core/``
    (models, train, serve, launch, ckpt, examples, benchmarks) selects
    codecs through the registry spec grammar — never by instantiating
    codec classes directly.  Tests may construct codecs (they test them).
    """
    import pathlib
    import re
    root = pathlib.Path(__file__).resolve().parents[1]
    pat = re.compile(r"\b(?:IdentityCodec|TacoCodec|Sdp4BitCodec"
                     r"|TahQuantCodec|Int8Codec)\s*\(")
    offenders = []
    for d in ("src/repro", "examples", "benchmarks"):
        for f in (root / d).rglob("*.py"):
            if f.parent.name == "core":
                continue  # the codecs + their registry live here
            if pat.search(f.read_text()):
                offenders.append(str(f.relative_to(root)))
    assert not offenders, \
        f"construct codecs via repro.core.registry specs, not directly: " \
        f"{offenders}"
