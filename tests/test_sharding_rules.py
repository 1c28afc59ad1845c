"""Sharding-rule unit tests: every param/cache leaf gets a legal spec.

Legality = each sharded dim divisible by its axis size, packing never split
(packed K-words stay whole), and the rules cover all 10 archs' pytrees
without falling through to errors.  Uses abstract (eval_shape) pytrees, so
the FULL configs are checked — this is the same machinery the 512-device
dry-run uses, minus XLA.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AbstractMesh, Mesh


from repro.configs import ASSIGNED, get_config
from repro.models import model_zoo as Z
from repro.runtime import sharding as SH


@pytest.fixture(scope="module")
def mesh():
    dev = np.array(jax.devices()[:1] * 1)
    # spec-level tests only need axis names/sizes; build an abstract mesh
    return AbstractMesh((16, 16), ("data", "model"))


def _check_tree(tree, shardings, mesh):
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    flat_sh = jax.tree.leaves(shardings, is_leaf=lambda x: hasattr(x, "spec"))
    assert len(flat) == len(flat_sh)
    for (path, leaf), sh in zip(flat, flat_sh):
        spec = sh.spec
        shape = leaf.shape
        assert len(spec) <= len(shape), f"{path}: spec {spec} rank > {shape}"
        for i, entry in enumerate(spec):
            if entry is None:
                continue
            axes = entry if isinstance(entry, tuple) else (entry,)
            size = 1
            for a in axes:
                size *= dict(zip(mesh.axis_names, mesh.axis_sizes))[a]
            assert shape[i] % size == 0, (
                f"{jax.tree_util.keystr(path)}: dim {i} ({shape[i]}) "
                f"not divisible by {axes} ({size})"
            )


@pytest.mark.parametrize("arch", ASSIGNED)
def test_train_param_specs_legal(arch, mesh):
    cfg = get_config(arch)
    params = jax.eval_shape(lambda k: Z.init_params(k, cfg), jax.random.PRNGKey(0))
    sh = SH.params_shardings(params, mesh, fsdp=True)
    _check_tree(params, sh, mesh)


@pytest.mark.parametrize("arch", ASSIGNED)
def test_serving_param_specs_legal(arch, mesh):
    cfg = get_config(arch)
    params = jax.eval_shape(
        lambda k: Z.prepare_serving_params(Z.init_params(k, cfg), cfg),
        jax.random.PRNGKey(0),
    )
    sh = SH.params_shardings(params, mesh)
    _check_tree(params, sh, mesh)


@pytest.mark.parametrize("arch", ["granite-8b", "deepseek-v3-671b", "mamba2-130m", "gemma3-27b"])
def test_cache_specs_legal(arch, mesh):
    cfg = get_config(arch)
    cache = jax.eval_shape(lambda: Z.init_cache(128, 32768, cfg))
    sh = SH.cache_shardings(cache, mesh, 128)
    _check_tree(cache, sh, mesh)


def test_row_parallel_never_splits_packed_words():
    """Row-parallel packed weights shard the WORD axis; 16-way sharding of
    K/32 words requires K % (32*16) == 0 — check the real archs satisfy it
    or the rule falls back to replication."""
    mesh = AbstractMesh((16, 16), ("data", "model"))
    for arch in ASSIGNED:
        cfg = get_config(arch)
        params = jax.eval_shape(
            lambda k: Z.prepare_serving_params(Z.init_params(k, cfg), cfg),
            jax.random.PRNGKey(0),
        )
        flat, _ = jax.tree_util.tree_flatten_with_path(params)
        for path, leaf in flat:
            names = [getattr(k, "key", str(k)) for k in path]
            if names[-1] == "w_packed":
                spec = SH.param_pspec(tuple(names), leaf.shape, mesh)
                for i, entry in enumerate(spec):
                    if entry is not None:
                        assert leaf.shape[i] % 16 == 0


def test_long500k_batch1_uses_sequence_sharding():
    mesh = AbstractMesh((16, 16), ("data", "model"))
    spec = SH.logical_batch_spec(1, 524288, mesh)
    assert spec == jax.sharding.PartitionSpec(None, "data")


def test_train4k_batch_sharded_over_pods_and_data():
    mesh = AbstractMesh((2, 16, 16), ("pod", "data", "model"))
    spec = SH.logical_batch_spec(256, 4096, mesh)
    assert spec == jax.sharding.PartitionSpec(("pod", "data"), None)
