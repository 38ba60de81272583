"""Ahead-of-time compiles of the main path's Pallas kernels for a TPU v5e.

Each kernel is lowered at the size the chip runs it, with
``interpret=False``, for one chip of a described (not attached) ``v5e:2x2``
topology, and the compiled HLO must contain the Mosaic kernel
(``tpu_custom_call``).  This catches what interpret mode cannot: block
shapes the TPU compiler refuses, and kernels that do not fit its fast
memory.  Nothing runs; results and times come only from a chip run.

The topology is described inside a module-scoped fixture, never at import
time: only one process may load the TPU library, and every test worker
imports this file.  The persistent compilation cache is off while these
tests run, since an entry compiled for a described chip cannot be read
back without one.
"""
from __future__ import annotations

import functools
import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

N_SPARSE = 1 << 20      # LPs of the sparse path
N_DENSE = 4096          # LPs of the dense path
K = 8                   # machines
EDGE_BLOCK = 1280       # padded edges per 128-row tile (about 9 per row)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "no topology"
        jax.config.update("jax_enable_compilation_cache", was_on)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_text(fn, *shapes) -> str:
    return jax.jit(fn).lower(*shapes).compile().as_text()


def _row_operands(n, sharding):
    """(assignment, node_weights, loads, speeds, mu, total_weight)."""
    return (_spec((n,), jnp.int32, sharding),
            _spec((n,), jnp.float32, sharding),
            _spec((K,), jnp.float32, sharding),
            _spec((K,), jnp.float32, sharding),
            _spec((), jnp.float32, sharding),
            _spec((), jnp.float32, sharding))


@pytest.mark.parametrize("framework", ["c", "ct"])
def test_aggregate_dissat_kernel_compiles(one_chip, framework):
    from repro.kernels.dissatisfaction import (
        dissatisfaction_from_aggregate_pallas)

    def fn(agg, r, b, loads, speeds, mu, total, theta):
        return dissatisfaction_from_aggregate_pallas(
            agg, r, b, loads, speeds, mu, framework, theta=theta,
            total_weight=total, interpret=False)

    text = _compiled_text(
        fn, _spec((N_SPARSE, K), jnp.float32, one_chip),
        *_row_operands(N_SPARSE, one_chip),
        _spec((N_SPARSE,), jnp.float32, one_chip))
    assert "tpu_custom_call" in text


def test_batched_aggregate_dissat_kernel_compiles(one_chip):
    from repro.kernels.dissatisfaction import (
        dissatisfaction_from_aggregate_batched_pallas)
    bsz = 8

    def fn(agg, r, b, loads, speeds, mu, total):
        return dissatisfaction_from_aggregate_batched_pallas(
            agg, r, b, loads, speeds, mu, "c", total_weight=total,
            interpret=False)

    text = _compiled_text(
        fn, _spec((bsz, N_DENSE, K), jnp.float32, one_chip),
        _spec((bsz, N_DENSE), jnp.int32, one_chip),
        _spec((bsz, N_DENSE), jnp.float32, one_chip),
        _spec((bsz, K), jnp.float32, one_chip),
        _spec((bsz, K), jnp.float32, one_chip),
        _spec((bsz,), jnp.float32, one_chip),
        _spec((bsz,), jnp.float32, one_chip))
    assert "tpu_custom_call" in text


def test_cost_matrix_kernel_compiles(one_chip):
    from repro.kernels.dissatisfaction import cost_matrix_pallas

    def fn(adj, r, b, loads, speeds, mu, total):
        return cost_matrix_pallas(adj, r, b, loads, speeds, mu, "c",
                                  total_weight=total, interpret=False)

    text = _compiled_text(
        fn, _spec((N_DENSE, N_DENSE), jnp.float32, one_chip),
        *_row_operands(N_DENSE, one_chip))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("kernel", ["dissat", "sweep"])
def test_edge_block_kernels_compile(one_chip, kernel):
    from repro.kernels import edge_block

    call = {"dissat": edge_block.dissatisfaction_from_edges_pallas,
            "sweep": edge_block.sweep_candidates_from_edges_pallas}[kernel]
    tiles = N_SPARSE // edge_block.DEFAULT_TILE_N

    def fn(ls, ri, ew, r, b, loads, speeds, mu, total):
        layout = edge_block.EdgeTileLayout(
            local_senders=ls, recv_index=ri, edge_w=ew, num_nodes=N_SPARSE,
            tile_n=edge_block.DEFAULT_TILE_N,
            tile_e=edge_block.DEFAULT_TILE_E)
        return call(layout, r, b, loads, speeds, mu, "c",
                    total_weight=total, interpret=False)

    slab = functools.partial(_spec, (tiles, 1, EDGE_BLOCK),
                             sharding=one_chip)
    text = _compiled_text(fn, slab(jnp.int32), slab(jnp.int32),
                          slab(jnp.float32),
                          *_row_operands(N_SPARSE, one_chip))
    assert "tpu_custom_call" in text
