"""Compile the main-path kernels for a described TPU v5e at real widths.

Nothing runs: the TPU compiler, which is installed without the chip,
compiles each program for a ``v5e:2x2`` topology and refuses what the chip
would refuse (tiling that Mosaic and XLA disagree on, unsupported in-kernel
ops, VMEM overruns) — faults that interpret mode cannot show.  Widths are
a deployment's: a 65,536-row slab of 384-wide embeddings (an encoder such
as all-MiniLM-L6-v2).

The topology is described inside a fixture, never while a module is
imported: only one process at a time may load the TPU library, and every
test worker imports every test file.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import fused, ops
from repro.kernels.decision import victim_value_pallas
from repro.kernels.rac_value import rac_value_pallas
from repro.kernels.similarity_topk import (sim_top1_pallas, sim_topk_pallas,
                                           sim_topk_q8_pallas)

N, D, Q, T, K = 65536, 384, 128, 1024, 8


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield desc
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


F32, I32, I8 = jnp.float32, jnp.int32, jnp.int8


@pytest.mark.parametrize("q", [Q, 3 * Q])
def test_sim_top1_compiles(one_chip, q):
    # 3 query tiles: XLA tiles a 1-D f32[384] by 512, not by the block's 128
    _compile(lambda q, c, nv: sim_top1_pallas(q, c, nv, interpret=False),
             one_chip, ((q, D), F32), ((N, D), F32), ((), I32))


def test_sim_topk_compiles(one_chip):
    _compile(lambda q, c, nv: sim_topk_pallas(q, c, nv, K, interpret=False),
             one_chip, ((Q, D), F32), ((N, D), F32), ((), I32))


def test_sim_topk_q8_compiles(one_chip):
    _compile(lambda q8, qs, c8, cs, nv: sim_topk_q8_pallas(
        q8, qs, c8, cs, nv, K, interpret=False),
        one_chip, ((Q, D), I8), ((Q,), F32), ((N, D), I8), ((N,), F32),
        ((), I32))


def test_victim_value_compiles(one_chip):
    _compile(lambda tsi, tid, occ, tp, tl, tn: victim_value_pallas(
        tsi, tid, occ, tp, tl, tn, 0.001, interpret=False),
        one_chip, ((N,), F32), ((N,), I32), ((N,), I32), ((T,), F32),
        ((T,), I32), ((), I32))


def test_rac_value_compiles(one_chip):
    _compile(lambda tsi, tid, tp, tl: rac_value_pallas(
        tsi, tid, tp, tl, 0.001, 0, interpret=False),
        one_chip, ((N,), F32), ((N,), I32), ((T,), F32), ((T,), I32))


def test_route_topics_compiles(one_chip):
    _compile(lambda q, aug, nv: ops.route_topics_raw(
        q, aug, nv, 3, use_pallas=True, interpret=False),
        one_chip, ((16, D), F32), ((T, D + 1), F32), ((), I32))


def test_fused_quant_body_compiles(one_chip):
    b = 16
    body = functools.partial(fused._fused_quant_body, k=K, armed=True,
                             use_pallas=True, interpret=False)
    _compile(body, one_chip,
             ((b, D), F32), ((b, D), I8), ((b,), F32), ((b,), F32),
             ((N, D), F32), ((N, D), I8), ((N,), F32), ((N,), F32),
             ((), I32), ((), I32), ((), F32))


def test_fused_pruned_body_compiles(one_chip):
    b, cap_c = 16, 4096
    body = functools.partial(fused._fused_pruned_body, probes=2,
                             cap_c=cap_c, k=K, armed=True, use_pallas=True,
                             interpret=False)
    _compile(body, one_chip,
             ((b, D), F32), ((b, D), I8), ((b,), F32), ((b,), F32),
             ((N, D), F32), ((N, D), I8), ((N,), F32), ((N,), F32),
             ((T, D + 1), F32), ((T + 2,), I32), ((N,), I32),
             ((), I32), ((), I32), ((), I32), ((), F32))
