"""Compile the main path's Pallas kernels and jitted steps for a described
TPU v5e, without a chip attached.

Interpret mode (tests/test_kernels.py) checks what the kernels compute;
only the TPU compiler checks what it accepts: block shapes aligned to the
(8, 128) tile, int8's (32, 128) tile, layouts the Mosaic lowering can
produce.  Each test lowers a function at real widths against a
``v5e:2x2`` topology description and compiles it, so a kernel or a step
the chip would refuse fails here.  ``ops`` picks the Pallas path from
``jax.default_backend()``, which is the CPU here, so the tests steer
``ops._use_pallas`` themselves; every compiled program must then contain
a ``tpu_custom_call`` (a Pallas kernel, not the jnp reference).

The topology is described inside a module-scoped fixture, never at
import: only one process at a time may load the TPU library, and test
collection runs in every worker.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import build, search
from repro.core import metric as metric_lib
from repro.kernels import ops


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def pallas(monkeypatch):
    monkeypatch.setattr(ops, "_use_pallas", lambda: True)
    monkeypatch.delenv("REPRO_PALLAS_INTERPRET", raising=False)


def _compile(fn, *shapes):
    """Lower ``fn`` at ``shapes`` for the described chip and compile it;
    returns the compiled program's HLO text."""
    return jax.jit(fn).lower(*shapes).compile().as_text()


def _spec(one_chip):
    def spec(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    return spec


@pytest.mark.parametrize("d", [128, 960])
@pytest.mark.parametrize("form", ["gather", "gather_sq8", "pairwise",
                                  "pairwise_sq8"])
def test_distance_kernel_compiles_for_v5e(one_chip, pallas, form, d):
    """The four distance kernels at SIFT (128) and GIST (960) widths.
    The gather forms take a beam hop's (256 queries, W·Mx = 4·32) slab;
    the pairwise forms take 1000 queries against 10,000 rows and, for
    int8, a corpus shorter than one 32-row tile (the block ops.py picks
    for small corpora)."""
    s = _spec(one_chip)
    b, k = 256, 128
    if form == "gather":
        hlo = _compile(lambda u, c: ops.gather_distance(u, c, metric="l2"),
                       s((b, d)), s((b, k, d)))
    elif form == "gather_sq8":
        hlo = _compile(
            lambda u, c, sc, cn: ops.gather_distance_q(u, c, sc, cn,
                                                       metric="l2"),
            s((b, d)), s((b, k, d), jnp.int8), s((d,)), s((b, k)))
    elif form == "pairwise":
        hlo = _compile(lambda q, x: ops.pairwise_distance(q, x, "l2"),
                       s((1000, d)), s((10_000, d)))
    else:
        for nx in (10_000, 20):
            hlo = _compile(
                lambda q, c, sc, cn: ops.pairwise_distance_q(
                    q, metric_lib.QuantizedData(c, sc, cn), "l2"),
                s((1000, d)), s((nx, d), jnp.int8), s((d,)), s((nx,)))
            assert "tpu_custom_call" in hlo
    assert "tpu_custom_call" in hlo


def test_fused_vamana_insert_step_compiles_for_v5e(one_chip, pallas):
    """One fused insertion batch (search → mPrune → commit) of a 4-graph
    group at the tuner's n=100,000, d=128: 256 queries, L_max=128,
    M_max=32, shared V_delta (ESO) and EPO on, dense visited state."""
    s = _spec(one_chip)
    n, d, m, b, l_max, m_max = 100_000, 128, 4, 256, 128, 32
    i32 = jnp.int32

    def step(ids, dist, data, u, row_mask, queries, L, M, alpha, entry):
        out = build.insert_batch(
            ids, dist, data, u, row_mask, queries, L, M, alpha, entry,
            None, None, ef_max=l_max,
            max_hops=search.default_max_hops(l_max), share_cache=True,
            use_epo=True, metric="l2", visited_impl="dense", expand_width=1,
            k_in=16, m_max=m_max)
        return out[:3]

    hlo = _compile(step, s((m, n, m_max), i32), s((m, n, m_max)),
                   s((n, d)), s((b,), i32), s((b,), jnp.bool_), s((b, d)),
                   s((m,), i32), s((m,), i32), s((m,)), s((b, m), i32))
    assert "tpu_custom_call" in hlo


def test_unsharded_knn_search_compiles_for_v5e(one_chip, pallas):
    """Serving search at n=1,000,000, d=128 with the serving defaults
    (hash visited set, W=4, ef=64) on a 256-query batch, fp32 and sq8
    (int8 beam + fp32 re-rank)."""
    s = _spec(one_chip)
    n, d, b, mx = 1_000_000, 128, 256, 32
    kw = dict(k=10, ef=64, entry=0, visited_impl="hash", expand_width=4)

    def fp32(g, data, q):
        return search.knn_search(g, data, q, **kw).pool_ids

    def sq8(g, data, q, codes, scale, norms):
        quant = metric_lib.QuantizedData(codes, scale, norms)
        return search.knn_search(g, data, q, quantize="sq8", quant=quant,
                                 **kw).pool_ids

    shapes = (s((n, mx), jnp.int32), s((n, d)), s((b, d)))
    assert "tpu_custom_call" in _compile(fp32, *shapes)
    assert "tpu_custom_call" in _compile(
        sq8, *shapes, s((n, d), jnp.int8), s((d,)), s((n,)))
