"""Serving: engine slot recycling + retrieval attention quality."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.core import vamana
from repro.models import model as M
from repro.serve import retrieval
from repro.serve.engine import Request, ServeEngine


@pytest.fixture(scope="module")
def tiny_model():
    cfg = registry.get_config("granite_3_8b").smoke()
    cfg = dataclasses.replace(cfg, vocab=64, n_layers=2)
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    return params, cfg


def test_engine_completes_requests(tiny_model):
    params, cfg = tiny_model
    eng = ServeEngine(params, cfg, batch_slots=2, max_seq=64)
    reqs = [Request(rid=i, prompt=np.array([1 + i, 5, 9]), max_new=4)
            for i in range(5)]
    done = eng.run(reqs)
    assert all(r.done for r in done)
    assert all(len(r.out) == 4 for r in done)
    assert all(0 <= t < cfg.vocab for r in done for t in r.out)


def test_engine_batches_share_slots(tiny_model):
    params, cfg = tiny_model
    eng = ServeEngine(params, cfg, batch_slots=2, max_seq=64)
    for i in range(3):
        eng.submit(Request(rid=i, prompt=np.array([2, 3]), max_new=2))
    ticks = 0
    while any(s is not None for s in eng.slots) or eng.queue:
        eng.step()
        ticks += 1
        assert ticks < 50


def test_retrieval_attention_approximates_exact():
    """With concentrated attention, PG top-k recovers the dense result."""
    r = np.random.default_rng(0)
    n, dh, b = 400, 16, 8
    keys = jnp.asarray(r.normal(size=(n, dh)), jnp.float32)
    values = jnp.asarray(r.normal(size=(n, dh)), jnp.float32)
    # queries close to specific keys -> attention mass concentrated
    tgt = r.integers(0, n, b)
    q = keys[tgt] * 4.0
    idx = retrieval.build_index(
        keys, values, vamana.VamanaParams(L=32, M=12, alpha=1.2))
    approx, res = retrieval.retrieval_attention(idx, q, top_k=32, ef=48)
    exact = retrieval.exact_attention(keys, values, q)
    cos = jnp.sum(approx * exact, -1) / (
        jnp.linalg.norm(approx, axis=-1) * jnp.linalg.norm(exact, axis=-1))
    assert float(jnp.mean(cos)) > 0.97
    assert int(res.n_computed) < b * n * 0.8   # sub-linear vs exhaustive


def test_retrieval_attention_batched_matches_unbatched():
    """Query blocking (static bucketed shapes + row-mask padding) is a pure
    scheduling change: identical pools, outputs, and #dist counters."""
    r = np.random.default_rng(2)
    n, dh, B = 400, 16, 20
    keys = jnp.asarray(r.normal(size=(n, dh)), jnp.float32)
    values = jnp.asarray(r.normal(size=(n, dh)), jnp.float32)
    q = keys[r.integers(0, n, B)] * 4.0
    idx = retrieval.build_index(
        keys, values, vamana.VamanaParams(L=32, M=12, alpha=1.2))
    out, res = retrieval.retrieval_attention(idx, q, top_k=16, ef=32)
    outb, resb = retrieval.retrieval_attention_batched(
        idx, q, top_k=16, ef=32, block_size=8)
    np.testing.assert_array_equal(np.asarray(res.pool_ids),
                                  np.asarray(resb.pool_ids))
    assert bool(jnp.allclose(out, outb, atol=1e-5))
    assert int(res.n_computed) == int(resb.n_computed)
    # ragged tail: B smaller than one block pads up and strips cleanly
    out3, _ = retrieval.retrieval_attention_batched(
        idx, q[:5], top_k=16, ef=32, block_size=64)
    assert bool(jnp.allclose(out3, out[:5], atol=1e-5))


def test_retrieval_attention_dense_hash_agree():
    """Serving's hash default returns the same retrieved set as the dense
    bitmap path on a real built index."""
    r = np.random.default_rng(4)
    keys = jnp.asarray(r.normal(size=(300, 8)), jnp.float32)
    vals = jnp.asarray(r.normal(size=(300, 8)), jnp.float32)
    idx = retrieval.build_index(
        keys, vals, vamana.VamanaParams(L=24, M=8, alpha=1.2))
    q = keys[:6] * 2
    _, rh = retrieval.retrieval_attention(idx, q, top_k=8, ef=16)
    _, rd = retrieval.retrieval_attention(idx, q, top_k=8, ef=16,
                                          visited_impl="dense")
    np.testing.assert_array_equal(np.asarray(rh.pool_ids),
                                  np.asarray(rd.pool_ids))


def test_sharded_index_serves_and_blocks_consistently():
    """build_index(num_shards>1): scatter-gather retrieval returns global
    key ids, approximates exact attention like the unsharded index, and
    the batched path stays a pure scheduling change (DESIGN.md §11)."""
    r = np.random.default_rng(6)
    n, dh, b = 400, 16, 12
    keys = jnp.asarray(r.normal(size=(n, dh)), jnp.float32)
    vals = jnp.asarray(r.normal(size=(n, dh)), jnp.float32)
    q = keys[r.integers(0, n, b)] * 4.0
    idx = retrieval.build_index(
        keys, vals, vamana.VamanaParams(L=32, M=12, alpha=1.2),
        num_shards=4)
    assert idx.num_shards == 4 and idx.graph_ids is None
    out, res = retrieval.retrieval_attention(idx, q, top_k=16, ef=32)
    ids = np.asarray(res.pool_ids)
    assert ids.min() >= 0 and ids.max() < n          # global, real ids
    exact = retrieval.exact_attention(keys, vals, q)
    cos = jnp.sum(out * exact, -1) / (
        jnp.linalg.norm(out, axis=-1) * jnp.linalg.norm(exact, axis=-1))
    assert float(jnp.mean(cos)) > 0.97
    outb, resb = retrieval.retrieval_attention_batched(
        idx, q, top_k=16, ef=32, block_size=8)
    np.testing.assert_array_equal(ids, np.asarray(resb.pool_ids))
    assert int(res.n_computed) == int(resb.n_computed)
    assert bool(jnp.allclose(out, outb, atol=1e-5))


def test_retrieval_knobs_num_shards():
    from repro.serve.engine import RetrievalKnobs
    assert RetrievalKnobs().index_kwargs() == {
        "num_shards": 1, "assign": "chunked", "quantize": "none"}
    assert RetrievalKnobs(num_shards=4).index_kwargs() == {
        "num_shards": 4, "assign": "chunked", "quantize": "none"}
    with pytest.raises(ValueError, match="num_shards"):
        RetrievalKnobs(num_shards=0)
    # the build has one driver: the knob is gone (DESIGN.md §12)
    assert "build_impl" not in {
        f.name for f in dataclasses.fields(RetrievalKnobs)}
    with pytest.raises(TypeError, match="build_impl"):
        RetrievalKnobs(build_impl="fused")


def test_retrieval_knobs_routing():
    """assign / routed_shards (DESIGN.md §13) thread through the knob
    kwargs and are validated at construction, not at search time."""
    from repro.serve.engine import RetrievalKnobs
    knobs = RetrievalKnobs(num_shards=4, assign="kmeans", routed_shards=2)
    assert knobs.index_kwargs()["assign"] == "kmeans"
    assert knobs.search_kwargs()["routed_shards"] == 2
    assert knobs.batched_kwargs()["routed_shards"] == 2
    assert RetrievalKnobs().search_kwargs()["routed_shards"] is None
    with pytest.raises(ValueError, match="assign"):
        RetrievalKnobs(assign="hashed")
    with pytest.raises(ValueError, match="routed_shards"):
        RetrievalKnobs(routed_shards=2)        # > num_shards=1
    with pytest.raises(ValueError, match="routed_shards"):
        RetrievalKnobs(num_shards=4, routed_shards=0)


def test_retrieval_knobs_quantize():
    """The quantize knob (DESIGN.md §16) validates at construction and
    threads through index_kwargs; search paths read it off the index."""
    from repro.serve.engine import RetrievalKnobs
    assert RetrievalKnobs(quantize="sq8").index_kwargs()["quantize"] == "sq8"
    with pytest.raises(ValueError, match="quantize"):
        RetrievalKnobs(quantize="sq4")


def test_quantized_index_serves():
    """build_index(quantize="sq8") end to end: the index carries the
    quantized corpus, search runs sq8 + fp32 re-rank, and the re-rank
    keeps exact-attention quality at fp32 level."""
    r = np.random.default_rng(12)
    n, dh, b = 300, 16, 8
    keys = jnp.asarray(r.normal(size=(n, dh)), jnp.float32)
    vals = jnp.asarray(r.normal(size=(n, dh)), jnp.float32)
    q = keys[r.integers(0, n, b)] + 0.05 * jnp.asarray(
        r.normal(size=(b, dh)), jnp.float32)
    idx = retrieval.build_index(
        keys, vals, vamana.VamanaParams(L=24, M=8, alpha=1.2),
        quantize="sq8")
    assert idx.quantize == "sq8" and idx.quant is not None
    assert idx.provenance["quantize"] == "sq8"
    out, res = retrieval.retrieval_attention(idx, q, top_k=8, ef=24)
    ids = np.asarray(res.pool_ids)
    assert ids.min() >= 0 and ids.max() < n
    # attention quality parity with the fp32 path on the SAME index (an
    # explicit quantize="none" override forces it): the re-rank restores
    # fp32 distances over the final pool, so outputs track closely
    out32, res32 = retrieval.retrieval_attention(idx, q, top_k=8, ef=24,
                                                 quantize="none")
    exact = retrieval.exact_attention(keys, vals, q)

    def _cos(a):
        return float(jnp.mean(jnp.sum(a * exact, -1) / (
            jnp.linalg.norm(a, axis=-1)
            * jnp.linalg.norm(exact, axis=-1))))

    assert _cos(out) >= _cos(out32) - 0.02
    # the re-rank is counted work on top of the quantized beam
    assert int(res32.n_computed) < int(res.n_computed)


def test_routed_sharded_index_serves():
    """kmeans-built sharded index + routed search: global ids, exact-
    attention quality preserved, and the routed path computes strictly
    fewer distances than scatter-gather (DESIGN.md §13)."""
    r = np.random.default_rng(8)
    n, dh, b = 400, 16, 12
    keys = jnp.asarray(r.normal(size=(n, dh)), jnp.float32)
    vals = jnp.asarray(r.normal(size=(n, dh)), jnp.float32)
    q = keys[r.integers(0, n, b)] * 4.0
    idx = retrieval.build_index(
        keys, vals, vamana.VamanaParams(L=32, M=12, alpha=1.2),
        num_shards=4, assign="kmeans")
    assert idx.shards.centroids is not None
    out_full, res_full = retrieval.retrieval_attention(
        idx, q, top_k=16, ef=32)
    out, res = retrieval.retrieval_attention(
        idx, q, top_k=16, ef=32, routed_shards=2)
    ids = np.asarray(res.pool_ids)
    assert ids.min() >= 0 and ids.max() < n
    assert int(res.n_computed) < int(res_full.n_computed)
    exact = retrieval.exact_attention(keys, vals, q)
    cos = jnp.sum(out * exact, -1) / (
        jnp.linalg.norm(out, axis=-1) * jnp.linalg.norm(exact, axis=-1))
    assert float(jnp.mean(cos)) > 0.97
    # blocked path stays a pure scheduling change under routing
    outb, resb = retrieval.retrieval_attention_batched(
        idx, q, top_k=16, ef=32, block_size=8, routed_shards=2)
    np.testing.assert_array_equal(ids, np.asarray(resb.pool_ids))
    assert bool(jnp.allclose(out, outb, atol=1e-5))
    # routing on an unsharded index is a user error, caught loudly
    idx1 = retrieval.build_index(
        keys, vals, vamana.VamanaParams(L=32, M=12, alpha=1.2))
    with pytest.raises(ValueError, match="unsharded"):
        retrieval.retrieval_attention(idx1, q, top_k=8, ef=16,
                                      routed_shards=2)


def test_retrieval_index_tunable_by_fastpgt():
    """The serving index is built from the same VamanaParams the tuner
    recommends — integration point of the paper technique."""
    from repro.core.tuner import params as pspace
    sp = pspace.space("vamana", scale=0.1)
    cfg = sp.decode(np.array([0.5, 0.5, 0.5]))
    bp = pspace.to_build_params("vamana", cfg)
    r = np.random.default_rng(1)
    keys = jnp.asarray(r.normal(size=(200, 8)), jnp.float32)
    vals = jnp.asarray(r.normal(size=(200, 8)), jnp.float32)
    idx = retrieval.build_index(keys, vals, bp)
    out, _ = retrieval.retrieval_attention(idx, keys[:4] * 2, top_k=8,
                                           ef=16)
    assert out.shape == (4, 8)
    assert bool(jnp.all(jnp.isfinite(out)))


def test_submit_rejects_oversized_prompt(tiny_model):
    """Prompts longer than max_seq-1 must fail at submit, not corrupt the
    KV cache during _admit's per-token prefill (regression: the overflow
    used to wrap pos past the cache pages and overwrite live slots)."""
    params, cfg = tiny_model
    eng = ServeEngine(params, cfg, batch_slots=2, max_seq=16)
    with pytest.raises(ValueError, match="prefill capacity"):
        eng.submit(Request(rid=0, prompt=np.arange(16, dtype=np.int32) % 8))
    # exactly at the limit (max_seq-1 tokens) is admissible and completes
    ok = Request(rid=1, prompt=(np.arange(15, dtype=np.int32) % 8) + 1,
                 max_new=1)
    eng.run([ok])
    assert ok.done and len(ok.out) == 1


def test_knobs_deadline_validation():
    from repro.serve.engine import RetrievalKnobs
    with pytest.raises(ValueError, match="deadline_ms"):
        RetrievalKnobs(deadline_ms=0.0)
    with pytest.raises(ValueError, match="deadline_ms"):
        RetrievalKnobs(deadline_ms=-5.0)
    k = RetrievalKnobs(deadline_ms=25.0)
    # consumed by the resilience layer only — never forwarded to search
    assert "deadline_ms" not in k.search_kwargs()
    assert "deadline_ms" not in k.batched_kwargs()
    assert "deadline_ms" not in k.index_kwargs()


def test_engine_retrieval_attachment(tiny_model):
    """attach_retrieval -> retrieve -> swap_retrieval_index round trip:
    the engine serves search through the resilience layer and hot-swaps
    a restored snapshot without touching decode state."""
    from repro.serve import resilience
    from repro.serve.engine import RetrievalKnobs
    params, cfg = tiny_model
    eng = ServeEngine(params, cfg, batch_slots=2, max_seq=32)
    q = jnp.asarray(np.random.default_rng(0).normal(size=(4, 8)),
                    jnp.float32)
    with pytest.raises(ValueError, match="attach_retrieval"):
        eng.retrieve(q)
    with pytest.raises(ValueError, match="attach_retrieval"):
        eng.swap_retrieval_index(None)
    r = np.random.default_rng(3)
    keys = jnp.asarray(r.normal(size=(128, 8)), jnp.float32)
    idx = retrieval.build_index(
        keys, keys, vamana.VamanaParams(L=16, M=6, alpha=1.2))
    rs = eng.attach_retrieval(idx, RetrievalKnobs(top_k=8, ef=16))
    assert isinstance(rs, resilience.ResilientSearcher)
    out, res = eng.retrieve(q)
    assert out.shape == (4, 8) and res.pool_ids.shape == (4, 8)
    import tempfile
    with tempfile.TemporaryDirectory() as d:
        resilience.save_index(idx, d)
        eng.swap_retrieval_index(resilience.load_index(d))
    out2, res2 = eng.retrieve(q)
    np.testing.assert_array_equal(np.asarray(res.pool_ids),
                                  np.asarray(res2.pool_ids))
    assert bool(jnp.allclose(out, out2))
