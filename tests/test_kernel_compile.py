"""Compiles for a described TPU v5e + CPU interpret sweeps.

The interpret-mode suites pin kernel *semantics*; they cannot show that a
kernel lowers through Mosaic.  The compile tests here build the main-path
kernels, as the served path dispatches them, at the ML-1M deployment's
widths (U=6040 users, I=3952 items, proxy dim 256, k=40, shortlist
M=0.15·U) for one chip of a described ``v5e:2x2`` topology — the TPU
compiler is installed even where no chip is attached.  Each asserts the
kernel is in the compiled program (``tpu_custom_call``) and bounds the
program's device memory from ``memory_analysis()``.

The topology is described inside a module-scoped fixture, never at
import: only one process may load the TPU library, and every test worker
imports this file.  The CPU-runnable part is an interpret-vs-oracle sweep
over odd, misaligned block shapes, which catches grid/padding bugs that
the default-aligned suites never exercise.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.index import clustered as cl
from repro.kernels import ref
from repro.kernels import support as sup
from repro.kernels.cluster import fused_centroid_distances
from repro.kernels.rerank import fused_rerank_scores
from repro.kernels.select import fused_scan_topm, select_topm
from repro.kernels.similarity import fused_similarity

U, I, P, K = 6040, 3952, 256, 40
M = int(0.15 * U)                  # IndexConfig.rerank_frac at ML-1M
BQ = 2048                          # fused pool-scan query block
KU = 8192                          # candidate-union bucket of one block
MEASURES = ("cosine", "jaccard", "pcc", "pcc_sig")


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
def spec(topo):
    """``spec(shape, dtype)``: an argument placed on one described chip."""
    from jax.sharding import SingleDeviceSharding
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype=jnp.float32: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one_chip)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _device_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes)


def test_cluster_kernel_compiles_for_v5e(spec):
    """Centroid distances: the 6040 user proxies vs ⌈√U⌉ = 78 centroids."""
    c = _compile(fused_centroid_distances, spec((U, P)), spec((78, P)))
    assert "tpu_custom_call" in c.as_text()
    assert _device_bytes(c) < 16 << 20


@pytest.mark.parametrize("measure", MEASURES)
def test_rerank_kernel_compiles_for_v5e(measure, spec):
    """The co-rated Gram kernel at the fused query's shapes: a 2048-query
    block against its 8192-row int8 candidate union."""
    fn = lambda *a: fused_rerank_scores(*a, measure=measure)
    c = _compile(fn, spec((BQ, I)), spec((KU, I), jnp.int8), spec((KU,)),
                 spec((KU,)))
    assert "tpu_custom_call" in c.as_text()
    assert _device_bytes(c) < 128 << 20


def test_fused_rerank_block_compiles_for_v5e(spec):
    """The whole rerank step as the fused query dispatches it: the sized
    candidate-union ``jnp.unique``, the int8 union gather and the kernel
    (the union sort dominates this compile, ~30 s on a CPU host)."""
    fn = lambda *a: cl._fused_rerank_block(
        *a, ku=KU, k=K, measure="pcc", beta=50.0, use_pallas=True,
        interpret=False)
    c = _compile(fn, spec((U, I), jnp.int8), spec((U, I)), spec((U,)),
                 spec((U,)), spec((BQ,), jnp.int32), spec((BQ, M), jnp.int32))
    assert "tpu_custom_call" in c.as_text()
    # operands (~120 MB) + the (BQ, KU) scores and gathered union rows
    assert _device_bytes(c) < 512 << 20


@pytest.mark.parametrize("measure", MEASURES)
def test_similarity_kernel_compiles_for_v5e(measure, spec):
    """The ``pallas`` exact backend's call: all users vs a 1024-user block."""
    fn = lambda a, b: fused_similarity(a, b, measure=measure, bm=256,
                                       bn=256, bk=512)
    c = _compile(fn, spec((U, I)), spec((1024, I)))
    assert "tpu_custom_call" in c.as_text()
    assert _device_bytes(c) < 256 << 20


@pytest.mark.parametrize("b", (256, 600))
def test_support_kernel_compiles_for_v5e(b, spec):
    """The item scorer over the stored (U, 1, I) tables: no copy of the
    two ~95 MB tables, and more than ``BB`` query rows loop over SMEM-sized
    row blocks."""
    w = sup.support_width(I)
    c = _compile(sup.fused_support_scores, spec((U, 1, w)), spec((U, 1, w)),
                 spec((b, K), jnp.int32), spec((b, K)), spec((b,)))
    assert "tpu_custom_call" in c.as_text()
    assert c.memory_analysis().temp_size_in_bytes < 16 << 20


def test_select_paths_compile_for_v5e(spec):
    """Shortlist selection as dispatched on TPU: the pool scan and the
    cluster-restricted scan both select with the ``lax.top_k`` twin."""
    pool = _compile(lambda p, q: cl._fused_scan_pool(p, q, m=M),
                    spec((U, P)), spec((BQ,), jnp.int32))
    restricted = _compile(
        lambda p, c, q: cl._fused_scan_restricted(p, c, q, m=M),
        spec((U, P)), spec((4096,), jnp.int32), spec((256,), jnp.int32))
    for c in (pool, restricted):
        assert "tpu_custom_call" not in c.as_text()
        assert _device_bytes(c) < 256 << 20


def test_select_kernel_sort_has_no_mosaic_lowering(spec):
    """Why the TPU selects with the twin: Mosaic refuses the select
    kernel's in-kernel ``lax.sort`` merge.  When this starts to compile,
    the kernel becomes a candidate for the served path again."""
    fn = lambda q, p, i: fused_scan_topm(q, p, i, m=M)
    with pytest.raises(NotImplementedError, match="sort"):
        _compile(fn, spec((256, P)), spec((U, P)), spec((256,), jnp.int32))


def _rerank_case(rng, g, kc, j):
    vq = (rng.integers(1, 6, (g, j))
          * (rng.random((g, j)) < 0.4)).astype(np.float32)
    rc = (rng.integers(1, 6, (kc, j))
          * (rng.random((kc, j)) < 0.4)).astype(np.float32)
    norms = np.sqrt((rc * rc).sum(1)).astype(np.float32)
    counts = (rc > 0).sum(1).astype(np.float32)
    return (jnp.asarray(vq), jnp.asarray(rc), jnp.asarray(norms),
            jnp.asarray(counts))


# -- CPU odd-block interpret sweeps -------------------------------------------

@pytest.mark.parametrize("blocks", [(8, 16, 32), (16, 48, 80),
                                    (24, 16, 112)])
def test_rerank_odd_blocks_sweep(blocks, rng):
    """Misaligned (bm, bn, bk) against odd operand shapes: the padded
    grid must never leak padding into the scores."""
    bm, bn, bk = blocks
    vq, rc, norms, counts = _rerank_case(rng, 29, 51, 173)
    want = np.asarray(ref.rerank_scores_ref(vq, rc, norms, counts,
                                            measure="pcc"))
    got = np.asarray(fused_rerank_scores(vq, rc, norms, counts,
                                         measure="pcc", bm=bm, bn=bn,
                                         bk=bk, interpret=True))
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("blocks", [(8, 32), (16, 128), (32, 64)])
def test_select_odd_blocks_sweep(blocks, rng):
    """Odd (bq, bn) select grids over a non-divisible pool, knockouts
    included — the running merge must stay canonical at every geometry."""
    bq, bn = blocks
    scores = rng.normal(size=(27, 211)).astype(np.float32)
    scores[rng.random(scores.shape) < 0.15] = -np.inf
    s_j = jnp.asarray(scores)
    want_v, want_i = ref.select_topm_ref(s_j, 19)
    got_v, got_i = select_topm(s_j, jnp.full((27,), -1, jnp.int32), m=19,
                               bq=bq, bn=bn, interpret=True)
    np.testing.assert_array_equal(np.asarray(want_i), np.asarray(got_i))
    np.testing.assert_array_equal(np.asarray(want_v), np.asarray(got_v))
