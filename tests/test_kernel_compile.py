"""Compiles for a described TPU v5e + CPU interpret sweeps.

The interpret-mode suites pin kernel *semantics*; they cannot show that a
kernel lowers through Mosaic.  The compile tests here build the main-path
kernels, as the served path dispatches them, at the ML-1M deployment's
widths (U=6040 users, I=3952 items, proxy dim 256, k=40, shortlist
M=0.15·U), and the fit's rerank and the item scorer at the ML-10M
deployment's (U=69,878, I=10,677, half-star int8 code), and the
served path's select-and-rerank program there, for one chip of a
described ``v5e:2x2`` topology — the TPU
compiler is installed even where no chip is attached.  Each asserts the
kernel is in the compiled program (``tpu_custom_call``) and bounds the
program's device memory from ``memory_analysis()``.

The topology is described inside a module-scoped fixture, never at
import: only one process may load the TPU library, and every test worker
imports this file.  The CPU-runnable part is an interpret-vs-oracle sweep
over odd, misaligned block shapes, which catches grid/padding bugs that
the default-aligned suites never exercise.
"""

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.facade import AUTO_RERANK_FRAC
from repro.core.predict import GatherSource
from repro.index import clustered as cl
from repro.index import item_index
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
# ML-10M (bench/configs/ml10m-served.json): half stars 0.5-5.0, int8 code step 0.5
U10, I10 = 69878, 10677


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
    src = GatherSource(spec((U, I), jnp.int8), 1.0, (1.0, 5.0))
    c = _compile(fn, src, spec((U, I)), spec((U,)),
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
    c = _compile(functools.partial(sup.fused_support_scores,
                                   scale=(1.0, 5.0)),
                 spec((U, 1, w)), spec((U, 1, w)),
                 spec((b, K), jnp.int32), spec((b, K)), spec((b,)))
    assert "tpu_custom_call" in c.as_text()
    assert c.memory_analysis().temp_size_in_bytes < 16 << 20


def test_support_kernel_compiles_for_v5e_at_ml10m(spec):
    """The item scorer of a served ML-10M batch (16 rows) over the
    stored (U, 1, 10,752) f32 tables (3.0 GB each): the 16 MiB temp bound
    of the ML-1M shape holds, since the kernel copies only the neighbor
    rows' slices of the tables into VMEM (a (40, 8, 1,536) f32 buffer per
    table and slot) and its output is 16 × 10,752 × 4 B = 0.7 MB —
    nothing scales with U·I."""
    w = sup.support_width(I10)
    assert w == 21 * 512
    c = _compile(functools.partial(sup.fused_support_scores,
                                   scale=(0.5, 5.0)),
                 spec((U10, 1, w)), spec((U10, 1, w)),
                 spec((16, K), jnp.int32), spec((16, K)), spec((16,)))
    assert "tpu_custom_call" in c.as_text()
    assert c.memory_analysis().temp_size_in_bytes < 16 << 20


def test_support_kernel_compiles_for_v5e_at_ml10m_bulk_block(spec):
    """A bulk block of 1,024 rows at ML-10M width, as four ``BB``-row
    calls: the same 16 MiB temp bound, since each call gathers into VMEM
    and the four (256, 10,752) outputs are written in place of the
    block's (1,024, 10,752) result."""
    w = sup.support_width(I10)
    c = _compile(functools.partial(sup.fused_support_scores,
                                   scale=(0.5, 5.0)),
                 spec((U10, 1, w)), spec((U10, 1, w)),
                 spec((1024, K), jnp.int32), spec((1024, K)), spec((1024,)))
    assert "tpu_custom_call" in c.as_text()
    assert c.memory_analysis().temp_size_in_bytes < 16 << 20


def test_select_rerank_fits_ml10m(spec):
    """The support path's select-and-rerank program of a served ML-10M
    batch: 16 rows of kernel scores (16 × 10,752 f32), top-512 and the
    exact rerank over the int8 code.  Its temps are the rerank's own,
    the (U, 512) item-tile slices of the code (586 MB compiled for the
    rerank alone, 587 MB with the selection), so the bound is 640 MB."""
    src = GatherSource(spec((U10, I10), jnp.int8), 0.5, (0.5, 5.0))
    fn = lambda r, s, *a: item_index._select_rerank_items(
        r, s, *a, n=10, shortlist=512, item_block=512)
    c = _compile(fn, spec((U10, I10)), src, spec((U10, K)),
                 spec((U10, K), jnp.int32), spec((U10,)),
                 spec((16,), jnp.int32), spec((16, 21 * 512)),
                 spec((), jnp.int32))
    assert c.memory_analysis().temp_size_in_bytes < 640 << 20


@pytest.mark.parametrize("frac, bound_mb", [(0.15, 2500),
                                             (AUTO_RERANK_FRAC, 3000)],
                         ids=["index_default", "engine_auto"])
def test_fused_rerank_whole_fits_ml10m(frac, bound_mb, spec):
    """The fit's rerank block at ML-10M on the whole-matrix path: a
    2,048-query block scored against all 69,878 rows of the int8 code,
    padded once to whole kernel tiles (69,888 × 10,752).  The union path
    compiled to 16.06 GB here (131,072 gathered f32 rows, their padded
    copy, the f32 matrix and the (2,048, 131,072) scores).  This one, at
    shortlists of M = ``frac`` · U (10,481 at 0.15, 27,951 at 0.4):

    - arguments: the padded code 69,888 × 10,752 × 1 B = 751 MB, the
      (2,048, M) int32 shortlists (86 MB at 0.15, 229 MB at 0.4), norms
      and counts 0.6 MB;
    - temps: the query rows 2,048 × 10,752 × 4 B = 88 MB, the scores
      2,048 × 69,888 × 4 B = 573 MB, the shortlist's scores and the sort's
      keys and values, about 4 × the shortlists (344 MB at 0.15, 916 MB
      at 0.4);

    about 1.84 GB at 0.15 (1.59 GB compiled), so the bound is 2.5 GB;
    about 2.56 GB at 0.4 (2.47 GB compiled), so the bound is 3 GB."""
    n_pad, j_pad = 273 * 256, 21 * 512
    fn = lambda src, *a: cl._fused_rerank_whole(
        src, *a, k=K, measure="pcc", beta=50.0, use_pallas=True,
        interpret=False)
    src = GatherSource(spec((n_pad, j_pad), jnp.int8), 0.5, (0.5, 5.0))
    c = _compile(fn, src, spec((U10,)), spec((U10,)),
                 spec((BQ,), jnp.int32),
                 spec((BQ, int(frac * U10)), jnp.int32))
    assert "tpu_custom_call" in c.as_text()
    assert _device_bytes(c) < bound_mb << 20


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
