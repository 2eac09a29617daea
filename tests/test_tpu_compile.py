"""Compile the main-path Pallas kernels for a described TPU v5e.

Interpret-mode tests cannot see what Mosaic refuses (layouts, unaligned
dynamic stores, VMEM overruns); the TPU compiler is installed here and
compiles for a chip that is described, not attached.  Shapes are the
flagship's (SIFT-like 1M x 128, IVF-PQ 4096 lists x cap 256, pq_dim 64
at 8 bits, nq 5000 x 72 probes, k = kt = 10), and one shard of the routed
index over the described 2x2 mesh (4,096 lists placed by owner with two
copies: 2,049 local slots of cap 416, k 20 re-ranked to 10 on the shard).

The topology is described inside a module fixture, never at import: only
one process may load libtpu, and every xdist worker imports this file.
"""

import functools
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

from raft_tpu.distance.types import DistanceType
from raft_tpu.distributed import ann
from raft_tpu.neighbors import grouped
from raft_tpu.ops import cagra_hop_pallas as chp
from raft_tpu.ops.fused_l2_nn_pallas import fused_l2_nn_pallas
from raft_tpu.ops import kmeans_update_pallas as kup
from raft_tpu.ops import pq_code_scan_pallas as pcs
from raft_tpu.ops import pq_group_scan_pallas as pgs
from raft_tpu.ops import vmem_budget as vb

NQ, N_PROBES, N_LISTS, CAP, ROT, K, KT = 5000, 72, 4096, 256, 128, 10, 10
PQ_DIM, PQ_BITS, BOOK = 64, 8, 256
N_DB, DIM, N_CLUSTERS = 1_000_000, 128, 1024
HOP_NQ, HOP_WD, HOP_PDIM = 64, 32, 32
# one shard of the routed r = 2 index over four chips
R_SLOTS, R_CAP, R_KR, R_K, N_ROWS = 2 * N_LISTS // 4 + 1, 416, 20, 10, N_DB


@pytest.fixture(scope="module")
def topo():
    """Only a missing TPU compiler skips: any other failure to describe
    the chip (a broken libtpu, a JAX upgrade) fails every test here."""
    if importlib.util.find_spec("libtpu") is None:
        pytest.skip("libtpu is not installed: no TPU compiler to run")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    return topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")


@pytest.fixture(scope="module")
def spec(topo):
    """Build ShapeDtypeStructs on one described chip; the persistent
    compilation cache is off meanwhile (entries compiled for a described
    chip cannot be read back without one)."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    one_chip = SingleDeviceSharding(topo.devices[0])
    yield lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                    sharding=one_chip)
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _assert_kernel(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def _prefetch_counts(jaxpr):
    """Scalar-prefetch operand count of every pallas_call in ``jaxpr``
    and the jaxprs it calls."""
    counts = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            counts.append(eqn.params["grid_mapping"].num_index_operands)
        for v in eqn.params.values():
            sub = getattr(v, "jaxpr", v)
            if hasattr(sub, "eqns"):
                counts += _prefetch_counts(sub)
    return counts


def _assert_fused_kernel(fn, *args):
    """A fused scan compiles for the chip with its two scalar-prefetch
    operands, the group list and the live group count that its steps
    past the live groups skip by."""
    traced = jax.jit(fn).trace(*args)
    assert _prefetch_counts(traced.jaxpr.jaxpr) == [2]
    assert "tpu_custom_call" in traced.lower().compile().as_text()


def _groups(spec, nq=NQ):
    ng, _ = grouped.group_capacity(nq, N_PROBES, N_LISTS)
    return ng, (spec((ng,), jnp.int32), spec((ng, grouped.GROUP), jnp.int32),
                spec((nq, ROT), jnp.float32), spec((N_LISTS, ROT), jnp.float32))


def _adm(spec, ng, with_adm):
    return spec((ng, grouped.GROUP, CAP // 32), jnp.int32) if with_adm \
        else None


# (nq, k, kt) of the fused scans: the flagship, the benchmark's batch
# cell (k = kt = 20 before a 2x refine) and a 64-row serving bucket —
# the SMEM query-row block, the dynamic row copies and the in-VMEM
# transposes must lower at each
FUSED_SHAPES = [(NQ, K, KT), (5000, 20, 20), (64, 20, 20)]


@pytest.mark.parametrize("nq,k,kt", FUSED_SHAPES)
@pytest.mark.parametrize("with_adm", [False, True])
def test_fused_recon_scan(spec, with_adm, nq, k, kt):
    ng, head = _groups(spec, nq)
    assert vb.fused_scan_fits(pgs._fused_bytes(CAP, ROT, kt, k, nq, 2))
    fn = functools.partial(pgs.grouped_l2_scan_fused, kt=kt, k=k,
                           n_probes=N_PROBES)
    _assert_fused_kernel(lambda *a: fn(*a[:7], adm_words=a[7]), *head,
                   spec((N_LISTS, CAP, ROT), jnp.bfloat16),
                   spec((N_LISTS, CAP), jnp.float32),
                   spec((N_LISTS, CAP), jnp.int32), _adm(spec, ng, with_adm))


@pytest.mark.parametrize("nq,k,kt", FUSED_SHAPES)
@pytest.mark.parametrize("with_adm", [False, True])
def test_fused_code_scan(spec, with_adm, nq, k, kt):
    ng, head = _groups(spec, nq)
    assert vb.fused_scan_fits(
        pcs._fused_codes_bytes(CAP, ROT, kt, k, nq, PQ_DIM, PQ_BITS))
    fn = functools.partial(pcs.grouped_code_scan_fused, kt=kt, k=k,
                           n_probes=N_PROBES, pq_bits=PQ_BITS)
    _assert_fused_kernel(lambda *a: fn(*a[:8], adm_words=a[8]), *head,
                   spec((N_LISTS, pcs.code_lane_words(PQ_DIM, PQ_BITS), CAP),
                        jnp.int32),
                   spec((PQ_DIM, BOOK, ROT // PQ_DIM), jnp.float32),
                   spec((N_LISTS, CAP), jnp.float32),
                   spec((N_LISTS, CAP), jnp.int32), _adm(spec, ng, with_adm))


def test_group_scan(spec):
    _, head = _groups(spec)
    _assert_kernel(functools.partial(pgs.grouped_l2_scan, kt=KT,
                                     n_probes=N_PROBES), *head,
                   spec((N_LISTS, CAP, ROT), jnp.bfloat16),
                   spec((N_LISTS, CAP), jnp.float32),
                   spec((N_LISTS, CAP), jnp.int32))


def test_flat_scan(spec):
    _, (gl, sp, q, _) = _groups(spec)
    _assert_kernel(functools.partial(pgs.grouped_flat_l2_scan, kt=KT,
                                     n_probes=N_PROBES), gl, sp, q,
                   spec((N_LISTS, CAP, DIM), jnp.float32),
                   spec((N_LISTS, CAP), jnp.float32),
                   spec((N_LISTS, CAP), jnp.int32))


def test_code_scan(spec):
    _, head = _groups(spec)
    _assert_kernel(functools.partial(pcs.grouped_code_scan, kt=KT,
                                     n_probes=N_PROBES, pq_bits=PQ_BITS),
                   *head,
                   spec((N_LISTS, pcs.code_lane_words(PQ_DIM, PQ_BITS), CAP),
                        jnp.int32),
                   spec((PQ_DIM, BOOK, ROT // PQ_DIM), jnp.float32),
                   spec((N_LISTS, CAP), jnp.float32),
                   spec((N_LISTS, CAP), jnp.int32))


def test_fused_l2_nn(spec):
    _assert_kernel(lambda x, y: fused_l2_nn_pallas(x, y),
                   spec((N_DB, DIM), jnp.float32),
                   spec((N_CLUSTERS, DIM), jnp.float32))


def test_fused_assign_update(spec):
    tile = kup.best_tile(N_DB, DIM, N_CLUSTERS, True)
    assert tile > 0
    _assert_kernel(functools.partial(kup.fused_assign_update, tile=tile),
                   spec((N_DB, DIM), jnp.float32),
                   spec((N_DB,), jnp.float32),
                   spec((N_CLUSTERS, DIM), jnp.float32))


@pytest.mark.parametrize("itopk,variant", [(32, 1), (64, 2)])
def test_fused_hop(spec, itopk, variant):
    """itopk 32 takes the legacy in-pass merge, 64 the staged merge."""
    assert chp.hop_merge_window(HOP_NQ, itopk, HOP_WD, HOP_PDIM) == variant
    _assert_kernel(
        functools.partial(chp.fused_hop, itopk=itopk, ip_metric=False),
        spec((HOP_NQ, HOP_PDIM), jnp.float32), spec((HOP_NQ,), jnp.float32),
        spec((HOP_NQ, HOP_WD, HOP_PDIM), jnp.float32),
        spec((HOP_NQ, HOP_WD), jnp.float32), spec((HOP_NQ, HOP_WD), jnp.int32),
        spec((HOP_NQ, itopk), jnp.float32), spec((HOP_NQ, itopk), jnp.int32),
        spec((HOP_NQ, itopk), jnp.bool_))


def _routed_head(spec):
    ng, _ = grouped.group_capacity(NQ, N_PROBES, R_SLOTS)
    return ng, (spec((ng,), jnp.int32), spec((ng, grouped.GROUP), jnp.int32),
                spec((NQ, ROT), jnp.float32),
                spec((R_SLOTS, ROT), jnp.float32))


@pytest.mark.parametrize("kernel", ["codes", "recon"])
def test_fused_scan_at_routed_shard_shapes(spec, kernel):
    """The kernels one shard of the routed cell runs: its 2,049 local
    slots, the capacity sized from every pair of the batch (4,862 grid
    steps), k = kt = 20 ahead of the re-rank."""
    ng, head = _routed_head(spec)
    assert ng == 4862
    if kernel == "codes":
        fits = vb.fused_scan_fits(pcs._fused_codes_bytes(
            R_CAP, ROT, R_KR, R_KR, NQ, PQ_DIM, PQ_BITS))
        fn = functools.partial(pcs.grouped_code_scan_fused, kt=R_KR, k=R_KR,
                               n_probes=N_PROBES, pq_bits=PQ_BITS)
        data = (spec((R_SLOTS, pcs.code_lane_words(PQ_DIM, PQ_BITS), R_CAP),
                     jnp.int32),
                spec((PQ_DIM, BOOK, ROT // PQ_DIM), jnp.float32))
    else:
        fits = vb.fused_scan_fits(
            pgs._fused_bytes(R_CAP, ROT, R_KR, R_KR, NQ, 2))
        fn = functools.partial(pgs.grouped_l2_scan_fused, kt=R_KR, k=R_KR,
                               n_probes=N_PROBES)
        data = (spec((R_SLOTS, R_CAP, ROT), jnp.bfloat16),)
    assert fits
    _assert_fused_kernel(fn, *head, *data,
                         spec((R_SLOTS, R_CAP), jnp.float32),
                         spec((R_SLOTS, R_CAP), jnp.int32))


def test_routed_refined_search_program(topo, spec):
    """The whole routed shard program over the described 2x2 mesh: the
    coarse select, the fused code scan at k 20, the re-rank against the
    rows the shard holds, the all_gather of candidates and the psum of
    the exact distances (``spec`` keeps the compile cache off)."""
    mesh = jax.sharding.Mesh(np.asarray(topo.devices), ("data",))

    def on(shape, dtype, sharded=True):
        axes = ("data",) + (None,) * (len(shape) - 1) if sharded else ()
        return jax.ShapeDtypeStruct(shape, dtype, sharding=NamedSharding(
            mesh, PartitionSpec(*axes)))
    n_dev = len(topo.devices)
    ng, _ = grouped.group_capacity(NQ, N_PROBES, R_SLOTS)
    sharded = (on((n_dev, R_SLOTS, ROT), jnp.float32),
               on((n_dev, R_SLOTS, pcs.code_lane_words(PQ_DIM, PQ_BITS),
                   R_CAP), jnp.int32),
               on((n_dev, R_SLOTS, R_CAP), jnp.float32),
               on((n_dev, R_SLOTS, R_CAP), jnp.int32),
               on((n_dev, R_SLOTS, R_CAP, DIM), jnp.float32),
               on((n_dev, N_ROWS), jnp.int32))
    replicated = (on((N_LISTS, ROT), jnp.float32, False),
                  on((DIM, ROT), jnp.float32, False),
                  on((N_LISTS,), jnp.int32, False),
                  on((N_LISTS,), jnp.int32, False),
                  on((PQ_DIM, BOOK, ROT // PQ_DIM), jnp.float32, False))
    text = ann._dist_search_routed_grouped.lower(
        sharded, replicated, on((NQ, DIM), jnp.float32, False), R_KR, R_KR,
        N_PROBES, DistanceType.L2Expanded, "data", mesh, ng, "fused_codes",
        pq_bits=PQ_BITS, refine_to=R_K).compile().as_text()
    assert "tpu_custom_call" in text
    assert "all-gather" in text and "all-reduce" in text
