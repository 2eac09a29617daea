"""Bring-up smoke on the chip: the library's main path, end to end, once.

One process, one chip, public API only, at the flagship width — the
SIFT-like 1M x 128 set of ``conf/sift-like-1m.json``, made from its seed:

1. IVF-PQ (4096 lists, pq_dim 64, ``scan_mode="auto"``) is built,
   serialized and loaded back;
2. a ``raft_tpu.serving.Server`` warms its buckets and answers a few
   hundred query rows (k = 20, refined to 10 — bench.py's 0.95 operating
   point, 72 probes x refine ratio 2); recall@10 against
   ``brute_force.knn`` must reach 0.95, with zero recompiles after
   warm-up and the fused in-kernel top-k serving every batch;
3. at full probe the fused search must equal the non-fused
   ``scan_mode="recon"`` kernel over the same bf16 cache (ids exact), and
   agree with the ``scan_mode="lut"`` twin up to the cache's rounding;
4. CAGRA (degree 32, itopk 32) must walk on its fused hop kernel and
   reach the recall of the XLA hop on the same graph, and a k-means fit
   (k = 1024) must reach its fused Pallas kernel.

``--chips 4`` runs only the routed index instead: lists placed
``by_list`` over a 4-device mesh, a fused routed search at full probe
equal to a single-chip search of the same index, and each shard's lists
on its own device.

Every phase prints one JSON line; the last line is
``{"ok": true, "device": {...}}``.  A failed check exits non-zero.  The
script refuses to run anywhere but on a TPU.
"""

from __future__ import annotations

import argparse
import io
import json
import sys
import time
from contextlib import contextmanager

import numpy as np

CONF = "conf/sift-like-1m.json"

# the flagship's shapes
N_DB = 1_000_000
N_LISTS, PQ_DIM, KMEANS_N_ITERS = 4096, 64, 20
K, REFINE_RATIO, N_PROBES = 10, 2, 72
MAX_BATCH, SERVE_ROWS = 256, 512
PROBE_ROWS = 64                  # full-probe and CAGRA query batches
CAGRA_DEGREE, CAGRA_ITOPK = 32, 32
KMEANS_K, KMEANS_ITERS = 1024, 10
RECALL_FLOOR = 0.95
# CAGRA at degree 32 / itopk 32 on this set measured recall@10 0.886 on
# the chip (PR 21): a graph build that lost much of its quality lands
# below this
CAGRA_GRAPH_FLOOR = 0.85


class Failed(Exception):
    pass


def log(**kv) -> None:
    print(json.dumps(kv, default=float), flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise Failed(what)


def peak_bytes():
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


@contextmanager
def phase(name: str, out: dict):
    """Time one phase; ``out`` collects the numbers it reports."""
    t0 = time.perf_counter()
    yield out
    log(phase=name, wall_s=time.perf_counter() - t0,
        peak_bytes_in_use=peak_bytes(), **out)


class KernelSpy:
    """Count how often the library traces a Pallas kernel entry point:
    a traced call is a kernel inside the compiled search program."""

    def __init__(self, module, name: str):
        self.module, self.name, self.calls = module, name, 0
        self._orig = getattr(module, name)

        def wrapped(*a, **kw):
            self.calls += 1
            return self._orig(*a, **kw)
        setattr(module, name, wrapped)


def recall(found, truth) -> float:
    found, truth = np.asarray(found), np.asarray(truth)
    k = truth.shape[1]
    return float(np.mean([len(set(f[:k]) & set(t)) / k
                          for f, t in zip(found, truth)]))


def same_up_to_ties(ia, da, ib, db, rtol=1e-6) -> dict:
    """Ids equal at every rank, except where the two answers hold
    distances tied within ``rtol`` (tie order is unspecified)."""
    ia, ib = np.asarray(ia), np.asarray(ib)
    da, db = np.asarray(da), np.asarray(db)
    diff = ia != ib
    tied = np.isclose(da, db, rtol=rtol, atol=0.0)
    return {"ranks_differ": int(diff.sum()),
            "ranks_differ_untied": int((diff & ~tied).sum()),
            "max_rel_dist_diff": float(np.max(
                np.abs(da - db) / np.maximum(np.abs(db), 1e-30)))}


def fused_counter() -> int:
    from raft_tpu import observability as obs
    return int(obs.snapshot()["counters"].get(
        "ivf_pq.search.fused_fallback", 0))


def make_data():
    from bench import _make_dataset
    with open(CONF) as f:
        ds = dict(json.load(f)["dataset"], n_db=N_DB)
    return _make_dataset(ds)


def run_single() -> None:
    import jax
    import jax.numpy as jnp

    from raft_tpu import DeviceResources, serving
    from raft_tpu import observability as obs
    from raft_tpu.cluster import kmeans
    from raft_tpu.cluster.kmeans_types import InitMethod, KMeansParams
    from raft_tpu.neighbors import brute_force, cagra, ivf_pq
    from raft_tpu.neighbors.refine import refine
    from raft_tpu.ops import cagra_hop_pallas as chp
    from raft_tpu.ops import kmeans_update_pallas as kup
    from raft_tpu.ops import pq_code_scan_pallas as pcs
    from raft_tpu.ops import pq_group_scan_pallas as pgs

    spies = {"ivf_pq": [KernelSpy(pgs, "grouped_l2_scan_fused"),
                        KernelSpy(pcs, "grouped_code_scan_fused")],
             "recon": [KernelSpy(pgs, "grouped_l2_scan")],
             "cagra": [KernelSpy(chp, "fused_hop")],
             "kmeans": [KernelSpy(kup, "fused_assign_update")]}

    def reached(name):
        return sum(s.calls for s in spies[name])

    res = DeviceResources(seed=0)
    obs.enable()
    with phase("data", {}) as out:
        db, queries = make_data()
        q = queries[:SERVE_ROWS]
        _, gt = brute_force.knn(res, db, q, K)
        gt = np.asarray(gt)
        out.update(n_db=int(db.shape[0]), dim=int(db.shape[1]),
                   n_queries=int(q.shape[0]))

    with phase("ivf_pq_build", {}) as out:
        built = ivf_pq.build(res, ivf_pq.IndexParams(
            n_lists=N_LISTS, pq_dim=PQ_DIM,
            kmeans_n_iters=KMEANS_N_ITERS), db)
        buf = io.BytesIO()
        ivf_pq.serialize(res, buf, built)
        buf.seek(0)
        index = ivf_pq.deserialize(res, buf)
        for name in ("centers", "list_indices", "list_codes", "rotation"):
            check(np.array_equal(np.asarray(getattr(index, name)),
                                 np.asarray(getattr(built, name))),
                  f"ivf_pq: {name} changed across serialize/deserialize")
        out.update(serialized_bytes=buf.getbuffer().nbytes,
                   capacity=int(index.capacity))
        del built

    kk = K * REFINE_RATIO
    sp = ivf_pq.SearchParams(n_probes=N_PROBES)
    with phase("serve", {}) as out:
        ex = serving.Executor(res, "ivf_pq", index, ks=(kk,),
                              max_batch=MAX_BATCH, search_params=sp,
                              warm="jit")
        srv = serving.Server(ex, serving.ServerConfig(max_batch=MAX_BATCH))
        t0 = time.perf_counter()
        srv.start()
        out["warmup_s"] = time.perf_counter() - t0
        try:
            qh = np.asarray(q)
            # settle one-time host-side compiles (transfers, masks) at
            # each request size before the counted window
            sizes = [MAX_BATCH, MAX_BATCH // 4]
            for m in sizes:
                srv.search(qh[:m], kk)
            compiles0 = obs.snapshot()["counters"].get("xla.compiles", 0)
            ids, t0 = [], time.perf_counter()
            # one max_batch request, then quarter-size ones: two buckets
            ids.append(srv.submit(qh[:MAX_BATCH], kk).result()[1])
            step = MAX_BATCH // 4
            for s in range(MAX_BATCH, SERVE_ROWS, step):
                ids.append(srv.submit(qh[s:s + step], kk).result()[1])
            out["serve_s"] = time.perf_counter() - t0
            compiles = (obs.snapshot()["counters"].get("xla.compiles", 0)
                        - compiles0)
        finally:
            srv.stop()
        cand = jnp.asarray(np.concatenate([np.asarray(i) for i in ids]))
        _, ref_i = refine(res, db, q, cand, K)
        out.update(buckets=sorted(set(sizes)), recompiles=compiles,
                   recall_at_10=recall(ref_i, gt),
                   unrefined_recall_at_10=recall(np.asarray(cand)[:, :K],
                                                 gt),
                   fused_kernel_traces=reached("ivf_pq"),
                   fused_fallback=fused_counter())
    check(out["recompiles"] == 0, f"serve: {compiles} recompiles after warm-up")
    check(out["recall_at_10"] >= RECALL_FLOOR,
          f"serve: recall@10 {out['recall_at_10']} < {RECALL_FLOOR}")
    check(out["fused_kernel_traces"] > 0,
          "serve: the fused in-kernel top-k never ran")

    with phase("full_probe_fused_vs_recon_vs_lut", {}) as out:
        qp = q[:PROBE_ROWS]
        full = dict(n_probes=index.n_lists, exact_coarse=True)
        fd, fi = ivf_pq.search(res, ivf_pq.SearchParams(**full), index, qp, K)
        rd, ri = ivf_pq.search(res, ivf_pq.SearchParams(
            scan_mode="recon", **full), index, qp, K)
        ld, li = ivf_pq.search(res, ivf_pq.SearchParams(
            scan_mode="lut", **full), index, qp, K)
        # fused and non-fused recon read the same bf16 cache through the
        # same distance formula: ids equal except among exactly tied
        # distances.  The LUT reads the f32 codebooks, so it agrees only
        # up to the cache's bf16 rounding
        vs_recon = same_up_to_ties(fi, fd, ri, rd, rtol=0.0)
        vs_lut = same_up_to_ties(fi, fd, li, ld, rtol=3e-2)
        out.update(vs_recon=vs_recon, vs_lut=vs_lut,
                   lut_id_overlap=recall(fi, li),
                   recon_kernel_traces=reached("recon"))
    check(np.isfinite(np.asarray(fd)).all(), "full probe: non-finite")
    check(out["recon_kernel_traces"] > 0,
          "full probe: the non-fused recon kernel never ran")
    check(vs_recon["ranks_differ_untied"] == 0
          and vs_recon["max_rel_dist_diff"] <= 1e-4,
          f"full probe: fused and recon kernels disagree: {vs_recon}")
    check(vs_lut["ranks_differ_untied"] == 0,
          "full probe: fused and lut disagree beyond bf16 rounding")

    with phase("cagra", {}) as out:
        cidx = cagra.build(res, cagra.IndexParams(
            graph_degree=CAGRA_DEGREE,
            intermediate_graph_degree=2 * CAGRA_DEGREE), db)
        csp = cagra.SearchParams(itopk_size=CAGRA_ITOPK)
        qc = q[:PROBE_ROWS]
        cd, ci = cagra.search(res, csp, cidx, qc, K)
        fused_traces = reached("cagra")
        # the reference walk: past the fused hop's 64-row batch ceiling
        # the same graph is walked by the XLA hop; rows walk alone, so
        # its first PROBE_ROWS rows are the same queries' answers
        _, ti = cagra.search(res, csp, cidx, q[:2 * PROBE_ROWS], K)
        ti = np.asarray(ti)[:PROBE_ROWS]
        out.update(recall_at_10=recall(ci, gt[:PROBE_ROWS]),
                   xla_hop_recall_at_10=recall(ti, gt[:PROBE_ROWS]),
                   id_overlap_with_xla_hop=recall(ci, ti),
                   fused_kernel_traces=fused_traces,
                   xla_hop_ran=reached("cagra") == fused_traces)
    check(np.isfinite(np.asarray(cd)).all(), "cagra: non-finite distances")
    check(out["fused_kernel_traces"] > 0, "cagra: the fused hop never ran")
    check(out["xla_hop_ran"], "cagra: the reference walk took the fused hop")
    # tests/test_cagra.py holds the fused walk to >= 0.9 id overlap with
    # the XLA walk (hop-local tie order differs, so walks may part)
    check(out["id_overlap_with_xla_hop"] >= 0.9,
          f"cagra: fused and XLA walks overlap {out['id_overlap_with_xla_hop']}")
    check(out["recall_at_10"] >= out["xla_hop_recall_at_10"] - 0.03,
          f"cagra: fused recall {out['recall_at_10']} below the XLA walk's "
          f"{out['xla_hop_recall_at_10']}")
    check(out["xla_hop_recall_at_10"] >= CAGRA_GRAPH_FLOOR,
          f"cagra: XLA-walk recall {out['xla_hop_recall_at_10']} < "
          f"{CAGRA_GRAPH_FLOOR}: the graph itself is off")

    with phase("kmeans", {}) as out:
        # Lloyd never raises the inertia: more fused passes from the same
        # random init must land lower than one pass
        fits = {}
        for iters in (1, KMEANS_ITERS):
            c, inertia, n_iter = kmeans.fit(res, KMeansParams(
                n_clusters=KMEANS_K, max_iter=iters, tol=0.0,
                init=InitMethod.Random), db)
            fits[iters] = float(inertia)
        out.update(inertia_one_pass=fits[1], inertia=fits[KMEANS_ITERS],
                   n_iter=int(n_iter), fused_kernel_traces=reached("kmeans"))
    check(np.isfinite(np.asarray(c)).all(), "kmeans: non-finite centroids")
    check(out["fused_kernel_traces"] > 0, "kmeans: the fused pass never ran")
    check(out["inertia"] < out["inertia_one_pass"],
          f"kmeans: inertia after {n_iter} passes is not below one pass")
    check(fused_counter() == 0,
          f"ivf_pq.search.fused_fallback = {fused_counter()}")


def run_routed(n_chips: int) -> None:
    import jax
    from jax.sharding import Mesh

    from raft_tpu import observability as obs
    from raft_tpu.comms import CommsSession
    from raft_tpu.distributed import ann as dist_ann
    from raft_tpu.neighbors import ivf_pq
    from raft_tpu.ops import pq_code_scan_pallas as pcs
    from raft_tpu.ops import pq_group_scan_pallas as pgs

    spies = [KernelSpy(pgs, "grouped_l2_scan_fused"),
             KernelSpy(pcs, "grouped_code_scan_fused")]
    devs = jax.devices()[:n_chips]
    session = CommsSession(mesh=Mesh(np.asarray(devs), ("data",)),
                           axis_name="data").init()
    obs.enable()
    try:
        handle = session.worker_handle(seed=0)
        with phase("data", {}) as out:
            db, queries = make_data()
            q = queries[:PROBE_ROWS]
            out.update(n_db=int(db.shape[0]), dim=int(db.shape[1]))
        with phase("routed_build", {}) as out:
            # build(..., placement="by_list") is ivf_pq.build followed by
            # shard_by_list; the two calls keep the single-chip index
            # for the comparison below
            base = ivf_pq.build(handle, ivf_pq.IndexParams(
                n_lists=N_LISTS, pq_dim=PQ_DIM,
                kmeans_n_iters=KMEANS_N_ITERS), db)
            routed = dist_ann.shard_by_list(handle, base)
            homes = {}
            for leaf in ("list_recon", "list_indices"):
                arr = getattr(routed, leaf)
                homes[leaf] = sorted(
                    (s.index[0].start or 0, s.device.id)
                    for s in arr.addressable_shards)
            out.update(shard_homes=homes["list_indices"],
                       lists_per_shard=int(routed.list_indices.shape[1]))
        for leaf, h in homes.items():
            check([d for _, d in h] == sorted(d.id for d in devs)
                  and [i for i, _ in h] == list(range(n_chips)),
                  f"routed: {leaf} shards are not one per device: {h}")

        with phase("routed_fused_vs_single_chip", {}) as out:
            sp = ivf_pq.SearchParams(n_probes=N_LISTS, scan_mode="fused")
            rd, ri, status = dist_ann.search(handle, sp, routed, q, K,
                                             return_status=True)
            sd, si = ivf_pq.search(handle, sp, base, q, K)
            out.update(same_up_to_ties(ri, rd, si, sd, rtol=1e-5),
                       status=np.asarray(status).tolist(),
                       fused_kernel_traces=sum(s.calls for s in spies),
                       fused_fallback=fused_counter())
        check(np.isfinite(np.asarray(rd)).all(), "routed: non-finite")
        check(max(out["status"]) <= dist_ann.SHARD_OK,
              f"routed: shards lowered or failed: {out['status']}")
        check(out["ranks_differ_untied"] == 0,
              "routed: ids differ from the single-chip search")
        check(out["fused_kernel_traces"] > 0, "routed: fused scan never ran")
        check(out["fused_fallback"] == 0, "routed: fused fallback counted")
    finally:
        session.destroy()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4 runs only the routed index over a 4-chip mesh")
    args = ap.parse_args(argv)

    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < args.chips:
        print(f"chip_smoke: needs {args.chips} TPU chip(s); JAX found "
              f"{len(devs)} {devs[0].platform} device(s)", file=sys.stderr)
        return 2

    from raft_tpu.core.platform import setup_compile_cache
    log(compile_cache=setup_compile_cache())
    try:
        if args.chips == 1:
            run_single()
        else:
            run_routed(args.chips)
    except Failed as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
