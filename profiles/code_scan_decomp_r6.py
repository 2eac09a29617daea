"""Round-6 A/B decomposition: where the IVF-PQ scan's HBM bytes go.

Two parts:

- ``--model`` (runs anywhere, CPU included): the static per-candidate-row
  HBM traffic of each scan mode at the bench shape — the acceptance
  number for the compact-code path (codes bytes/row must be < half the
  recon path's) — plus the per-batch totals implied by the measured
  group count.
- on-chip timing (default): kernel-only A/B of recon vs codes (and the
  fused kernels) at matched (n_probes, kt), isolating the scan from
  coarse select and refine; --trace captures a profiler trace of both.

Run on the real chip:  python profiles/code_scan_decomp_r6.py [--trace]
Traffic model only:    python profiles/code_scan_decomp_r6.py --model
"""

import sys
import time

import numpy as np


def traffic_model(cap, rot, pq_dim, pq_bits, n_groups, group=128):
    from raft_tpu.neighbors import grouped

    per_row = grouped.scan_traffic(rot, pq_dim, pq_bits)
    print(f"per-candidate-row HBM bytes (rot={rot}, pq_dim={pq_dim}, "
          f"pq_bits={pq_bits}):")
    for mode in ("recon", "codes", "fused"):
        b = per_row[mode]
        ratio = b / per_row["recon"]
        print(f"  {mode:>7}: {b:4d} B/row  ({ratio:.2f}x recon)")
    assert per_row["codes"] < per_row["recon"] / 2, (
        "codes bytes/row must undercut half the recon path's")
    print(f"per-batch scan totals at n_groups={n_groups}, cap={cap} "
          f"(each group streams its list's rows once):")
    for mode in ("recon", "codes", "fused"):
        total = n_groups * cap * per_row[mode]
        print(f"  {mode:>7}: {total / 1e9:6.2f} GB")
    return per_row


def output_model(kt, k, nq, n_probes, n_groups, group=128):
    """Round-7 columns: the OUTPUT side of the scan — what the fused
    in-kernel top-k eliminates.  The split path writes a (dist, id) pair
    per kept candidate per (query, probe) pair, then re-reads it through
    scatter + select; fused mode keeps the running top-k in VMEM scratch
    and writes one (k, nq) answer pair for the whole batch."""
    from raft_tpu.neighbors import grouped

    per_pair = grouped.pair_output_traffic(kt)
    n_pairs = nq * n_probes
    split_total = n_pairs * per_pair
    fused_total = 2 * 4 * k * nq            # final (vals, ids), f32
    print(f"extraction/output traffic at kt={kt}, k={k}, nq={nq}, "
          f"n_probes={n_probes}:")
    print(f"  split: {per_pair} B/pair x {n_pairs} pairs = "
          f"{split_total / 1e6:7.1f} MB  (+ scatter/select passes)")
    print(f"  fused: one ({k}, {nq}) answer pair     = "
          f"{fused_total / 1e6:7.1f} MB")
    print(f"  predicted elimination: {split_total / fused_total:6.1f}x "
          "output bytes, extraction stage -> 0 (in-kernel)")
    # round-5 extraction cost model: ~3.3 us per kept candidate per
    # group of pairs — the wall-clock the fused kernel absorbs
    pair_groups = -(-n_pairs // group)
    print(f"  predicted extraction wall-clock absorbed: "
          f"~{3.3e-6 * kt * pair_groups * 1e3:.1f} ms/batch")
    return split_total, fused_total


def main():
    import jax

    sys.path.insert(0, ".")
    import bench
    from raft_tpu import DeviceResources
    from raft_tpu.neighbors import grouped, ivf_pq

    model_only = "--model" in sys.argv
    if model_only:
        # bench-shape geometry without building: cap from the mean list
        # occupancy rounded like the list allocator
        n_db, n_lists, pq_dim, pq_bits, rot = 1_000_000, 4096, 64, 8, 128
        cap = -(-int(n_db / n_lists * 1.35) // 32) * 32
        n_groups = 23_000   # measured round-5 magnitude at n_probes=96
        traffic_model(cap, rot, pq_dim, pq_bits, n_groups)
        output_model(kt=4, k=10, nq=5_000, n_probes=96,
                     n_groups=n_groups)
        return

    bench._setup_jax_cache()
    res = DeviceResources(seed=0)
    db, queries = bench._make_dataset({"n_db": 1_000_000, "dim": 128,
                                       "latent_dim": 16, "noise": 0.05,
                                       "n_queries": 5_000})
    params = ivf_pq.IndexParams(n_lists=4096, pq_dim=64, kmeans_n_iters=20)
    t0 = time.perf_counter()
    index = ivf_pq.build(res, params, db)
    jax.block_until_ready(index.list_codes)
    print("build_s", round(time.perf_counter() - t0, 1))

    n_probes, k, kt = 72, 20, 4
    m = index.metric
    probes = ivf_pq._select_clusters(index.centers, index.rotation,
                                     queries, n_probes, m)
    n_groups = grouped.round_groups(
        int(grouped.num_groups(probes, index.n_lists)))
    cap = index.capacity
    G, rot = grouped.GROUP, index.rot_dim
    block = grouped.block_size(n_groups, G * cap * 8, cap * rot * 2,
                               G * rot * 4)
    print("n_groups", n_groups, "cap", cap)
    traffic_model(cap, rot, index.pq_dim, index.pq_bits, n_groups)

    index = ivf_pq._with_recon(res, index)
    index = ivf_pq._with_code_lanes(index)

    def run_recon(kt_):
        return ivf_pq._search_impl_recon_grouped(
            index.centers, index.list_recon, index.list_recon_sq,
            index.list_indices, index.rotation, queries, probes, k, m,
            n_groups, block, use_pallas=True, kt=kt_)[1]

    def run_codes(kt_):
        return ivf_pq._search_impl_codes_grouped(
            index.centers, index.codebooks, index.list_code_lanes,
            index.list_code_rsq, index.list_indices, index.rotation,
            queries, probes, k, kt_, m, n_groups, index.pq_bits)[1]

    def run_fused_codes(kt_):
        return ivf_pq._search_impl_fused_codes_grouped(
            index.centers, index.codebooks, index.list_code_lanes,
            index.list_code_rsq, index.list_indices, index.rotation,
            queries, probes, k, kt_, m, n_groups, index.pq_bits)[1]

    def run_fused_recon(kt_):
        return ivf_pq._search_impl_fused_recon_grouped(
            index.centers, index.list_recon, index.list_recon_sq,
            index.list_indices, index.rotation, queries, probes, k, kt_,
            m, n_groups)[1]

    variants = [
        ("recon      kt=k ", lambda: run_recon(0)),
        (f"recon      kt={kt} ", lambda: run_recon(kt)),
        ("codes      kt=k ", lambda: run_codes(0)),
        (f"codes      kt={kt} ", lambda: run_codes(kt)),
        # round-7: scan + top-k in ONE kernel, no extraction stage
        (f"fused-cod  kt={kt} ", lambda: run_fused_codes(kt)),
        (f"fused-rec  kt={kt} ", lambda: run_fused_recon(kt)),
    ]
    timed = {}
    for name, fn in variants:
        i = fn()
        np.asarray(i)                    # warm
        t0 = time.perf_counter()
        for _ in range(3):
            i = fn()
        np.asarray(i)
        dt = (time.perf_counter() - t0) / 3
        timed[name.strip()] = dt
        print(f"{name}: {dt*1000:7.1f} ms/batch  ({5000/dt:7.0f} qps)")

    # measured extraction-stage elimination: the codes-vs-fused delta at
    # matched kt IS the (extraction + scatter + select) stage the fused
    # kernel absorbed — print it beside the static model's prediction
    split = timed[f"codes      kt={kt}".strip()]
    fused = timed[f"fused-cod  kt={kt}".strip()]
    print(f"measured extraction elimination (codes kt={kt} -> fused): "
          f"{(split - fused) * 1e3:+.1f} ms/batch "
          f"({split / fused:.2f}x)")
    output_model(kt=kt, k=k, nq=queries.shape[0], n_probes=n_probes,
                 n_groups=n_groups)

    if "--trace" in sys.argv:
        with jax.profiler.trace("profiles/code_scan_trace"):
            np.asarray(run_recon(kt))
            np.asarray(run_codes(kt))
        print("trace written to profiles/code_scan_trace")


if __name__ == "__main__":
    main()
