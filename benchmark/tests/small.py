"""Small cells for the CPU tests: each cell's configuration and mix from
its files, cut to a size a test run holds."""

CELLS = ("sift1m-ivfpq.batch5k", "sift1m-cagra.online")
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
SEED = 2**31 + 12345


def small(cell):
    cfg, mix = cell["config"], cell["traffic"]
    cfg["dataset"].update(n_db=3000, n_queries=256, dim=32, latent_dim=8)
    if cfg["index"]["kind"] == "ivf_pq":
        cfg["index"]["build"] = {"n_lists": 16, "pq_dim": 16,
                                 "kmeans_n_iters": 5}
        cfg["index"]["search"] = {"n_probes": 6}
    else:
        cfg["index"]["build"] = {"graph_degree": 16,
                                 "intermediate_graph_degree": 32}
    if mix["loop"] == "closed":
        mix["batch"] = 100
    else:
        mix.update(rate_rows_per_s=400, max_batch=16)
        mix["rows"]["max"] = 16
    return cell
