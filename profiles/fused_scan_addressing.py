"""Time the fused IVF-PQ recon list scan alone, per grid step.

    python3 profiles/fused_scan_addressing.py [--nq 64,640,2560,5000]
        [--groups 6909] [--cap 416] [--reps 10] [--cell-groups 0]
        [--live 0] [--kernel recon|codes]

Runs ``pq_group_scan_pallas.grouped_l2_scan_fused`` of the checkout it
is started from at the SIFT-1M IVF-PQ cell's kernel shape (4,096 lists
of ``--cap`` rows, rot 128, k = kt = 20, 72 probes) on synthetic lists,
or with ``--kernel codes`` ``pq_code_scan_pallas.grouped_code_scan_fused``
over lane-packed codes (``pq_dim`` 64 at 8 bits), the kernel the routed
cell's shards run (one shard: ``--lists 2049 --groups 4862``),
and prints one JSON line per nq: milliseconds per call,
microseconds per grid step, and a digest of the answers (values at every
rank, ids at every live rank, in query-major order) so two checkouts can
be compared for bit-identity.

Each nq in ``--nq`` runs at ``--groups`` grid steps with every slot
holding a real query (query ids cycle below nq, distinct in a group), so
only the batch width changes between lines.  Widths below 128 queries
cannot fill a group with distinct queries; they run at the shape the
search dispatches instead (``grouped.group_capacity`` groups built from
random probes).

``--live N`` also times each nq of at least 128 at the same ``--groups``
grid with only its first N groups live and the rest an all-empty tail,
laid out as ``grouped.build_groups`` lays it (the tail's slots empty,
its list the last list), and prints the microseconds per live step (from
the all-live call) and per empty step (what the tail adds over N live
steps, divided by the tail's length), with the digest of that call.

``--cell-groups N`` builds the cell's index from ``benchmark/data.py``
(seed 1), cuts up to N batches of 5,000 queries from the pool (10,000
rows: two batches) and prints how
many pair groups each needs (``grouped.num_groups``) against the static
capacity the search dispatches at.

``--tiny`` rehearses the whole script off the chip at a toy size in
Pallas interpret mode.
"""

from __future__ import annotations

import argparse
import functools
import hashlib
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from raft_tpu.neighbors import grouped  # noqa: E402
from raft_tpu.ops import pq_code_scan_pallas as pcs  # noqa: E402
from raft_tpu.ops import pq_group_scan_pallas as pgs  # noqa: E402

N_PROBES, ROT, K = 72, 128, 20
PQ_DIM, PQ_BITS = 64, 8


def lists(key, n_lists, cap, kernel):
    """(centers, list data..., norms, ids): the bf16 reconstructions, or
    the lane-packed codes and their codebooks."""
    kr, kc, ki = jax.random.split(key, 3)
    if kernel == "codes":
        codes = jax.random.randint(kr, (n_lists, cap, PQ_DIM), 0,
                                   1 << PQ_BITS).astype(jnp.uint8)
        books = jax.random.normal(kc, (PQ_DIM, 1 << PQ_BITS,
                                       ROT // PQ_DIM), jnp.float32)
        data = (pcs.pack_code_lanes(codes), books)
        # each row's norm is the sum of its codewords' norms
        norms = jnp.sum(books ** 2, axis=-1)           # (pq_dim, book)
        rsq = jnp.sum(norms[jnp.arange(PQ_DIM), codes.astype(jnp.int32)],
                      axis=-1)
    else:
        recon = jax.random.normal(kr, (n_lists, cap, ROT), jnp.bfloat16)
        data = (recon,)
        rsq = jnp.sum(recon.astype(jnp.float32) ** 2, axis=-1)
    ids = jax.random.randint(ki, (n_lists, cap), 0, 1 << 20, jnp.int32)
    # the allocator pads every list: the last eighth of each is empty
    ids = jnp.where(jnp.arange(cap)[None, :] >= cap - cap // 8, -1, ids)
    centers = jax.random.normal(kc, (n_lists, ROT), jnp.float32)
    return (centers, *data, rsq, ids)


def scan_fn(kernel, interpret):
    """The jitted fused scan of ``kernel``."""
    if kernel == "codes":
        call = functools.partial(pcs.grouped_code_scan_fused,
                                 pq_bits=PQ_BITS)
    else:
        call = pgs.grouped_l2_scan_fused
    return jax.jit(functools.partial(call, kt=K, k=K, n_probes=N_PROBES,
                                     interpret=interpret))


def full_groups(nq, n_groups, n_lists):
    """Every slot real: group g scans list g % n_lists with queries
    (g*128 + r) % nq, distinct within the group when nq >= 128."""
    g = np.arange(n_groups)[:, None]
    r = np.arange(grouped.GROUP)[None, :]
    q = (g * grouped.GROUP + r) % nq
    slots = (q * N_PROBES + g % N_PROBES).astype(np.int32)
    return (jnp.asarray((np.arange(n_groups) % n_lists).astype(np.int32)),
            jnp.asarray(slots))


def tail_groups(gl, sp, n_live, nq, n_lists):
    """The first ``n_live`` groups of ``(gl, sp)``; the rest empty slots
    of the last list, as ``grouped.build_groups`` pads its tail."""
    live = jnp.arange(gl.shape[0]) < n_live
    return (jnp.where(live, gl, n_lists - 1),
            jnp.where(live[:, None], sp, nq * N_PROBES))


def probe_groups(rng, nq, n_lists):
    probes = np.stack([rng.choice(n_lists, N_PROBES, replace=False)
                       for _ in range(nq)]).astype(np.int32)
    ng, _ = grouped.group_capacity(nq, N_PROBES, n_lists)
    return grouped.build_groups(jnp.asarray(probes), n_lists, ng)


def digest(v, i, nq):
    """Query-major (nq, k) answers, whatever layout the kernel emits."""
    v, i = np.asarray(v), np.asarray(i)
    if v.shape[0] == K and v.shape[1] != K:
        v, i = v.T, i.T
    v, i = v[:nq, :K], i[:nq, :K]
    i = np.where(v < pgs._ACC_WORST / 2, i, -1)
    return hashlib.sha256(v.tobytes() + i.tobytes()).hexdigest()[:16]


def time_call(fn, args, reps):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps, out


def cell_groups(n_batches):
    from benchmark import data
    from raft_tpu import DeviceResources
    from raft_tpu.neighbors import ivf_pq

    conf = json.loads((ROOT / "benchmark/configs/sift1m-ivfpq.json")
                      .read_text())
    db, pool = data.make(1, conf["dataset"])
    res = DeviceResources()
    index = ivf_pq.build(res, ivf_pq.IndexParams(**conf["index"]["build"]),
                         db)
    n_probes = conf["index"]["search"]["n_probes"]
    for b in range(min(n_batches, pool.shape[0] // 5000)):
        q = pool[b * 5000:(b + 1) * 5000]
        probes = ivf_pq._select_clusters(index.centers, index.rotation, q,
                                         n_probes, index.metric)
        cap_groups, _ = grouped.group_capacity(q.shape[0], n_probes,
                                               index.n_lists)
        print(json.dumps({
            "batch": b, "num_groups": int(grouped.num_groups(
                probes, index.n_lists)),
            "capacity": cap_groups, "list_cap": int(index.capacity)}),
            flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--nq", default="64,640,2560,5000")
    ap.add_argument("--groups", type=int, default=6909)
    ap.add_argument("--cap", type=int, default=416)
    ap.add_argument("--lists", type=int, default=4096)
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--cell-groups", type=int, default=0)
    ap.add_argument("--live", type=int, default=0)
    ap.add_argument("--kernel", choices=("recon", "codes"), default="recon")
    ap.add_argument("--tiny", action="store_true")
    a = ap.parse_args()
    if a.tiny:
        a.groups, a.cap, a.lists, a.reps = 80, 32, 128, 1
    dev = jax.devices()[0]
    interpret = dev.platform != "tpu"
    if interpret and not a.tiny:
        raise SystemExit("needs a TPU (or --tiny to rehearse)")
    data = lists(jax.random.PRNGKey(0), a.lists, a.cap, a.kernel)
    fn = scan_fn(a.kernel, interpret)
    rng = np.random.default_rng(0)
    for nq in (int(x) for x in a.nq.split(",")):
        if nq >= grouped.GROUP:
            gl, sp = full_groups(nq, a.groups, a.lists)
        else:
            gl, sp = probe_groups(rng, nq, a.lists)
        qrot = jax.random.normal(jax.random.PRNGKey(nq), (nq, ROT),
                                 jnp.float32)
        args = (gl, sp, qrot, *data)
        sec, (v, i) = time_call(fn, args, a.reps)
        ng = int(gl.shape[0])
        print(json.dumps({
            "kernel": a.kernel, "nq": nq, "n_groups": ng,
            "ms_per_call": sec * 1e3, "us_per_step": sec * 1e6 / ng,
            "digest": digest(v, i, nq),
            "device": dev.device_kind}), flush=True)
        if a.live and nq >= grouped.GROUP:
            n_live = min(a.live, ng)
            lsec, (v, i) = time_call(
                fn, (*tail_groups(gl, sp, n_live, nq, a.lists), *args[2:]),
                a.reps)
            live_us = sec * 1e6 / ng
            print(json.dumps({
                "kernel": a.kernel, "nq": nq, "n_groups": ng,
                "live": n_live,
                "ms_per_call": lsec * 1e3, "us_per_live_step": live_us,
                "us_per_empty_step": (
                    (lsec * 1e6 - n_live * live_us) / max(ng - n_live, 1)),
                "digest": digest(v, i, nq),
                "device": dev.device_kind}), flush=True)
    if a.cell_groups:
        cell_groups(a.cell_groups)


if __name__ == "__main__":
    main()
