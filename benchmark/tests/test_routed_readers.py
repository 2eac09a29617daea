"""The routed cell's readers and a CPU rehearsal of the cell itself."""

import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from benchmark import loops, run, trace_reduce
from benchmark.tests.small import PEAK, SEED

# op names recorded from the routed cell's trace on four TPU v5e chips
# (seed 2147489002), cut after the opcode's operands
CHIP_COLLECTIVES = (
    "%psum.7 = f32[5000,20]{1,0:T(8,128)S(1)} all-reduce(f32[5000,20]"
    "{1,0:T(8,128)S(1)} %select_select_fusion), channel_id=1, "
    "replica_groups={{0,1,2,3}}",
    "%all-gather.13 = s32[4,5000,20]{1,2,0:T(8,128)S(1)} all-gather("
    "s32[1,5000,20]{1,2,0:T(8,128)S(1)} %copy.47), channel_id=3",
    "%all-gather.12 = f32[4,5000,20]{1,2,0:T(8,128)S(1)} all-gather("
    "f32[1,5000,20]{1,2,0:T(8,128)S(1)} %copy.45), channel_id=2",
    "%all-reduce.7 = (s32[4]{0:T(128)}, s32[4]{0:T(128)}) all-reduce("
    "s32[4]{0:T(128)S(1)} %dynamic-update-slice.5",
)
# ops of the same trace that are not collectives, some naming one as an
# operand
NOT_COLLECTIVES = (
    "%grouped_code_scan_fused.1 = (f32[5120,128]{1,0:T(8,128)S(1)}, "
    "f32[5120,128]{1,0:T(8,128)S(1)}) custom-call(s32[4862]",
    "%neg.28 = f32[5000,20]{1,0:T(8,128)S(1)} negate(f32[5000,20]"
    "{1,0:T(8,128)S(1)} %psum.7)",
    "%multiply_reduce_fusion = (f32[5000,20]{1,0:T(8,128)S(1)}, "
    "f32[5000,20]{1,0:T(8,128)S(1)}) fusion(f32[5000,20,128]",
    "%reduce-window.36 = s32[38,128]{1,0:T(8,128)S(1)} reduce-window(",
    "%approx_top_k.86 = (f32[5000,2048]{1,0:T(8,128)}, s32[5000,2048]"
    "{1,0:T(8,128)}) sort(",
)


def _summary(n_devices, kernel_s, busy_s=1.0, ops=None, gaps=None):
    ops = dict(ops or {})
    ops["%grouped_code_scan_fused.1 = (f32[5120,128]"] = kernel_s
    return trace_reduce.Summary(window_s=2.0, busy_s=busy_s,
                                n_devices=n_devices, ops=ops, modules={},
                                gaps=dict(gaps or {}))


def _ctx(summary):
    rng = np.random.default_rng(3)
    pool = rng.normal(size=(64, 16)).astype(np.float32)
    lay = {"centers": rng.normal(size=(8, 16)),
           "rotation": np.eye(16), "list_sizes": rng.integers(5, 40, 8),
           "dim": 16, "code_bytes": 8, "n_probes": 3, "n_devices": 4}
    win = loops.Window(seconds=1.0, rows=np.arange(64), ids=None,
                       dists=None, batches=[np.arange(32), np.arange(32, 64)],
                       attempted=64, failed=0, lost=0, metrics={}, notes={})
    return {"trace": summary, "layout": lay, "window": win, "pool": pool,
            "peak": PEAK, "notes": {}}


def test_routed_roofline_counts_every_chip():
    # the same per-device kernel time on four devices is four chips' time
    one = run.read_metric("scan_roofline", _ctx(_summary(1, 0.5)))
    four = run.read_metric("routed.scan_roofline", _ctx(_summary(4, 0.5)))
    assert one > 0
    assert four == pytest.approx(one / 4, rel=1e-12)
    assert run.read_metric("routed.scan_roofline",
                           _ctx(_summary(4, 0.0))) is None


@pytest.mark.parametrize("name", CHIP_COLLECTIVES)
def test_exchange_patterns_match_the_chip_collectives(name):
    others = {n: 0.125 for n in NOT_COLLECTIVES}
    s = _summary(4, 0.25, busy_s=1.0, ops={name: 0.0625, **others})
    got = run.read_metric("routed.exchange.busy_share", _ctx(s))
    assert got == pytest.approx(6.25)


def test_exchange_reads_nothing_without_collectives():
    s = _summary(4, 0.25, ops={n: 0.125 for n in NOT_COLLECTIVES})
    assert run.read_metric("routed.exchange.busy_share", _ctx(s)) is None


def test_idle_readers():
    s = _summary(4, 0.25, busy_s=1.5,
                 gaps={"bench.search": 0.25, "bench.readback": 0.25})
    ctx = _ctx(s)
    assert run.read_metric("device.idle_share.routed", ctx) == \
        pytest.approx(25.0)
    assert run.read_metric("device.idle_in_search.routed", ctx) == \
        pytest.approx(12.5)
    assert run.read_metric("device.idle_share.routed",
                           {"trace": None}) is None


REHEARSAL = textwrap.dedent("""
    import json, sys, time
    sys.path.insert(0, sys.argv[1])
    import jax
    from benchmark import run
    from benchmark.tests.small import PEAK, SEED
    run.check_kernels = lambda spies, n: {}
    run.fallback_events = lambda system: 0
    cell = run.cell_spec("sift1m-ivfpq-routed.batch5k")
    cfg, mix = cell["config"], cell["traffic"]
    cfg["dataset"].update(n_db=3000, n_queries=256, dim=32, latent_dim=8)
    cfg["index"]["build"] = {"n_lists": 16, "pq_dim": 16,
                             "kmeans_n_iters": 5}
    cfg["index"]["search"].update(n_probes=6)
    mix["batch"] = 100
    out = run.run_cell(cell, SEED, 1.0, bool(int(sys.argv[2])), PEAK,
                       time.perf_counter())
    print(json.dumps({"devices": len(jax.devices()), **out}, default=float))
""")


@pytest.mark.parametrize("trace", [0, 1])
def test_routed_cell_rehearsal(trace):
    """The cell end to end on four virtual CPU devices (a process of its
    own: the device count is fixed when JAX starts)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run(
        [sys.executable, "-c", REHEARSAL, str(run.ROOT), str(trace)],
        env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["devices"] == 4
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["checks"]["dist_gap"]["value"] < 5e-5
    if not trace:
        assert set(out["metrics"]) == {"qps", "recall_at_10", "setup_s"}
    else:
        # no device plane on the CPU: the device readers find nothing
        assert out["metrics"] == {} and "breakdown" in out
