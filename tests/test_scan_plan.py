"""The IVF-PQ scan planner (``ivf_pq.plan_scan``): which list-scan
formulation each placement runs for each scan mode, inside and outside
the fused kernels' gates, and the cells' own plans.

The planner is host-static, so every case asks it as a TPU would
(``on_tpu=True``, f32-exact ids) without a chip or an index."""

import pytest

from raft_tpu import observability as obs
from raft_tpu.core.error import LogicError
from raft_tpu.distance.types import DistanceType
from raft_tpu.neighbors import grouped, ivf_pq

MODES = ("auto", "codes", "recon", "lut", "fused")

# one 1M x 128 index, pq_dim 64 at 8 bits, 4,096 lists of 416 rows, seen
# whole (single), as one routed shard's 2,049 slots with its code lanes
# (by_list), and as one stacked shard of 1,024 lists (by_row)
PLACEMENTS = {
    "single": dict(lists=4096),
    "by_list": dict(lists=2049, code_lanes=True),
    "by_row": dict(lists=1024),
}

# (nq, k, per_probe_topk): inside every gate; k past the fused scans'
# ceiling (the non-fused codes kernel still takes kt 16); a batch wider
# than any kernel takes
SHAPES = {
    "inside": (64, 10, 0),
    "k-too-large": (64, 300, 16),
    "too-wide": (8192, 10, 0),
}

# (placement, shape) -> one (form, lowered, reason) per mode of MODES
EXPECTED = {
    ("single", "inside"): (
        ("fused_recon", False, ""), ("codes", False, ""),
        ("grouped_recon", False, ""), ("lut", False, ""),
        ("fused_codes", False, "")),
    ("single", "k-too-large"): (
        ("grouped_recon", False, "k-too-large"), ("codes", False, ""),
        ("grouped_recon", False, ""), ("lut", False, ""),
        ("codes", False, "k-too-large")),
    ("single", "too-wide"): (
        ("grouped_recon", False, "bucket-too-wide"), ("lut", False, ""),
        ("grouped_recon", False, ""), ("lut", False, ""),
        ("lut", False, "bucket-too-wide")),
    ("by_list", "inside"): (
        ("fused_codes", False, ""), ("probe_recon", True, ""),
        ("probe_recon", False, ""), ("probe_recon", True, ""),
        ("fused_codes", False, "")),
    ("by_list", "k-too-large"): (
        ("grouped_recon", False, ""), ("probe_recon", True, ""),
        ("probe_recon", False, ""), ("probe_recon", True, ""),
        ("grouped_recon", False, "k-too-large")),
    ("by_list", "too-wide"): (
        ("grouped_recon", False, ""), ("probe_recon", True, ""),
        ("probe_recon", False, ""), ("probe_recon", True, ""),
        ("grouped_recon", False, "bucket-too-wide")),
    ("by_row", "inside"): (
        ("fused_recon", False, ""), ("lut", True, ""),
        ("probe_recon", False, ""), ("lut", False, ""),
        ("fused_recon", False, "")),
    ("by_row", "k-too-large"): (
        ("grouped_recon", False, ""), ("lut", True, ""),
        ("probe_recon", False, ""), ("lut", False, ""),
        ("grouped_recon", False, "k-too-large")),
    ("by_row", "too-wide"): (
        ("grouped_recon", False, ""), ("lut", True, ""),
        ("probe_recon", False, ""), ("lut", False, ""),
        ("grouped_recon", False, "bucket-too-wide")),
}

CASES = [(placement, mode, shape, EXPECTED[placement, shape][i])
         for placement in PLACEMENTS for shape in SHAPES
         for i, mode in enumerate(MODES)]


def _plan(placement, mode, nq, k, kt=0, **over):
    facts = dict(placement=placement, metric=DistanceType.L2Expanded,
                 cap=416, rot=128, pq_bits=8, nq=nq, k=k, n_probes=72,
                 on_tpu=True, pq_dim=64, per_subspace=True,
                 has_recon=True, ids_exact=True)
    facts.update(PLACEMENTS[placement])
    facts.update(over)
    sp = ivf_pq.SearchParams(n_probes=72, scan_mode=mode,
                             per_probe_topk=kt)
    return ivf_pq.plan_scan(sp, **facts)


def _fallbacks(reg, reason):
    counters = reg.snapshot()["counters"]
    return (counters.get("ivf_pq.search.fused_fallback", 0),
            counters.get(f"ivf_pq.search.fused_fallback.reason.{reason}",
                         0))


@pytest.mark.parametrize(
    "placement,mode,shape,want", CASES,
    ids=[f"{p}-{m}-{s}" for p, m, s, _ in CASES])
def test_plan_form(placement, mode, shape, want):
    """Each case's form, lowering and fallback reason; a plan with a
    reason ticks the fallback counter and its reason child once."""
    nq, k, kt = SHAPES[shape]
    form, lowered, reason = want
    with obs.collecting() as reg:
        before = _fallbacks(reg, reason)
        plan = _plan(placement, mode, nq, k, kt)
        after = _fallbacks(reg, reason)
    assert (plan.form, plan.lowered, plan.reason) == want
    ticks = 1 if reason else 0
    assert after == (before[0] + ticks, before[1] + ticks)
    assert plan.kt == min(kt or k, 416)
    if form in ("fused_codes", "fused_recon", "codes", "grouped_recon"):
        n_groups, _ = grouped.group_capacity(nq, 72,
                                             PLACEMENTS[placement]["lists"])
        assert (plan.n_groups, plan.worst_groups, plan.exact) == (
            n_groups, n_groups, True)
        assert plan.use_pallas
    else:
        assert plan.n_groups == 0


# the cells: batches of 5,000 queries at k 20 (10 refined x2), 72 probes
# over the SIFT-1M index of 4,096 lists of 416 rows
CELLS = [
    # sift1m-ivfpq.batch5k: SearchParams(n_probes=72) on one chip
    ("single", "auto", "fused_recon", 6909),
    # sift1m-ivfpq-routed.batch5k: one shard's 2,049 slots, "fused"
    ("by_list", "fused", "fused_codes", 4862),
    # the routed index asked with the default mode takes the same scan
    ("by_list", "auto", "fused_codes", 4862),
]


@pytest.mark.parametrize("placement,mode,form,n_groups", CELLS)
def test_cell_plans(placement, mode, form, n_groups):
    plan = _plan(placement, mode, 5000, 20)
    assert (plan.form, plan.kt, plan.n_groups, plan.exact, plan.lowered,
            plan.reason) == (form, 20, n_groups, True, False, "")


EDGES = [
    # off the chip the Pallas kernels cannot run
    (dict(placement="single", mode="auto", on_tpu=False),
     ("grouped_recon", False, "backend")),
    (dict(placement="by_list", mode="fused", on_tpu=False),
     ("grouped_recon", False, "backend")),
    (dict(placement="by_list", mode="auto", on_tpu=False),
     ("probe_recon", False, "")),
    (dict(placement="by_row", mode="codes", on_tpu=False),
     ("lut", False, "")),
    # ids past 2^24 cannot ride the kernels' f32 id lanes
    (dict(placement="single", mode="fused", ids_exact=False),
     ("lut", False, "backend")),
    # a single index without the recon cache
    (dict(placement="single", mode="auto", has_recon=False),
     ("fused_codes", False, "")),
    (dict(placement="single", mode="recon", has_recon=False),
     ("grouped_recon", False, "")),
    # under an outer trace: the probe-order forms
    (dict(placement="single", mode="fused", traced=True),
     ("lut", False, "")),
    (dict(placement="single", mode="recon", traced=True),
     ("probe_recon", False, "")),
    # inner product: no codes kernel, no fused recon
    (dict(placement="single", mode="fused",
          metric=DistanceType.InnerProduct),
     ("grouped_recon", False, "dtype")),
    (dict(placement="single", mode="auto", has_recon=False,
          metric=DistanceType.InnerProduct),
     ("lut", False, "mode")),
    # per-cluster codebooks: no codes kernel
    (dict(placement="single", mode="codes", per_subspace=False),
     ("lut", False, "")),
    # a routed index placed without code lanes; a stacked index without
    # PQ metadata
    (dict(placement="by_list", mode="fused", code_lanes=False),
     ("fused_recon", False, "")),
    (dict(placement="by_row", mode="lut", pq_bits=0),
     ("probe_recon", True, "")),
]


@pytest.mark.parametrize("facts,want", EDGES,
                         ids=[f"{f['placement']}-{f['mode']}-"
                              + "-".join(f"{k}" for k in f
                                         if k not in ("placement", "mode"))
                              for f, _ in EDGES])
def test_plan_edges(facts, want):
    facts = dict(facts)
    plan = _plan(facts.pop("placement"), facts.pop("mode"), 64, 10, **facts)
    assert (plan.form, plan.lowered, plan.reason) == want


def test_calibrated_capacity_arms_the_overflow_check():
    """A calibrated estimate tightens the single and routed capacity
    (re-dispatch at the exact-safe bound); a stacked index keeps the
    bound, since its dispatch has no overflow path."""
    for placement in ("single", "by_list"):
        plan = _plan(placement, "fused", 5000, 20, group_est=0.3)
        worst, _ = grouped.group_capacity(5000, 72,
                                          PLACEMENTS[placement]["lists"])
        assert not plan.exact
        assert plan.n_groups < plan.worst_groups == worst
    plan = _plan("by_row", "fused", 5000, 20, group_est=0.3)
    assert plan.exact and plan.n_groups == plan.worst_groups


def test_unknown_mode_is_refused():
    with pytest.raises(LogicError, match="unknown scan_mode"):
        _plan("single", "recon8", 64, 10)
