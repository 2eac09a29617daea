"""The CAGRA cell's operating point sits inside the fused hop's gates for
every serving bucket, and the TPU's compiler accepts the kernel there, so
the walk takes ``fused_hop`` in every batch."""

import json
import os
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from raft_tpu.ops import cagra_hop_pallas as chp
from raft_tpu.serving.buckets import bucket_sizes

HERE = Path(__file__).resolve().parents[1]
# the walk's table width is calibrated at build; these bracket it
PDIMS = (32, 64)


def _shape():
    cfg = json.loads((HERE / "configs" / "sift1m-cagra.json").read_text())
    mix = json.loads((HERE / "traffic" / "online.json").read_text())
    s = cfg["index"]["search"]
    wd = s["search_width"] * cfg["index"]["build"]["graph_degree"]
    return s["itopk_size"], wd, bucket_sizes(mix["max_batch"])


@pytest.mark.parametrize("pdim", [16, 32, 64, 96, 128])
def test_fused_hop_gate_admits_every_bucket(pdim):
    itopk, wd, buckets = _shape()
    for nq in buckets:
        assert chp.hop_merge_window(nq, itopk, wd, pdim) > 0


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler here
        pytest.skip(f"no v5e topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("pdim", PDIMS)
def test_fused_hop_compiles_for_v5e_at_every_bucket(pdim, one_chip):
    itopk, wd, buckets = _shape()

    def s(shape, dt=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    for nq in buckets:
        chp.fused_hop.lower(
            s((nq, pdim)), s((nq,)), s((nq, wd, pdim)), s((nq, wd)),
            s((nq, wd), jnp.int32), s((nq, itopk)),
            s((nq, itopk), jnp.int32), s((nq, itopk), jnp.bool_),
            itopk=itopk, ip_metric=False).compile()
