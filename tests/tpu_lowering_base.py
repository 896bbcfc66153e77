"""What the files that lower and compile for the TPU from a CPU-only host
share (`test_tpu_lowering.py`: the kernels and the small steps;
`test_tpu_lowering_experts.py`: the experts' grouped product at the cells'
widths; `test_tpu_lowering_steps.py`: the cells' whole steps): the dispatch
decisions of a TPU host, and a described v5e to compile for. A test file
imports the two fixtures by name; nothing here runs at import."""
import importlib

import pytest

from paddle_tpu.ops.pallas_kernels import fused_bn

fa = importlib.import_module("paddle_tpu.ops.pallas_kernels.flash_attention")
ssd = importlib.import_module("paddle_tpu.ops.pallas_kernels.ssd_scan")
gffn = importlib.import_module("paddle_tpu.ops.pallas_kernels.grouped_ffn")
kda = importlib.import_module("paddle_tpu.ops.pallas_kernels.kda_chunk")


@pytest.fixture(autouse=True)
def _dispatch_as_on_tpu(monkeypatch):
    """Take the dispatch decisions a TPU host would take."""
    monkeypatch.setattr(fa, "_on_tpu", lambda: True)
    monkeypatch.setattr(fused_bn, "_on_tpu", lambda: True)
    monkeypatch.setattr(ssd, "_on_tpu", lambda: True)
    monkeypatch.setattr(gffn, "_on_tpu", lambda: True)
    monkeypatch.setattr(kda, "_on_tpu", lambda: True)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])
