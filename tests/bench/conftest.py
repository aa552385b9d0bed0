"""Shared fixtures of the chip benchmark's CPU tests: the harness on the
path and tiny cells of each driver."""
import copy
import sys
from pathlib import Path

import pytest

CHIP = Path(__file__).resolve().parents[2] / "benchmarks" / "chip"
if str(CHIP) not in sys.path:
    sys.path.insert(0, str(CHIP))

import harness  # noqa: E402

TINY_DENSE = {
    "name": "tiny", "program_config": "yi-9b", "family": "dense",
    "n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 2,
    "head_dim": 16, "d_ff": 128, "vocab": 256, "rope_theta": 10000.0,
    "norm_eps": 1e-6, "dtype": "bfloat16",
    "reduced": list(harness.family("dense").SIZE_KEYS),
}


def tiny_cell(name: str, traffic: dict, config=None, limits=None):
    """A cell of the real BENCHMARK.json metrics with a tiny configuration
    and mix."""
    bench = harness.benchmark()
    cell = harness.load_cell(name, bench)
    cell.config = copy.deepcopy(config or TINY_DENSE)
    cell.traffic = dict(cell.traffic, **traffic)
    if limits is not None:
        cell.limits = dict(limits)
    return cell


@pytest.fixture
def cpu_devices():
    import jax
    return jax.devices("cpu")[:1]


@pytest.fixture(scope="module")
def cpu_devices_module():
    import jax
    return jax.devices("cpu")[:1]
