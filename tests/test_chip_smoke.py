"""`chip_smoke.py` phases at tiny sizes on the CPU, kernels interpreted.

The script itself refuses to run without a TPU; these tests drive its
phase functions directly to check control flow, request conservation and
the parity assertions before any chip time is spent.
"""
import importlib.util
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.core.batch_routing import BatchRoutingEngine
from repro.core.routing import RoutingConfig

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


cs = _load()


@pytest.mark.parametrize("argv", [[], ["--four-chips"]])
def test_main_refuses_non_tpu(argv, capsys):
    assert cs.main(argv) != 0
    out = capsys.readouterr()
    assert out.out == ""
    assert "no TPU" in out.err


def test_served_phase_conserves_requests():
    info = cs.phase_served(16, 40, 400.0, 0, interpret=True)
    assert info["offered"] == 40
    assert info["routed"] + info["shed"] + info["expired"] == 40
    assert info["routed"] > 0


def test_parity_phase_all_algorithms():
    info = cs.phase_parity(60, 16, 0, interpret=True)
    assert set(info) == {"precision", *cs.PARITY_ALGOS}
    for algo in cs.PARITY_ALGOS:
        assert info[algo]["max_fused_diff"] <= 1e-6
        assert info[algo]["distinct_servers"] > 1


def test_mega_phase_kernel_matches_jnp():
    info = cs.phase_mega(3000, 16, 0, interpret=True)
    assert info["n_servers"] == 3000 and info["n_tools"] > 3000


def test_catalog_descriptions_are_distinct():
    servers = cs.catalog(200, 0)
    assert len({s.description for s in servers}) == 200


def test_same_picks_reports_mismatch():
    a = (np.array([1, 2, 3]), np.array([4, 5, 6]))
    cs.same_picks("equal", a, a)
    with pytest.raises(AssertionError, match="1/3 picks differ"):
        cs.same_picks("one off", a, (np.array([1, 2, 3]), np.array([4, 0, 6])))


def test_compiled_check_demands_a_kernel():
    """A route program without a Pallas kernel fails the check unless the
    kernels are interpreted: the jnp path cannot pass for the chip path."""
    servers = cs.catalog(30, 0)
    eng = BatchRoutingEngine(servers, RoutingConfig(top_s=4, top_k=4),
                             algo="sonar", use_kernels=False)
    batch = eng.encode(cs.queries(8, 0))
    assert cs.check_compiled(eng, batch, interpret=True) >= 0.0
    with pytest.raises(AssertionError, match="no TPU kernel"):
        cs.check_compiled(eng, batch, interpret=False)


FOUR_CHIPS = r"""
import json, sys
sys.path.insert(0, {root!r})
import jax
assert len(jax.devices()) == 4
import chip_smoke as cs
print(json.dumps(cs.phase_four_chips(4000, 64, 16, 0, interpret=True)))
"""


def test_four_chip_phase_on_virtual_devices():
    """The 4-shard mesh and the sharded gateway ring on 4 CPU devices (in
    a child process, so the forced device count stays out of this one)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run(
        [sys.executable, "-c", FOUR_CHIPS.format(root=ROOT)],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=600,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    info = json.loads(out.stdout.strip().splitlines()[-1])
    assert info["ring_devices"] == 4
