"""chip_smoke.py on the CPU platform conftest forces: the script's own
drive-and-check function at a small size, proof that its device check
refuses anything but a TPU (it fails, it does not fall back), and the
contract of where the compile cache lives."""
import os
import subprocess
import sys

import pytest

import chip_smoke

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_device_check_refuses_cpu():
    with pytest.raises(chip_smoke.SmokeFailure, match="platform == 'cpu'"):
        chip_smoke.device_check()


def test_main_fails_without_a_chip_and_prints_no_result(capsys):
    assert chip_smoke.main() == 1
    out = capsys.readouterr()
    assert out.out == ""
    assert "platform == 'cpu'" in out.err


def test_spine_drive_and_checks():
    """The same submit-and-check function the chip runs, on the 8-device
    CPU mesh.  2,048 nodes, not fewer: below that the job mix itself
    dirties more than a quarter of the world's rows between two
    dispatches, which DeviceWorld answers with a full upload by design,
    and the steady gate would fail for a reason unrelated to the device.
    The big batch job is cut to 200 (still above SPARSE_CAP) so that no
    rack of so small a cluster fills up under the spread jobs."""
    s = chip_smoke.run(n_nodes=2048, big_count=200)
    per_pass = 4 * 100 + 200 + 2 * chip_smoke.SPREAD_COUNT + 2 * 4 \
        + chip_smoke.POOL_NODES * 9 + chip_smoke.POOL_NODES // 2 + 2
    assert s["allocs"] == 2 * per_pass
    assert s["mesh"] == {"node_shard": 4, "wave": 2}
    gate = s["steady_gate"]
    assert gate["transfer_guard"] == "disallow"
    assert gate["compile_events"] == 0 and gate["steady_reuploads"] == 0
    assert gate["donated_carries"] > 0
    assert gate["bulk_parts"] == gate["bulk_groups"] > 0
    assert gate["sharded_evals"] > 0 and gate["wave_lanes"] > 0
    for p in (s["warm_pass"], s["steady_pass"]):
        assert min(p["spread_racks"]) >= chip_smoke.SPREAD_MIN_RACKS
        assert p["evicted"] >= chip_smoke.POOL_NODES // 2
        assert p["binpack_gap"] <= chip_smoke.BINPACK_TOL


_CACHE_PROBE = """
import jax
updated = []
_update = jax.config.update
jax.config.update = lambda k, v: (updated.append(k), _update(k, v))[1]
from nomad_tpu.utils import enable_compile_cache
print(enable_compile_cache())
print(jax.config.jax_compilation_cache_dir)
print("jax_compilation_cache_dir" in updated)
"""


def _probe_cache(env_dir):
    env = dict(os.environ, JAX_PLATFORMS="cpu", NOMAD_TPU_JAX_CACHE="1")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    out = subprocess.run([sys.executable, "-c", _CACHE_PROBE], cwd=REPO,
                         env=env, capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    return out.stdout.split()


def test_compile_cache_dir_comes_from_the_environment(tmp_path):
    want = str(tmp_path / "placed-from-outside")
    returned, configured, updated_in_code = _probe_cache(want)
    assert returned == configured == want
    assert updated_in_code == "False"


def test_compile_cache_dir_defaults_to_the_checkout():
    returned, configured, updated_in_code = _probe_cache(None)
    assert returned == configured == os.path.join(REPO, ".jax_cache")
    assert updated_in_code == "True"
