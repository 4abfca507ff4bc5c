"""chip_smoke.py: refuses to run without a TPU or outside a checkout,
and its phases pass on the CPU at smoke sizes (interpret-mode kernels,
smoke-width evaluator, virtual devices for the four-chip path)."""
import importlib.util
import os
import shutil
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", REPO / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_exits_nonzero_without_a_tpu(smoke, capsys):
    assert smoke.main([]) != 0
    out = capsys.readouterr()
    assert '"ok"' not in out.out
    assert "no TPU" in out.err


def test_exits_nonzero_outside_a_checkout(tmp_path):
    shutil.copy(REPO / "chip_smoke.py", tmp_path / "chip_smoke.py")
    r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                       capture_output=True, text=True, timeout=120,
                       env={**os.environ, "PYTHONPATH": ""})
    assert r.returncode != 0
    assert '"ok"' not in r.stdout


def test_kernel_phase_matches_oracles(smoke):
    smoke.phase_kernels(native=False, n_items=1024, n_docs=512)


def test_serving_phase_on_the_launcher_path(smoke, capsys):
    smoke.phase_serve(smoke.serve_argv(full_width=False, corpus=512,
                                       index_shards=4, n_queries=12))
    out = capsys.readouterr().out
    assert "tiers equal the shed_plan oracle" in out
    assert "FAIL" not in out


def test_serving_phase_fails_when_the_executor_rescues(smoke, monkeypatch):
    """A device step that raises is answered from the prior by the
    executor; the smoke check must fail rather than pass on it."""
    from repro.core.fused_shedder import FusedLoadShedder

    def boom(self, staged):
        raise RuntimeError("injected device failure")

    monkeypatch.setattr(FusedLoadShedder, "dispatch_staged", boom)
    with pytest.raises(smoke.SmokeFailure):
        smoke.phase_serve(smoke.serve_argv(full_width=False, corpus=512,
                                           index_shards=4, n_queries=4))


def test_four_chip_phase_on_virtual_devices():
    code = textwrap.dedent("""
        import os, sys
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        sys.path.insert(0, os.getcwd())
        import chip_smoke
        chip_smoke.phase_four_chips(smoke=True, n_items=64, n_batches=2)
        print("OK")
    """)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=300, cwd=REPO,
                       env={**os.environ, "PYTHONPATH": "src",
                            "JAX_PLATFORMS": "cpu"})
    assert "OK" in r.stdout, (r.stdout[-2000:], r.stderr[-3000:])
    assert "every device holds a parameter shard" in r.stdout
