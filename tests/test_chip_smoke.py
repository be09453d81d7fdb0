"""Rehearsal of chip_smoke.py on the CPU backend: every stage function
runs here at a tiny size through the same code path the chip will run
at full size (the platform assertion lives in the script's entry, which
these tests call only to see it refuse)."""
import os
import subprocess

import pytest

import chip_smoke
from windflow_tpu.ops import backend
from windflow_tpu.runtime import native

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_stage_a_fed_headline(capsys):
    chip_smoke.stage_a(n_events=40_960, n_warm=8_192, n_keys=4, win=64,
                       slide=32, chunk=4096)
    out = capsys.readouterr().out
    assert "stage A:" in out and "win_seq_tpu.0', 'device')]" in out
    if native.native_available():
        assert "native_engine=True" in out


def test_stage_b_python_staged_lanes(capsys):
    chip_smoke.stage_b(n_events=16_384, n_keys=4, win=64, slide=32,
                       chunk=4096)
    out = capsys.readouterr().out
    assert "stage B/custom fn:" in out and "stage B/ffat hypot:" in out
    assert "native_engine=True" not in out


def test_stage_c_python_staging_under_device_step(capsys):
    chip_smoke.stage_c(n_events=32_768, n_keys=4, win=64, slide=16,
                       chunk=1024, batch_len=16)
    out = capsys.readouterr().out
    assert "C device_step=True" in out and "C device_step=False" in out
    assert "stage C: chunks=" in out


def test_stage_d_yahoo(capsys):
    chip_smoke.stage_d(n_events=20_000, n_ads=50, n_campaigns=10,
                       win=4096, chunk=4096)
    assert "50 windows equal" in capsys.readouterr().out


def test_stage_kernels_interpreted(capsys):
    chip_smoke.stage_kernels(shapes=[(256, 64)])
    assert "interpret, equal to XLA" in capsys.readouterr().out


def test_mesh_stage_through_the_dry_run(capsys):
    """__graft_entry__.dryrun_multichip and ``chip_smoke.py --chips 4``
    share stage_mesh; conftest's eight virtual devices stand in."""
    import __graft_entry__
    __graft_entry__.dryrun_multichip(8)
    out = capsys.readouterr().out
    for name in ("ShardedWindowEngine.step", "KeyFarmMesh", "PaneFarmMesh",
                 "WinMapReduceMesh"):
        assert name in out


def test_a_wrong_window_fails_the_check():
    import numpy as np
    want = np.arange(6, dtype=np.float64).reshape(3, 2) + 1
    got = want.copy()
    got[1, 1] *= 1 + 1e-4
    chip_smoke.check("ok", want.copy(), want)
    with pytest.raises(AssertionError, match="1 of 6 windows differ"):
        chip_smoke.check("sum", got, want, chip_smoke.SUM_RTOL)


def test_entry_refuses_the_cpu_backend(monkeypatch, capsys):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    with pytest.raises(SystemExit) as e:
        chip_smoke.main([])
    assert e.value.code not in (0, None)
    out = capsys.readouterr().out
    assert "platform=cpu" in out
    assert "stage" not in out and '"ok"' not in out


def test_compile_cache_dir_is_placed_from_outside(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert backend.compile_cache_dir() == str(tmp_path)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    assert backend.compile_cache_dir() == os.path.join(CHECKOUT,
                                                       ".jax_cache")


@pytest.mark.skipif(not native.native_available(),
                    reason="no native library on this host")
def test_native_stamp_of_another_cpu_is_rebuilt_not_loaded(monkeypatch,
                                                           capsys):
    here = native._build_stamp()
    with open(native._STAMP) as f:
        assert f.read() == here
    monkeypatch.setattr(native, "_cpu_identity", lambda: "another cpu")
    assert native._build_stamp() != here
    calls = []

    def failing_compiler(cmd, **kw):
        calls.append(cmd)
        raise subprocess.CalledProcessError(1, cmd, stderr=b"g++: boom")

    monkeypatch.setattr(native.subprocess, "run", failing_compiler)
    # the library on disk is this host's; with another host's identity
    # it must go back to the compiler, and a failed build says why
    assert native._build() is None
    assert len(calls) == 1 and calls[0][0] == "g++"
    assert "-march=native" in calls[0]
    err = capsys.readouterr().err
    assert "pure-Python plane" in err and "g++: boom" in err
