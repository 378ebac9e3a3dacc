"""Native C++ kernel tests — and parity between the native and numpy
twin paths (reference analogs: structs/funcs_test.go AllocsFit/
ScoreFit tests, plan_apply_test.go node validation)."""
import os

import numpy as np
import pytest

from nomad_tpu import native


@pytest.fixture(scope="module")
def lib_available():
    native._load()
    return native.NATIVE_AVAILABLE


def test_native_library_builds(lib_available):
    # the toolchain is part of the environment contract; the native
    # path must actually be exercised in CI, not silently skipped
    assert lib_available, "g++ build of native/nomad_native.cpp failed"


def _unbuilt(monkeypatch, tmp_path, source: str):
    src = tmp_path / "nomad_native.cpp"
    src.write_text(source)
    monkeypatch.setattr(native, "_SRC", str(src))
    monkeypatch.setattr(native, "_BUILD_DIR", str(tmp_path / "build"))
    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.delenv("NOMAD_TPU_NATIVE_LIB", raising=False)


def test_failed_build_raises_with_the_compilers_message(monkeypatch,
                                                        tmp_path):
    _unbuilt(monkeypatch, tmp_path, "int broken( { return 0; }\n")
    with pytest.raises(native.NativeBuildError, match=r"(?s)g\+\+.*error"):
        native.allocs_fit(np.ones((1, 3), np.float32),
                          np.zeros((1, 3), np.float32),
                          np.zeros(3, np.float32))


def test_missing_compiler_raises(monkeypatch, tmp_path):
    _unbuilt(monkeypatch, tmp_path, "int f() { return 0; }\n")
    monkeypatch.setenv("PATH", str(tmp_path))
    with pytest.raises(native.NativeBuildError, match=r"g\+\+"):
        native._load()


def test_allocs_fit():
    cap = np.array([[1000, 1000, 1000], [100, 100, 100]], np.float32)
    used = np.array([[500, 500, 500], [90, 90, 90]], np.float32)
    fit = native.allocs_fit(cap, used, np.array([100, 100, 100], np.float32))
    assert fit.tolist() == [True, False]
    # exact boundary fits
    fit = native.allocs_fit(cap, used, np.array([500, 500, 500], np.float32))
    assert fit.tolist() == [True, False]


def test_score_fit_matches_host_reference():
    from nomad_tpu.structs import (
        ComparableResources,
        score_fit_binpack_host,
    )
    cap = np.array([[4000, 8192, 0]], np.float32)
    used = np.array([[1000, 2048, 0]], np.float32)
    demand = np.array([500, 1024, 0], np.float32)
    got = native.score_fit(cap, used, demand)[0]
    node = ComparableResources(cpu_shares=4000, memory_mb=8192)
    util = ComparableResources(cpu_shares=1500, memory_mb=3072)
    # native.score_fit returns the /18-normalized score in [0, 1]
    want = score_fit_binpack_host(node, util) / 18.0
    assert got == pytest.approx(want, abs=1e-4)


def test_score_fit_binpack_prefers_fuller_node():
    cap = np.array([[1000, 1000, 0], [1000, 1000, 0]], np.float32)
    used = np.array([[800, 800, 0], [100, 100, 0]], np.float32)
    s = native.score_fit(cap, used, np.array([50, 50, 0], np.float32))
    assert s[0] > s[1]                        # binpack packs fuller node
    s2 = native.score_fit(cap, used, np.array([50, 50, 0], np.float32),
                          spread=True)
    assert s2[1] > s2[0]                      # spread prefers emptier


def test_ports_roundtrip():
    words = np.zeros((2, 2048), np.uint32)
    native.ports_set(words, 0, [80, 443, 20000], True)
    assert not native.ports_check(words, 0, [80])
    assert native.ports_check(words, 0, [8080])
    assert native.ports_check(words, 1, [80])          # other row clean
    # freed ports count as free
    assert native.ports_check(words, 0, [443], freed=[443])
    # duplicates within a request collide
    assert not native.ports_check(words, 0, [8080, 8080])
    native.ports_set(words, 0, [80], False)
    assert native.ports_check(words, 0, [80])


def test_scatter_add():
    used = np.zeros((4, 3), np.float32)
    native.scatter_add(used, [1, 1, 3],
                       np.array([[1, 2, 3], [1, 2, 3], [5, 5, 5]],
                                np.float32))
    assert used[1].tolist() == [2, 4, 6]
    assert used[3].tolist() == [5, 5, 5]
    assert used[0].tolist() == [0, 0, 0]


def test_validate_plan_batch():
    cap = np.array([[1000, 1000, 1000]] * 3, np.float32)
    used = np.array([[0, 0, 0], [950, 0, 0], [500, 500, 500]], np.float32)
    words = np.zeros((3, 2048), np.uint32)
    native.ports_set(words, 2, [9090], True)
    ok = native.validate_plan(
        cap, used, words,
        rows=[0, 1, 2, -1],
        demand=np.array([[100, 100, 100], [100, 0, 0],
                         [100, 100, 100], [1, 1, 1]], np.float32),
        freed=np.array([[0, 0, 0], [0, 0, 0], [0, 0, 0], [0, 0, 0]],
                       np.float32),
        group_ports=[[80], [], [9090], []],
        group_freed_ports=[[], [], [], []])
    assert ok.tolist() == [True, False, False, False]
    # with 9090 freed by a stop in the same plan, node 2 passes
    ok2 = native.validate_plan(
        cap, used, words, rows=[2],
        demand=np.array([[100, 100, 100]], np.float32),
        freed=np.array([[0, 0, 0]], np.float32),
        group_ports=[[9090]], group_freed_ports=[[9090]])
    assert ok2.tolist() == [True]


def test_native_numpy_parity():
    """The numpy fallback and C++ path agree on random inputs."""
    if not native.NATIVE_AVAILABLE:
        pytest.skip("native lib unavailable")
    rng = np.random.default_rng(42)
    cap = rng.uniform(100, 5000, (64, 3)).astype(np.float32)
    used = (cap * rng.uniform(0, 1.2, (64, 3))).astype(np.float32)
    demand = rng.uniform(0, 500, 3).astype(np.float32)

    lib, native._lib = native._lib, None
    avail = native.NATIVE_AVAILABLE
    native.NATIVE_AVAILABLE = False
    try:
        import unittest.mock as m
        with m.patch.object(native, "_load", return_value=None):
            fit_np = native.allocs_fit(cap, used, demand)
            score_np = native.score_fit(cap, used, demand)
    finally:
        native._lib = lib
        native.NATIVE_AVAILABLE = avail
    fit_c = native.allocs_fit(cap, used, demand)
    score_c = native.score_fit(cap, used, demand)
    assert (fit_np == fit_c).all()
    np.testing.assert_allclose(score_np, score_c, atol=1e-4)


def test_six_processes_build_into_an_empty_directory(tmp_path):
    """A fresh checkout under `pytest -n 6`: every worker finds
    `native/build` empty and compiles.  Each compiles to a name of its
    own and `os.replace`s it, so all six end with a library that loads
    (they used to share one `.tmp` name, and the losers of the race
    raised FileNotFoundError from `os.replace`)."""
    import subprocess
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    code = ("import ctypes, sys; from nomad_tpu import native; "
            "p = native._build(sys.argv[1]); ctypes.CDLL(p); print(p)")
    env = {k: v for k, v in os.environ.items()
           if k != "NOMAD_TPU_NATIVE_LIB"}
    build = str(tmp_path / "build")
    procs = [subprocess.Popen([sys.executable, "-c", code, build], cwd=root,
                              env=env, stdout=subprocess.PIPE,
                              stderr=subprocess.PIPE, text=True)
             for _ in range(6)]
    outs = [p.communicate(timeout=300) for p in procs]
    assert [p.returncode for p in procs] == [0] * 6, [e for _o, e in outs]
    assert len({o.strip() for o, _e in outs}) == 1
    assert [n for n in os.listdir(build) if not n.endswith(".so")] == []
