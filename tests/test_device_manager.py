"""Device manager: fail-fast init, version gate, HBM pool math.

Reference: GpuDeviceManager.scala:120-262 (init + computeRmmInitSizes),
Plugin.scala:146-201 (fail-fast executor init + version check with
override flag).
"""
import time

import pytest

from spark_rapids_tpu import device as D
from spark_rapids_tpu.conf import TpuConf


@pytest.fixture(autouse=True)
def fresh_state():
    D._reset_for_tests()
    yield
    D._reset_for_tests()
    # leave the process initialized for later tests in the session
    D.initialize_device(TpuConf({}))


def test_initialize_populates_info():
    D.initialize_device(TpuConf({}))
    info = D.device_info()
    assert info["initialized"]
    assert info["device_count"] >= 1
    assert info["platform"] == "cpu"  # conftest pins the CPU backend


def test_init_timeout_fails_fast():
    conf = TpuConf({"spark.rapids.tpu.initTimeoutSeconds": 1})
    with pytest.raises(D.TpuInitError, match="did not complete"):
        D.initialize_device(conf, probe=lambda: time.sleep(30))


def test_init_probe_error_fails_fast():
    def boom():
        raise RuntimeError("PJRT exploded")
    with pytest.raises(D.TpuInitError, match="PJRT exploded"):
        D.initialize_device(TpuConf({}), probe=boom)


def test_version_gate_and_override(monkeypatch):
    import jax
    monkeypatch.setattr(jax, "__version__", "0.3.0")
    with pytest.raises(D.TpuInitError, match="jax 0.3.0"):
        D.initialize_device(TpuConf({}))
    # override flag continues with a warning (reference Plugin.scala:198)
    conf = TpuConf({"spark.rapids.tpu.allowIncompatibleRuntime": True})
    with pytest.warns(RuntimeWarning, match="incompatible runtime"):
        D.initialize_device(conf)
    assert D.device_info()["initialized"]


def test_pool_limit_math():
    # 16 GB HBM, 75% alloc fraction, 256 MB reserve
    got = D._compute_pool_limit(16 << 30, 0.75, 256 << 20)
    assert got == int((16 << 30) * 0.75) - (256 << 20)
    # degenerate budget floors at 64 MB instead of going negative
    assert D._compute_pool_limit(1 << 20, 0.5, 1 << 30) == 64 << 20


def test_catalog_uses_device_pool_limit():
    from spark_rapids_tpu.memory.catalog import BufferCatalog
    D.initialize_device(TpuConf({}))
    # CPU backend exposes no bytes_limit: simulate an initialized TPU
    D._State.hbm_bytes_limit = 8 << 30
    D._State.pool_limit = D._compute_pool_limit(8 << 30, 0.75, 256 << 20)
    cat = BufferCatalog(conf=TpuConf({}))
    assert cat.device_limit == D._State.pool_limit
    # an explicit spillStoreSize always wins over the derived budget
    cat2 = BufferCatalog(conf=TpuConf(
        {"spark.rapids.memory.tpu.spillStoreSize": 123 << 20}))
    assert cat2.device_limit == 123 << 20
    cat.close()
    cat2.close()


# -- no fallback that hides the device ----------------------------------

class _FakeDevice:
    def __init__(self, platform, stats=None):
        self.platform = self.device_kind = platform
        self._stats = stats

    def memory_stats(self):
        return self._stats


def test_cpu_device_without_cpu_request_raises(monkeypatch):
    # CPU was not asked for, yet the probe resolves to a CPU device (a
    # TPU that failed to initialise makes bare jax.devices() do that)
    monkeypatch.setattr(D, "_cpu_requested", lambda: False)
    with pytest.raises(D.TpuInitError, match="does not fall back"):
        D.initialize_device(TpuConf({}), probe=lambda: [_FakeDevice("cpu")])
    assert not D.device_info()["initialized"]


def test_cpu_device_with_cpu_request_passes():
    assert D._cpu_requested()  # conftest pins JAX_PLATFORMS=cpu
    D.initialize_device(TpuConf({}), probe=lambda: [_FakeDevice("cpu")])
    info = D.device_info()
    assert info["initialized"] and info["platform"] == "cpu"
    assert info["hbm_bytes_limit"] is None


@pytest.mark.parametrize("platforms,want", [
    ("cpu", True), ("cpu,tpu", True), ("tpu", False), ("tpu,cpu", False),
    ("", False), (None, False)])
def test_cpu_requested_reads_the_platform_request(monkeypatch, platforms,
                                                  want):
    import jax

    class _Cfg:
        jax_platforms = platforms
    monkeypatch.setattr(jax, "config", _Cfg)
    assert D._cpu_requested() is want


def test_tpu_without_bytes_limit_raises():
    with pytest.raises(D.TpuInitError, match="bytes_limit"):
        D.initialize_device(TpuConf({}),
                            probe=lambda: [_FakeDevice("tpu", {})])
    D._reset_for_tests()
    D.initialize_device(TpuConf({}), probe=lambda: [
        _FakeDevice("tpu", {"bytes_limit": 16 << 30})])
    info = D.device_info()
    assert info["platform"] == "tpu"
    assert info["hbm_bytes_limit"] == 16 << 30
    assert info["pool_limit"] == D._compute_pool_limit(
        16 << 30, 0.75, 256 << 20)


# -- one compile-cache rule ----------------------------------------------

@pytest.fixture
def cache_updates(monkeypatch):
    """Record jax.config.update calls made by the runtime instead of
    applying them (the test process must keep its own cache setting)."""
    import jax
    from spark_rapids_tpu import runtime
    seen = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: seen.append((k, v)))
    monkeypatch.setattr(runtime, "_enabled_dir", None)
    monkeypatch.setattr(runtime.os, "makedirs", lambda *a, **k: None)
    return seen


def test_cache_dir_from_environment_is_not_set_in_code(monkeypatch,
                                                       cache_updates):
    from spark_rapids_tpu import runtime
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    assert runtime.enable_compilation_cache() == "/some/dir"
    assert "jax_compilation_cache_dir" not in [k for k, _ in cache_updates]


def test_cache_dir_default_is_fixed_checkout_path(monkeypatch,
                                                  cache_updates):
    import os
    from spark_rapids_tpu import runtime
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    want = os.path.join(repo, ".jax_cache")
    got = []
    # two processes' worth of inputs: a different pid, clock, tmp dir,
    # flags and home must not move the directory
    for pid, flags in ((111, ""), (222, "--xla_foo=1")):
        monkeypatch.setattr(runtime, "_enabled_dir", None)
        monkeypatch.setattr(os, "getpid", lambda pid=pid: pid)
        monkeypatch.setenv("XLA_FLAGS", flags)
        monkeypatch.setenv("TMPDIR", f"/tmp/t{pid}")
        monkeypatch.setenv("HOME", f"/home/u{pid}")
        got.append(runtime.enable_compilation_cache())
    assert got == [want, want]
    dirs = [v for k, v in cache_updates if k == "jax_compilation_cache_dir"]
    assert dirs == [want, want]


def test_unmakeable_cache_dir_raises(monkeypatch, cache_updates):
    from spark_rapids_tpu import runtime
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)

    def refuse(*a, **k):
        raise PermissionError("read-only checkout")
    monkeypatch.setattr(runtime.os, "makedirs", refuse)
    with pytest.raises(PermissionError):
        runtime.enable_compilation_cache()
    assert cache_updates == []


@pytest.mark.parametrize("env_dir,mode,want_on", [
    (None, "auto", False),          # plain XLA:CPU: off
    ("/some/dir", "auto", True),    # ... unless the environment placed one
    (None, "true", True), ("/some/dir", "false", False)])
def test_cache_auto_mode_on_cpu(monkeypatch, cache_updates, env_dir, mode,
                                want_on):
    from spark_rapids_tpu import runtime
    if env_dir:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    runtime.ensure_runtime(TpuConf(
        {"spark.rapids.tpu.compilationCache.enabled": mode}))
    assert (runtime._enabled_dir is not None) is want_on


# -- a configured mesh wider than the devices raises ---------------------

def test_mesh_wider_than_devices_raises():
    import jax
    from spark_rapids_tpu import TpuSession
    D.initialize_device(TpuConf({}))
    too_wide = len(jax.devices()) * 2
    s = TpuSession({"spark.rapids.tpu.mesh.deviceCount": too_wide,
                    "spark.rapids.sql.resultCache.enabled": "false"})
    df = s.from_pydict({"k": [1, 2, 1, 2], "v": [1, 2, 3, 4]},
                       _kv_schema())
    from spark_rapids_tpu.expr.aggregates import Sum
    from spark_rapids_tpu.expr.core import col
    with pytest.raises(RuntimeError, match="mesh.deviceCount"):
        df.group_by("k").agg(Sum(col("v")).alias("s")).collect()


def _kv_schema():
    from spark_rapids_tpu import types as T
    return T.Schema([T.StructField("k", T.LongType(), True),
                     T.StructField("v", T.LongType(), True)])


def test_mesh_devices_helper_raises_and_slices():
    import jax
    from spark_rapids_tpu.exec.mesh_exec import _mesh_devices
    n = len(jax.devices())
    assert _mesh_devices(n) == jax.devices()[:n]
    with pytest.raises(RuntimeError, match="refusing"):
        _mesh_devices(n + 1)


# -- chip_smoke: the CPU rehearsal, and the refusal to run off the chip --

def test_chip_smoke_refuses_cpu_before_writing_data(tmp_path):
    import chip_smoke
    D.initialize_device(TpuConf({}))
    data = tmp_path / "data"
    with pytest.raises(RuntimeError, match="expected 'tpu'"):
        chip_smoke.run(sf=0.1, seed=42, chips=1, data_dir=str(data))
    assert not data.exists()


@pytest.fixture(scope="module")
def smoke_data(tmp_path_factory):
    """One SF0.1 data directory for both rehearsals (the generator's
    stamp makes the second one skip generation)."""
    return str(tmp_path_factory.mktemp("smoke") / "data")


@pytest.mark.parametrize("chips", [1, 4])
def test_chip_smoke_cpu_rehearsal(smoke_data, capsys, chips):
    import json

    import chip_smoke
    D.initialize_device(TpuConf({}))
    result = chip_smoke.run(sf=0.1, seed=42, chips=chips,
                            expect_platform="cpu", data_dir=smoke_data)
    assert result["ok"] is True and result["device"]["platform"] == "cpu"
    facts = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("{")]
    collects = [f for f in facts if f.get("phase") == "collect"]
    assert [c["run"] for c in collects] == ["cold", "warm1", "warm2"]
    assert all(c["rows"] > 0 and c["queries_executed"] == 1
               for c in collects)
    assert [c["compile_count"] for c in collects[1:]] == [0, 0]
    plan = next(f for f in facts if f.get("phase") == "plan")
    assert any("Mesh" in ln for ln in plan["exec"]) is (chips == 4)
    # one chip: TPC-H Q6 and its kept rows by discount, bounds included
    tpch = [f for f in facts if f.get("phase") == "tpch_q6"]
    assert [f["query"] for f in tpch] \
        == (["q6", "q6 by discount"] if chips == 1 else [])
    if tpch:
        assert [r[0] for r in tpch[1]["rows"]] == [0.05, 0.06, 0.07]
        assert tpch[1]["rows"] == tpch[1]["oracle"]
