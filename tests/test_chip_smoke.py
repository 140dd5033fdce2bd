"""chip_smoke.py rehearsed on the CPU (no chip, no subprocess).

The platform JAX must report and the sizes the phases run at are
ARGUMENTS of ``chip_smoke.main`` — the script reads no env var and has
no CLI switch for them — so this file drives the very phases the chip
runs, at tiny size, with ``platform="cpu"`` injected.
"""
import json
import subprocess

import pytest

import chip_smoke

TINY_SWEEP = """
topology_paths = ["{topo}"]
environments = ["NONE", "ISTIO"]

[client]
qps = [1000]
duration = "4m"
num_concurrent_connections = [16, 64]
load_kind = "closed"

[sim]
num_requests = 2000
seed = 0
"""


@pytest.fixture
def no_subprocess(monkeypatch):
    """Any attempt to start a process fails the test."""

    def boom(*args, **kwargs):
        raise AssertionError(f"chip_smoke spawned a process: {args}")

    monkeypatch.setattr(subprocess, "Popen", boom)
    for name in ("system", "fork", "execv", "execve", "posix_spawn"):
        monkeypatch.setattr(chip_smoke.os, name, boom, raising=False)


@pytest.fixture
def tiny(tmp_path, monkeypatch, jax_cache_config):
    """Tiny sizes, and the compile cache in a temp dir — named the way
    the chip's machine names it: the variable set, and JAX (which
    reads it at import) already pointing there."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    xla = str(tmp_path / "xla")
    monkeypatch.setenv(jax_cache_config.ENV_JAX_CACHE_DIR, xla)
    jax.config.update("jax_compilation_cache_dir", xla)
    compilation_cache.reset_cache()
    toml = tmp_path / "exp.toml"
    toml.write_text(TINY_SWEEP.format(
        topo=chip_smoke.REPO + "/examples/topologies/canonical.yaml"
    ))
    return chip_smoke.Sizes(max_requests=512, agree_requests=256,
                            sweep_toml=str(toml))


def _lines(capsys):
    return [json.loads(x) for x in capsys.readouterr().out.splitlines()]


@pytest.mark.parametrize("argv", [[], ["--chips", "4"]])
def test_fails_without_a_tpu(argv, capsys, no_subprocess):
    """As the driver runs it here: non-zero, ``ok`` false, no phase."""
    assert chip_smoke.main(argv) == 1
    lines = _lines(capsys)
    assert len(lines) == 1
    last = lines[-1]
    assert last["ok"] is False
    assert set(last["device"]) == {"platform", "kind", "count"}
    assert last["device"]["platform"] == "cpu"


def test_wrong_device_count_fails(capsys, no_subprocess):
    # conftest gives this process 8 CPU devices, never exactly 1 or 4
    assert chip_smoke.main([], platform="cpu") == 1
    (last,) = _lines(capsys)
    assert last["ok"] is False and last["device"]["count"] == 8


def test_default_phases_at_tiny_size(tiny, capsys, no_subprocess,
                                     monkeypatch):
    monkeypatch.setattr(
        chip_smoke, "_device_doc",
        lambda: {"platform": "cpu", "kind": "cpu", "count": 1},
    )
    rc = chip_smoke.main([], platform="cpu", sizes=tiny)
    lines = _lines(capsys)
    assert [x.get("phase") for x in lines] == [
        "start", "simulate", "simulate", "agree", "sweep", None,
    ], lines
    assert all(x["ok"] for x in lines[1:]), lines
    assert rc == 0
    assert lines[-1] == {
        "ok": True,
        "device": {"platform": "cpu", "kind": "cpu", "count": 1},
    }
    big, tree = lines[1], lines[2]
    assert (big["hops_per_request"], tree["hops_per_request"]) == (
        1000.0, 111.0)
    for sim in (big, tree):
        assert sim["requests"] >= 512
        assert sim["persistent_cache_misses"] > 0
        assert sim["compile_cache_quarantined"] == 0
        assert sim["setup_s"] > 0 and sim["steady_s"] > 0
    assert lines[3]["max_quantile_rel_gap"] <= chip_smoke.AGREE_RTOL
    assert (lines[4]["runs"], lines[4]["failed"],
            lines[4]["degraded"]) == (4, 0, 0)


def test_mesh_phase_at_tiny_size(tiny, tmp_path, capsys, no_subprocess):
    """The ``--chips 4`` phase on the virtual CPU mesh: the default
    mesh spans every device, 2x2 four, and both agree with one."""
    assert chip_smoke.phase_mesh(tiny, str(tmp_path))
    (line,) = _lines(capsys)
    assert line["phase"] == "mesh" and line["devices"] == 8
    (default,) = line["default_mesh"]["sharded_runs"]
    (grid,) = line["mesh_2x2"]["sharded_runs"]
    assert default["mesh"] == {"data": 8, "svc": 1}
    assert grid["mesh"] == {"data": 2, "svc": 2}
    assert len(set(default["shard_devices"]["count"])) == 8
    assert len(set(grid["shard_devices"]["latency_hist"])) == 4


def test_a_failed_phase_fails_the_run(tiny, capsys, monkeypatch):
    monkeypatch.setattr(
        chip_smoke, "_device_doc",
        lambda: {"platform": "cpu", "kind": "cpu", "count": 1},
    )
    monkeypatch.setattr(chip_smoke, "phase_simulate", lambda *a: True)
    monkeypatch.setattr(chip_smoke, "phase_sweep", lambda *a: True)

    def crash(sizes):
        raise RuntimeError("boom")

    monkeypatch.setattr(chip_smoke, "phase_agree", crash)
    assert chip_smoke.main([], platform="cpu", sizes=tiny) == 1
    lines = _lines(capsys)
    assert lines[-2]["phase"] == "crashed"
    assert lines[-1]["ok"] is False
