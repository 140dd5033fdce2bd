"""AOT executable cache + persistent compilation cache (compiler/cache.py).

The executable cache shares jitted entry points across Simulator
instances keyed by the engine shape signature; sharing must be exact —
identical shape signature (bucket bounds, block shape, feature flags)
AND identical baked constants — and any bound/flag change must miss.
"""
import os

import jax
import numpy as np
import pytest

from isotope_tpu.compiler import compile_graph
from isotope_tpu.compiler.cache import (
    array_digest,
    enable_persistent_cache,
    executable_cache,
)
from isotope_tpu.models.graph import ServiceGraph
from isotope_tpu.sim import LoadModel, SimParams, Simulator

CHAIN = """
services:
- name: a
  isEntrypoint: true
  script:
  - call: b
- name: b
  script:
  - call: c
- name: c
"""

OPEN = LoadModel(kind="open", qps=100.0)
KEY = jax.random.PRNGKey(0)


def _sim(params=SimParams()):
    return Simulator(compile_graph(ServiceGraph.from_yaml(CHAIN)), params)


def test_identical_topologies_share_one_executable():
    s1, s2 = _sim(), _sim()
    assert s1.signature == s2.signature
    f1 = s1._get(64, "open")
    f2 = s2._get(64, "open")
    assert f1 is f2  # one jitted entry point, process-wide
    # and it runs correctly for the second instance
    r = f2(KEY, np.float32(100.0), np.float32(0.0), np.float32(100.0),
           visits_pc=s2._vis_arg(100.0),
           phase_windows=s2._windows_arg(100.0, False))
    assert int(r.hop_events) == 64 * 3


def test_summary_executable_shared_and_block_size_misses():
    s1, s2 = _sim(), _sim()
    f1 = s1._get_summary(64, 2, "open", 0, None)
    f2 = s2._get_summary(64, 2, "open", 0, None)
    assert f1 is f2
    f3 = s2._get_summary(128, 2, "open", 0, None)  # block size change
    assert f3 is not f1


def test_request_shape_misses():
    s1, s2 = _sim(), _sim()
    assert s1._get(64, "open") is not s2._get(128, "open")
    assert s1._get(64, "open") is s2._get(64, "open")


def test_bucket_bound_change_misses():
    # a different waste budget changes the plan bounds => new signature
    s1 = _sim(SimParams(level_bucket_waste=1.6))
    s2 = _sim(SimParams(level_bucket_waste=64.0))
    # same topology — the plans may or may not coincide, but the
    # signature must incorporate the params either way
    assert s1.signature != s2.signature
    assert s1._get(64, "open") is not s2._get(64, "open")


def test_feature_flag_change_misses():
    s1 = _sim(SimParams())
    s2 = _sim(SimParams(service_time="deterministic"))
    s3 = _sim(SimParams(bucketed_scan=False))
    assert len({s1.signature, s2.signature, s3.signature}) == 3


def test_different_constants_same_shape_miss():
    """Same tensor shapes, different sleep constant: must NOT share."""
    other = CHAIN.replace("- name: c", "- name: c\n  script:\n  - sleep: 1ms")
    s1 = _sim()
    s2 = Simulator(compile_graph(ServiceGraph.from_yaml(other)))
    # shapes differ here (extra step) — craft a pure-constant change:
    g3 = ServiceGraph.from_yaml(CHAIN)
    g3.services[2].num_replicas = 7
    s3 = Simulator(compile_graph(g3))
    assert s1.signature != s2.signature
    assert s1.signature != s3.signature


def test_signature_stable_across_runs():
    s = _sim()
    sig = s.signature
    s.run(OPEN, 64, KEY)
    assert s.signature == sig


def _two_scripts(left, right) -> str:
    """An entry calling ``left``, ``right`` and ``leaf``: two scripted
    hops at one level, their scripts the steps given."""
    def script(steps):
        return "".join(f"  - {step}\n" for step in steps)

    return ("services:\n- name: entry\n  isEntrypoint: true\n  script:\n"
            "  - call: left\n  - call: right\n  - call: leaf\n"
            "- name: left\n  script:\n" + script(left)
            + "- name: right\n  script:\n" + script(right)
            + "- name: leaf\n")


STEPS = (["sleep: 1ms", "sleep: 2ms"], ["sleep: 1ms"])


@pytest.mark.parametrize("other, same", [
    pytest.param(None, True, id="built_twice"),
    pytest.param(STEPS, True, id="decoded_afresh"),
    pytest.param((["sleep: 1ms", "sleep: 3ms"], ["sleep: 1ms"]), False,
                 id="one_sleep_base"),
    pytest.param((["sleep: 1ms", "call: leaf"], ["sleep: 1ms"]), False,
                 id="one_step_made_a_call"),
    pytest.param((["sleep: 2ms", "sleep: 1ms"], ["sleep: 1ms"]), False,
                 id="two_steps_of_one_script_swapped"),
    pytest.param((["sleep: 1ms"], ["sleep: 1ms", "sleep: 2ms"]), False,
                 id="another_hop_of_the_level_owns_the_step"),
])
def test_signature_tells_a_levels_steps_apart(other, same):
    """What the signature digests of a level's steps is their packed
    form (``compiler.program.HopLevel``): one signature for one graph,
    however often it is decoded and built; another for a graph whose
    steps differ in a constant the traced program bakes in - whose a
    step is, where in the script, its sleep base - whatever encoding
    the level runs."""
    compiled = compile_graph(ServiceGraph.from_yaml(_two_scripts(*STEPS)))
    base = Simulator(compiled)
    if other is not None:
        compiled = compile_graph(
            ServiceGraph.from_yaml(_two_scripts(*other)))
        # every case keeps the levels' hop counts: the steps tell apart
        assert [lvl.num_hops for lvl in compiled.levels][:2] == [1, 3]
    assert (Simulator(compiled).signature == base.signature) == same


def test_array_digest_discriminates():
    a = np.arange(6, dtype=np.float32)
    assert array_digest(a) == array_digest(a.copy())
    assert array_digest(a) != array_digest(a.reshape(2, 3))
    assert array_digest(a) != array_digest(a.astype(np.float64))
    assert array_digest(a, "x") != array_digest(a, "y")
    assert array_digest(None, a) == array_digest(a)


def test_executable_cache_lru_bounds_memory():
    from isotope_tpu.compiler.cache import ExecutableCache

    c = ExecutableCache(max_entries=2)
    c.get_or_build(("a",), lambda: 1)
    c.get_or_build(("b",), lambda: 2)
    c.get_or_build(("a",), lambda: 99)   # hit, refreshes recency
    c.get_or_build(("c",), lambda: 3)    # evicts ("b",)
    assert ("a",) in c and ("c",) in c and ("b",) not in c
    assert c.hits == 1 and c.misses == 3


@pytest.fixture
def cache_mod(jax_cache_config, monkeypatch):
    """compiler/cache.py with its wired-dir memo cleared and JAX's
    variable unset (conftest restores JAX's own settings)."""
    monkeypatch.delenv(jax_cache_config.ENV_JAX_CACHE_DIR, raising=False)
    return jax_cache_config


def _dir_updates(monkeypatch):
    """Record every ``jax.config.update`` of the cache DIRECTORY."""
    seen = []
    real = jax.config.update

    def spy(name, value):
        if name == "jax_compilation_cache_dir":
            seen.append(value)
        return real(name, value)

    monkeypatch.setattr(jax.config, "update", spy)
    return seen


@pytest.mark.parametrize("request_", [None, "on", "/somewhere/else"])
def test_env_dir_serves_and_jax_setting_is_untouched(
    cache_mod, tmp_path, monkeypatch, request_
):
    """JAX_COMPILATION_CACHE_DIR set: that directory, whatever was
    asked, and no update of JAX's own directory setting."""
    d = str(tmp_path / "env-cache")
    monkeypatch.setenv(cache_mod.ENV_JAX_CACHE_DIR, d)
    seen = _dir_updates(monkeypatch)
    assert enable_persistent_cache(request_) == d
    assert seen == []
    assert os.path.isdir(d)
    assert cache_mod.persistent_cache_dir() == d


def test_unset_env_not_asked_is_a_noop(cache_mod, monkeypatch):
    seen = _dir_updates(monkeypatch)
    assert enable_persistent_cache() is None
    assert seen == [] and cache_mod.persistent_cache_dir() is None


def test_unset_env_default_dir_ignores_cwd(
    cache_mod, tmp_path, monkeypatch
):
    """Asked with the variable unset: <checkout>/.xla-cache from the
    package's own location, from whichever cwd the run started."""
    checkout = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    want = os.path.join(checkout, ".xla-cache")
    assert cache_mod.DEFAULT_CACHE_DIR == want
    made = []
    monkeypatch.setattr(
        cache_mod.os, "makedirs", lambda p, **kw: made.append(p)
    )
    for cwd in (tmp_path, tmp_path.parent):
        monkeypatch.chdir(cwd)
        monkeypatch.setattr(cache_mod, "_persistent_dir", None)
        assert enable_persistent_cache("on") == want
    assert made == [want, want]
    assert jax.config.jax_compilation_cache_dir == want


@pytest.mark.parametrize("off", ["off", "OFF", "0", "none", ""])
def test_off_disables(cache_mod, off, monkeypatch):
    seen = _dir_updates(monkeypatch)
    assert enable_persistent_cache(off) is None
    assert seen == [] and cache_mod.persistent_cache_dir() is None


def test_off_switches_jax_cache_off_under_env_dir(
    cache_mod, tmp_path, monkeypatch
):
    monkeypatch.setenv(cache_mod.ENV_JAX_CACHE_DIR, str(tmp_path))
    assert enable_persistent_cache("off") is None
    assert jax.config.jax_enable_compilation_cache is False
    # an un-asked enable (the sharded runner's) does not undo "off" ...
    assert enable_persistent_cache() is None
    assert jax.config.jax_enable_compilation_cache is False
    # ... asking again does
    assert enable_persistent_cache("on") == str(tmp_path)
    assert jax.config.jax_enable_compilation_cache is True
    assert enable_persistent_cache() == str(tmp_path)


def test_explicit_dir_when_env_unset(cache_mod, tmp_path):
    d = tmp_path / "xla"
    got = enable_persistent_cache(str(d))
    assert got == str(d) and os.path.isdir(got)
    assert jax.config.jax_compilation_cache_dir == got
    # idempotent re-enable
    assert enable_persistent_cache(str(d)) == got


def test_enable_never_scans_what_jax_owns(cache_mod, tmp_path):
    """Enabling touches no file in the directory: an entry another
    live process may still be writing (empty, or off its recorded
    digest) and JAX's ``-atime`` bookkeeping all survive."""
    d = tmp_path / "xla"
    d.mkdir()
    (d / "jit_half_written").write_bytes(b"")
    (d / "jit_x-atime").write_bytes(b"\x00" * 8)
    (d / cache_mod.DIGEST_SIDECAR).write_text('{"jit_y": "stale"}')
    (d / "jit_y").write_bytes(b"rewritten")
    enable_persistent_cache(str(d))
    assert sorted(os.listdir(d)) == sorted(
        ["jit_half_written", "jit_x-atime", "jit_y",
         cache_mod.DIGEST_SIDECAR]
    )
    # the on-demand scan (corruption reaction) skips -atime files too
    stats = cache_mod.scan_cache_dir(str(d))
    assert "jit_x-atime" not in stats["quarantined"]
    assert (d / "jit_x-atime").exists()


def test_persistent_cache_writes_entries(cache_mod, tmp_path):
    """Compiling through the wired cache leaves entries on disk."""
    d = str(tmp_path / "xla")
    enable_persistent_cache(d)
    sim = _sim(SimParams(cpu_time_s=1.0 / 9_999.0))  # fresh program
    sim.run(OPEN, 32, KEY)
    assert os.listdir(d), "no persistent cache entries written"


def test_telemetry_runs_ask_for_the_cache():
    from isotope_tpu.commands.common import default_compile_cache

    assert default_compile_cache(None, "on") == "on"
    assert default_compile_cache(None, "detail") is None
    assert default_compile_cache(None, None) is None
    assert default_compile_cache("off", "on") == "off"


def test_executable_cache_stats_visible():
    executable_cache.clear()
    _sim()._get(48, "open")
    before = executable_cache.hits
    _sim()._get(48, "open")
    assert executable_cache.hits == before + 1
