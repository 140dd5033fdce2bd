"""Resilient execution supervisor (isotope_tpu/resilience/).

Pins the tentpole's contracts: the error taxonomy classifies real and
injected failures, transient retries back off deterministically, the
OOM degradation ladder completes a sharded run with results identical
(<= 1 f32 ULP — measured bit-exact on CPU) to a clean run, corrupted
persistent-cache entries quarantine instead of crashing, numeric
sentinels catch NaN/negative outputs (and localize the segment in
detail mode), and the no-fault default path gains zero sync points.
"""
import json
import pathlib

import jax
import jax.tree_util as jtu
import numpy as np
import pytest

from isotope_tpu import telemetry
from isotope_tpu.compiler import cache as compile_cache, compile_graph
from isotope_tpu.models.graph import ServiceGraph
from isotope_tpu.parallel import ShardedSimulator, make_mesh
from isotope_tpu.resilience import (
    DETERMINISTIC,
    RESOURCE_EXHAUSTED,
    TRANSIENT,
    InjectedFault,
    NumericSentinelError,
    ResiliencePolicy,
    backoff_seconds,
    call_with_retries,
    classify,
    execution_rungs,
    faults,
    run_ladder,
)
from isotope_tpu.resilience import sentinels
from isotope_tpu.sim import LoadModel, Simulator

CHAIN = """
services:
- name: a
  isEntrypoint: true
  script: [{call: b}]
- name: b
  script: [{call: c}]
- name: c
"""

FORK = """
services:
- name: entry
  isEntrypoint: true
  script:
  - - call: x
    - call: y
- name: x
- name: y
  script: [{call: z}]
- name: z
"""

OPEN = LoadModel(kind="open", qps=2000.0)
KEY = jax.random.PRNGKey(11)
NOSLEEP = ResiliencePolicy(sleep=lambda s: None)


@pytest.fixture(autouse=True)
def clean_state():
    faults.clear()
    telemetry.reset()
    yield
    faults.clear()
    telemetry.reset()
    telemetry.disable()


# -- taxonomy --------------------------------------------------------------


@pytest.mark.parametrize(
    "exc,want",
    [
        (RuntimeError("RESOURCE_EXHAUSTED: Out of memory allocating "
                      "268435456 bytes"), RESOURCE_EXHAUSTED),
        (RuntimeError("Failed to allocate request for 2.0GiB"),
         RESOURCE_EXHAUSTED),
        (MemoryError(), RESOURCE_EXHAUSTED),
        (RuntimeError("UNAVAILABLE: Socket closed"), TRANSIENT),
        (RuntimeError("DEADLINE_EXCEEDED: RPC timed out"), TRANSIENT),
        (ConnectionResetError("peer reset"), TRANSIENT),
        (TimeoutError(), TRANSIENT),
        (ValueError("shapes (3,) and (4,) not aligned"), DETERMINISTIC),
        (RuntimeError("INVALID_ARGUMENT: bad operand"), DETERMINISTIC),
        (NumericSentinelError("NaN"), DETERMINISTIC),
    ],
)
def test_classify(exc, want):
    assert classify(exc) == want


def test_injected_faults_classify_like_their_shape():
    faults.install("oom:sharded.compute:1,transient:cache.load:1")
    with pytest.raises(InjectedFault) as oom:
        faults.check("sharded.compute")
    with pytest.raises(InjectedFault) as tr:
        faults.check("cache.load")
    assert classify(oom.value) == RESOURCE_EXHAUSTED
    assert classify(tr.value) == TRANSIENT
    # budgets are consumed: the sites pass afterwards
    faults.check("sharded.compute")
    faults.check("cache.load")
    assert telemetry.counter_get("faults_injected") == 2.0


def test_fault_spec_validation():
    with pytest.raises(ValueError, match="unknown fault kind"):
        faults.install("explode:engine.run:1")
    with pytest.raises(ValueError, match="nan faults target segments"):
        faults.install("nan:engine.run:1")
    with pytest.raises(ValueError, match="bad fault spec"):
        faults.install("oom")
    # a typo'd site must raise AT PARSE TIME with the valid-site list,
    # not parse fine and silently never fire
    with pytest.raises(ValueError, match="sharded.gather"):
        faults.install("oom:sharded.gater:1")
    for site in faults.VALID_SITES:
        faults.install(f"oom:{site}:1")  # every documented site parses
    faults.clear()


# -- retry / backoff --------------------------------------------------------


def test_backoff_deterministic_and_bounded():
    p = ResiliencePolicy()
    seq = [backoff_seconds("engine.run", a, p) for a in range(8)]
    assert seq == [backoff_seconds("engine.run", a, p) for a in range(8)]
    assert all(0 < s <= p.backoff_cap_s for s in seq)
    assert seq[1] > seq[0]  # exponential growth under the cap
    # jitter decorrelates sites
    assert backoff_seconds("sharded.gather", 0, p) != seq[0]


def test_transient_retries_then_succeeds():
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise ConnectionResetError("blip")
        return "ok"

    slept = []
    p = ResiliencePolicy(max_retries=3, sleep=slept.append)
    assert call_with_retries(flaky, "t.site", p) == "ok"
    assert calls["n"] == 3
    assert len(slept) == 2
    assert telemetry.counter_get("retries_total") == 2.0


def test_retry_budget_exhausts():
    def always():
        raise TimeoutError("never")

    with pytest.raises(TimeoutError):
        call_with_retries(
            always, "t.site", ResiliencePolicy(max_retries=2,
                                               sleep=lambda s: None)
        )
    assert telemetry.counter_get("retries_total") == 2.0


def test_deterministic_error_not_retried():
    calls = {"n": 0}

    def boom():
        calls["n"] += 1
        raise ValueError("bad shape")

    with pytest.raises(ValueError):
        call_with_retries(boom, "t.site", NOSLEEP)
    assert calls["n"] == 1
    assert telemetry.counter_get("retries_total") == 0.0


# -- the ladder ------------------------------------------------------------


def test_ladder_descends_on_oom_only():
    seen = []

    def rung(name, fail):
        def thunk():
            seen.append(name)
            if fail:
                raise RuntimeError("RESOURCE_EXHAUSTED: injected")
            return name
        return (name, thunk)

    out, degraded = run_ladder(
        [rung("a", True), rung("b", True), rung("c", False)], NOSLEEP
    )
    assert (out, degraded) == ("c", "c")
    assert seen == ["a", "b", "c"]
    assert telemetry.counter_get("degradations_total") == 2.0
    assert telemetry.get_meta("degraded_to") == "c"
    # Prometheus: first-class series, not an events_total label
    assert "isotope_engine_degradations_total 2" in (
        telemetry.prometheus_text()
    )


def test_ladder_respects_no_degrade():
    def oom():
        raise RuntimeError("RESOURCE_EXHAUSTED: no")

    with pytest.raises(RuntimeError):
        run_ladder(
            [("a", oom), ("b", lambda: "b")],
            ResiliencePolicy(degrade=False, sleep=lambda s: None),
        )


def test_ladder_undegraded_run_sets_no_meta():
    out, degraded = run_ladder([("a", lambda: 1)], NOSLEEP)
    assert (out, degraded) == (1, None)
    assert telemetry.get_meta("degraded_to") is None
    assert telemetry.counter_get("degradations_total") == 0.0


# -- acceptance: injected sharded OOM completes bit-identically ------------


def _ulp_diff(a, b) -> float:
    a, b = np.asarray(a), np.asarray(b)
    if a.dtype == bool:
        return 0.0 if (a == b).all() else np.inf
    a64, b64 = a.astype(np.float64), b.astype(np.float64)
    same = (a64 == b64) | (np.isinf(a64) & np.isinf(b64)
                           & (np.sign(a64) == np.sign(b64)))
    sp = np.spacing(
        np.maximum(np.abs(a), np.abs(b)).astype(np.float32)
    ).astype(np.float64)
    with np.errstate(invalid="ignore"):  # inf - inf on the `same` mask
        diff = np.abs(a64 - b64) / np.where(sp > 0, sp, 1.0)
    return float(np.max(np.where(same, 0.0, diff)))


@pytest.mark.slow
def test_sharded_gather_oom_degrades_to_identical_results():
    """ISSUE acceptance: OOM injected at sharded.gather -> the ladder
    completes the run on the single-device rung with every summary
    field within 1 f32 ULP of the clean sharded run, and the
    degradation is counted in the Prometheus exposition."""
    compiled = compile_graph(ServiceGraph.from_yaml(FORK))
    sharded = ShardedSimulator(compiled, make_mesh(4, 2))
    n = 8192
    clean = sharded.run(OPEN, n, KEY, block_size=1024, trim=True)
    jax.block_until_ready(clean.count)

    telemetry.reset()
    faults.install("oom:sharded.gather:2")  # rung 0 AND half-block fail
    rungs = execution_rungs(
        sharded.sim, sharded, True, OPEN, n, KEY, 1024, trim=True
    )
    summary, degraded = run_ladder(rungs, NOSLEEP, site_prefix="engine")
    assert degraded == "single-device"
    assert telemetry.counter_get("degradations_total") >= 1.0
    prom = telemetry.prometheus_text()
    line = next(
        ln for ln in prom.splitlines()
        if ln.startswith("isotope_engine_degradations_total")
    )
    assert float(line.split()[-1]) >= 1.0

    clean_leaves = jtu.tree_flatten_with_path(clean)[0]
    got_leaves = jtu.tree_flatten_with_path(summary)[0]
    assert len(clean_leaves) == len(got_leaves)
    for (path, want), (_, got) in zip(clean_leaves, got_leaves):
        assert _ulp_diff(want, got) <= 1.0, jtu.keystr(path)


def test_transient_compute_fault_retries_to_identical_results():
    compiled = compile_graph(ServiceGraph.from_yaml(CHAIN))
    sharded = ShardedSimulator(compiled, make_mesh(4, 2))
    n = 4096
    clean = sharded.run(OPEN, n, KEY, block_size=1024)
    jax.block_until_ready(clean.count)
    faults.install("transient:sharded.compute:1")
    rungs = execution_rungs(
        sharded.sim, sharded, True, OPEN, n, KEY, 1024, trim=False
    )
    summary, degraded = run_ladder(rungs, NOSLEEP)
    assert degraded is None
    assert telemetry.counter_get("retries_total") == 1.0
    for (path, want), (_, got) in zip(
        jtu.tree_flatten_with_path(clean)[0],
        jtu.tree_flatten_with_path(summary)[0],
    ):
        assert _ulp_diff(want, got) == 0.0, jtu.keystr(path)


def test_single_device_ladder_halves_block():
    sim = Simulator(compile_graph(ServiceGraph.from_yaml(CHAIN)))
    faults.install("oom:engine.run:1")
    rungs = execution_rungs(sim, None, False, OPEN, 2048, KEY, 1024)
    summary, degraded = run_ladder(rungs, NOSLEEP)
    assert degraded == "half-block"
    assert float(summary.count) >= 2048


# -- the summary crosses to the host once ----------------------------------

POLICED = """
services:
- name: entry
  isEntrypoint: true
  numReplicas: 4
  script:
  - call: {service: worker, timeout: 850us, retries: 2}
- name: worker
  numReplicas: 4
policies:
  worker:
    breaker: {max_pending: 6, max_connections: 64,
              consecutive_errors: 5, base_ejection: 2s}
"""


def _finished_and_device(rung: str):
    """``(finished, device)``: what the named rung hands on, and the
    summary of the same run as its entry point leaves it."""
    from isotope_tpu.metrics import MetricsCollector

    n, block = 1024, 512
    if rung == "protected":
        from isotope_tpu.compiler import compile_policies
        from isotope_tpu.runner.run import _protected_call
        from isotope_tpu.sim import SimParams

        graph = ServiceGraph.from_yaml(POLICED)
        compiled = compile_graph(graph)
        sim = Simulator(
            compiled, SimParams(timeline=True, timeline_window_s=0.5),
            policies=compile_policies(graph, compiled),
        )
        kwargs = dict(trim=True, window_s=0.5,
                      collector=MetricsCollector(compiled))
        out = _protected_call(sim, "run_policies", ("scan", block, "dev"),
                              OPEN, n, KEY, kwargs)
        ref = sim.run_policies(OPEN, n, KEY, block_size=block, **kwargs)
        assert len(out) == len(ref) == 3
        # what rides beside the summary goes on as it came
        for got, want in zip(jax.tree.leaves(out[1:]),
                             jax.tree.leaves(ref[1:])):
            assert isinstance(got, jax.Array)
            assert np.array_equal(np.asarray(got), np.asarray(want))
        return out[0], ref[0]
    compiled = compile_graph(ServiceGraph.from_yaml(FORK))
    if rung == "single-device":
        sharded = ShardedSimulator(compiled, make_mesh(4, 2))
        rungs = execution_rungs(
            sharded.sim, sharded, True, OPEN, n, KEY, block, trim=True
        )
        device = sharded.run_emulated(OPEN, n, KEY, block_size=block,
                                      trim=True)
    else:
        import contextlib

        sim = Simulator(compiled)
        collector = MetricsCollector(compiled)
        rungs = execution_rungs(
            sim, None, False, OPEN, n, KEY, block,
            collector=collector, trim=True,
        )
        eager = rung == "cpu-eager"
        with jax.disable_jit() if eager else contextlib.nullcontext():
            device = sim.run_summary(
                OPEN, n, KEY, block_size=block // 2 if eager else block,
                collector=collector, trim=True,
            )
    return dict(rungs)[rung](), device


@pytest.mark.parametrize(
    "rung", ["scan", "cpu-eager", "single-device", "protected"]
)
def test_finished_summary_is_the_device_summary_on_the_host(rung):
    """``finish_summary`` hands on ONE host copy: every leaf but the
    collector's ``metrics`` a numpy array with the device leaf's bits,
    dtype and shape, fetched once; the artifacts built from it are the
    bytes of those built from the device summary."""
    import dataclasses
    from datetime import datetime, timezone

    from isotope_tpu.metrics import (
        fortio_result_from_summary,
        window_summary_from_summary,
    )

    finished, device = _finished_and_device(rung)
    assert telemetry.counter_get("summary_fetches") == 1.0
    assert type(finished) is type(device)
    for name, got in finished._asdict().items():
        want = getattr(device, name)
        if name == "metrics":
            # the collector's series ride along where they were
            for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
                assert type(g) is type(w)
                assert np.array_equal(np.asarray(g), np.asarray(w))
            continue
        assert isinstance(got, (np.ndarray, np.generic)), name
        want = np.asarray(want)
        assert got.dtype == want.dtype and got.shape == want.shape, name
        assert got.tobytes() == want.tobytes(), name
    if rung != "single-device":     # whose merge is the host's already
        assert all(isinstance(x, jax.Array) for x in jax.tree.leaves(device))
    when = datetime(2026, 1, 1, tzinfo=timezone.utc)
    docs = [
        json.dumps(fortio_result_from_summary(
            s, OPEN, labels="x", start_time=when, response_size_bytes=128.0
        ))
        for s in (finished, device)
    ]
    assert docs[0] == docs[1]
    names = [f"s{i}" for i in range(finished.utilization.size)]
    wins = [
        json.dumps(dataclasses.asdict(window_summary_from_summary(
            s, service_names=names
        )))
        for s in (finished, device)
    ]
    assert wins[0] == wins[1]


# -- zero added sync points on the default path ----------------------------


def test_no_fault_path_adds_zero_sync_points(monkeypatch):
    """The fault hooks and supervisor plumbing must not fence the
    engine's default dispatch (the PR-2 contract extends to PR 3)."""
    sim = Simulator(compile_graph(ServiceGraph.from_yaml(CHAIN)))
    calls = {"n": 0}
    orig = jax.block_until_ready

    def counting(x):
        calls["n"] += 1
        return orig(x)

    monkeypatch.setattr(jax, "block_until_ready", counting)
    res = sim.run(OPEN, 64, KEY)
    assert calls["n"] == 0, "default path must not fence"
    monkeypatch.undo()
    assert int(res.hop_events) == 64 * 3


# -- numeric sentinels -----------------------------------------------------


def test_nan_injection_trips_summary_sentinel():
    faults.install("nan:segment:0")
    sim = Simulator(compile_graph(ServiceGraph.from_yaml(CHAIN)))
    summary = sim.run_summary(OPEN, 512, KEY, block_size=256)
    with pytest.raises(NumericSentinelError, match="NaN"):
        sentinels.check_summary(summary)
    assert telemetry.counter_get("numeric_sentinel_violations") >= 1.0


def test_nan_localized_per_segment_in_detail_mode():
    faults.install("nan:segment:0")
    telemetry.enable(detail=True)
    sim = Simulator(compile_graph(ServiceGraph.from_yaml(CHAIN)))
    sim.run(OPEN, 64, KEY)
    snap = telemetry.snapshot()
    hits = [
        k for k in snap.gauges
        if k.startswith("numeric_sentinel{") and "segment=" in k
    ]
    assert hits, "detail mode must pin the offending segment"


def test_clean_run_passes_sentinels():
    sim = Simulator(compile_graph(ServiceGraph.from_yaml(CHAIN)))
    sentinels.check_summary(sim.run_summary(OPEN, 512, KEY,
                                            block_size=256))
    sentinels.check_results(sim.run(OPEN, 64, KEY))
    assert telemetry.counter_get("numeric_sentinel_violations") == 0.0


@pytest.fixture(scope="module")
def clean_summary():
    sim = Simulator(compile_graph(ServiceGraph.from_yaml(CHAIN)))
    return jax.device_get(sim.run_summary(OPEN, 512, KEY, block_size=256))


@pytest.mark.parametrize("side", ["device", "host"])
@pytest.mark.parametrize(
    "field,value,message",
    [
        ("latency_sum", np.nan, "latency_sum: NaN"),
        ("latency_hist", np.nan, "latency_hist: NaN"),
        ("latency_min", -np.inf, "latency_min: -inf"),
        ("latency_min", -1.0, r"latency_min: negative \(-1\)"),
        ("utilization", np.nan, "utilization: NaN"),
        ("win_hi", np.inf, None),        # the trim window off
        ("utilization", 1.5, None),      # overload, not a fault
    ],
)
def test_sentinel_cases_on_device_and_host_summaries(
    clean_summary, side, field, value, message
):
    """The checks read a summary of device arrays and ``finish_summary``'s
    host copy alike: same fields, same order, same messages."""
    import jax.numpy as jnp

    leaf = np.array(getattr(clean_summary, field))
    leaf.reshape(-1)[0] = value
    summary = clean_summary._replace(**{field: leaf})
    if side == "device":
        summary = jax.tree.map(jnp.asarray, summary)
    if message is None:
        sentinels.check_summary(summary)
        assert telemetry.counter_get("numeric_sentinel_violations") == 0.0
        return
    with pytest.raises(NumericSentinelError, match=message):
        sentinels.check_summary(summary)
    assert telemetry.counter_get("numeric_sentinel_violations") == 1.0


def test_violations_come_in_the_fields_order(clean_summary):
    bad = clean_summary._replace(
        utilization=np.full_like(clean_summary.utilization, np.nan),
        end_max=np.float32(-2.0),
        count=np.float32(np.nan),
    )
    with pytest.raises(
        NumericSentinelError,
        match=r"on case-7: count: NaN; end_max: negative \(-2\); "
              r"utilization: NaN \(re-run",
    ):
        sentinels.check_summary(bad, label="case-7")


def test_nan_poisoned_trace_never_shares_executables():
    """The fault plan participates in the engine signature: a poisoned
    program must not be served from (or pollute) the clean cache."""
    sim_clean = Simulator(compile_graph(ServiceGraph.from_yaml(CHAIN)))
    faults.install("nan:segment:0")
    sim_bad = Simulator(compile_graph(ServiceGraph.from_yaml(CHAIN)))
    assert sim_clean.signature != sim_bad.signature
    faults.clear()
    res = sim_clean.run(OPEN, 64, KEY)
    assert not np.isnan(np.asarray(res.client_latency)).any()


# -- compile-cache quarantine ----------------------------------------------


def test_scan_quarantines_corrupted_entries(tmp_path):
    d = tmp_path / "cache"
    d.mkdir()
    (d / "jit_good").write_bytes(b"compiled-bytes-1")
    (d / "jit_bad").write_bytes(b"compiled-bytes-2")
    (d / "jit_empty").write_bytes(b"")
    # first scan: the empty entry quarantines, digests recorded
    stats = compile_cache.scan_cache_dir(str(d))
    assert stats["quarantined"] == ["jit_empty"]
    assert stats["recorded"] == 2
    # corrupt one entry between runs (bit rot / torn write)
    (d / "jit_bad").write_bytes(b"compiled-bytes-CORRUPTED")
    stats = compile_cache.scan_cache_dir(str(d))
    assert stats["quarantined"] == ["jit_bad"]
    assert (d / "quarantine" / "jit_bad").exists()
    assert not (d / "jit_bad").exists()
    # the intact entry survives both scans
    assert (d / "jit_good").read_bytes() == b"compiled-bytes-1"
    assert telemetry.counter_get("compile_cache_quarantined") == 2.0
    sidecar = json.loads(
        (d / compile_cache.DIGEST_SIDECAR).read_text()
    )
    assert set(sidecar) == {"jit_good"}


def test_scan_tolerates_corrupt_sidecar(tmp_path):
    d = tmp_path / "cache"
    d.mkdir()
    (d / "jit_x").write_bytes(b"abc")
    (d / compile_cache.DIGEST_SIDECAR).write_text("{not json")
    stats = compile_cache.scan_cache_dir(str(d))
    assert stats["quarantined"] == []
    assert stats["recorded"] == 1


def test_corrupt_cache_load_evicts_and_retraces():
    faults.install("corrupt:cache.load:1")
    built = {"n": 0}

    def build():
        built["n"] += 1
        return "executable"

    out = compile_cache.executable_cache.get_or_build(
        ("resilience-corrupt-probe", KEY.tolist()[0]), build
    )
    assert out == "executable"
    assert built["n"] == 1  # the injected corruption fired pre-build
    assert telemetry.counter_get(
        "compile_cache_quarantine_retries"
    ) == 1.0


def test_non_corruption_build_errors_propagate():
    def build():
        raise ValueError("real bug")

    with pytest.raises(ValueError, match="real bug"):
        compile_cache.executable_cache.get_or_build(
            ("resilience-bug-probe",), build
        )


# -- runner integration: failed case recorded, sweep continues -------------

TOPO = (
    pathlib.Path(__file__).parent.parent
    / "examples/topologies/canonical.yaml"
)


def _config(tmp_path, connections="[8]"):
    from isotope_tpu.runner import load_toml

    cfg = tmp_path / "exp.toml"
    cfg.write_text(
        f"""
topology_paths = ["{TOPO}"]
environments = ["NONE"]

[client]
qps = [200, 400]
num_concurrent_connections = {connections}
duration = "30s"
load_kind = "open"

[sim]
num_requests = 1500
seed = 7
"""
    )
    return load_toml(cfg)


def test_numeric_failure_fails_case_but_not_sweep(tmp_path):
    from isotope_tpu.runner.run import run_experiment

    faults.install("nan:segment:0")
    results = run_experiment(
        _config(tmp_path), out_dir=str(tmp_path / "out"),
        policy=NOSLEEP,
    )
    faults.clear()
    assert len(results) == 2
    assert all(r.failed for r in results)
    assert all("sentinel" in (r.error or "") for r in results)
    ckpt = (tmp_path / "out" / "checkpoint.jsonl").read_text()
    recs = [json.loads(ln) for ln in ckpt.splitlines()[1:]]
    assert all(r["failed"] for r in recs)
    assert all(r["error_class"] == DETERMINISTIC for r in recs)
    # the failed sweep's CSV has no data rows (header only)
    csv = (tmp_path / "out" / "benchmark.csv").read_text().splitlines()
    assert len(csv) == 1

    # resume with the fault gone: both cases retry and complete
    ran = []
    results = run_experiment(
        _config(tmp_path), out_dir=str(tmp_path / "out"),
        progress=ran.append, policy=NOSLEEP,
    )
    assert len(ran) == 2
    assert not any(r.failed for r in results)


def test_a_four_run_sweep_fetches_four_summaries(tmp_path):
    from isotope_tpu.runner.run import run_experiment

    results = run_experiment(
        _config(tmp_path, connections="[8, 16]"),
        out_dir=str(tmp_path / "out"), policy=NOSLEEP,
    )
    assert len(results) == 4 and not any(r.failed for r in results)
    assert telemetry.counter_get("summary_fetches") == 4.0
