"""Runner / sweep driver + CLI tests."""
import json
import pathlib

import pytest

from isotope_tpu import cli
from isotope_tpu.runner import load_toml, run_experiment

TOPO = pathlib.Path(__file__).parent.parent / "examples/topologies/canonical.yaml"


def small_toml(tmp_path, **sim_overrides):
    sim = {"num_requests": 2000, "seed": 7}
    sim.update(sim_overrides)
    sim_lines = "\n".join(
        f'{k} = {json.dumps(v)}' for k, v in sim.items()
    )
    cfg = tmp_path / "exp.toml"
    cfg.write_text(
        f"""
topology_paths = ["{TOPO}"]
environments = ["NONE", "ISTIO"]

[client]
qps = [500]
num_concurrent_connections = [8]
duration = "120s"
load_kind = "open"

[sim]
{sim_lines}
"""
    )
    return cfg


def test_load_toml_schema(tmp_path):
    cfg = load_toml(small_toml(tmp_path))
    assert cfg.topology_paths == (str(TOPO),)
    assert [e.name for e in cfg.environments] == ["NONE", "ISTIO"]
    assert cfg.qps == (500.0,)
    assert cfg.connections == (8,)
    assert cfg.duration_s == 120.0
    assert cfg.num_requests == 2000
    # ISTIO default == "both": two proxy passes of per-edge latency tax
    istio = cfg.environments[1]
    assert istio.client_proxy and istio.server_proxy
    base = cfg.sim_params()
    assert istio.apply(base).network.base_latency_s == pytest.approx(
        base.network.base_latency_s + 500e-6
    )


def test_load_toml_qps_max_and_env_override(tmp_path):
    cfg_path = tmp_path / "exp.toml"
    cfg_path.write_text(
        f"""
topology_paths = ["{TOPO}"]
environments = ["CUSTOM"]

[environment.CUSTOM]
extra_hop_latency = "2ms"

[client]
qps = "max"
"""
    )
    cfg = load_toml(cfg_path)
    assert cfg.qps == (None,)
    assert cfg.environments[0].extra_hop_latency_s == pytest.approx(0.002)


def test_unknown_environment_rejected(tmp_path):
    cfg_path = tmp_path / "exp.toml"
    cfg_path.write_text(
        f'topology_paths = ["{TOPO}"]\nenvironments = ["WAT"]\n'
    )
    with pytest.raises(ValueError, match="WAT"):
        load_toml(cfg_path)


def test_run_experiment_grid_and_artifacts(tmp_path):
    cfg = load_toml(small_toml(tmp_path))
    out = tmp_path / "results"
    results = run_experiment(cfg, out_dir=out)
    # 1 topology x 2 envs x 1 conn x 1 qps
    assert len(results) == 2
    labels = [r.label for r in results]
    assert labels == [
        "canonical_none_500qps_8c",
        "canonical_istio_500qps_8c",
    ]
    # ISTIO pays the sidecar tax on every hop
    assert results[1].flat["p50"] > results[0].flat["p50"]
    # artifacts
    lines = (out / "results.jsonl").read_text().splitlines()
    assert len(lines) == 2 and json.loads(lines[0])["Labels"] == labels[0]
    csv = (out / "benchmark.csv").read_text().splitlines()
    assert csv[0].startswith("Labels,StartTime")
    assert len(csv) == 3
    for r in results:
        assert (out / f"{r.label}.json").exists()
        prom = (out / f"{r.label}.prom").read_text()
        assert "service_request_duration_seconds" in prom


def test_cli_simulate_flat(tmp_path, capsys):
    rc = cli.main(
        [
            "simulate",
            str(TOPO),
            "--qps", "200",
            "--duration", "100s",
            "--load-kind", "open",
            "--max-requests", "2000",
            "--flat",
            "--prometheus", str(tmp_path / "m.prom"),
        ]
    )
    assert rc == 0
    cap = capsys.readouterr()
    flat = json.loads(cap.out)
    assert flat["RequestedQPS"] == 200
    assert flat["p99"] >= flat["p50"] > 0
    # the five service series + the two sim-side resource series
    assert (tmp_path / "m.prom").read_text().count("# TYPE") == 7


def test_cli_sweep(tmp_path, capsys):
    cfg = small_toml(tmp_path)
    out = tmp_path / "res"
    rc = cli.main(["sweep", str(cfg), "-o", str(out)])
    assert rc == 0
    assert (out / "benchmark.csv").exists()


def test_cli_simulate_unknown_environment_errors(capsys):
    rc = cli.main(["simulate", str(TOPO), "--environment", "NOPE"])
    assert rc == 1
    assert "unknown environment" in capsys.readouterr().err


def test_heavy_tail_toml_plumbing(tmp_path):
    cfg = load_toml(
        small_toml(tmp_path, service_time="pareto", service_time_param=1.5)
    )
    params = cfg.sim_params()
    assert params.service_time == "pareto"
    assert params.service_time_param == 1.5
    # and it actually runs
    results = run_experiment(
        load_toml(small_toml(tmp_path, service_time="lognormal",
                             service_time_param=2.0, num_requests=500))
    )
    assert results and results[0].flat["p50"] > 0


@pytest.mark.slow
@pytest.mark.slow
def test_sweep_profile_captures_traces(tmp_path):
    import glob

    from isotope_tpu.runner import load_toml, run_experiment

    cfg = small_toml(tmp_path, num_requests=500)
    prof = tmp_path / "prof"
    run_experiment(load_toml(cfg), profile_dir=str(prof))
    # one trace directory per run, each with an xplane dump
    runs = sorted(p.name for p in prof.iterdir())
    assert runs == [
        "canonical_istio_500qps_8c", "canonical_none_500qps_8c"
    ]
    for r in runs:
        assert glob.glob(str(prof / r / "**" / "*.xplane.pb"),
                         recursive=True)


# -- an output asked for by name is written, or the call fails --------------


def _observed_argv(tmp_path, *flags):
    return [
        "simulate", str(TOPO), "--qps", "200", "--duration", "2s",
        "--load-kind", "open", "--seed", "3", "--max-requests", "400",
        "--prometheus", str(tmp_path / "run.prom"), *flags,
    ]


def _break(monkeypatch, method):
    from isotope_tpu.sim.engine import Simulator

    def boom(self, *args, **kwargs):
        raise RuntimeError(f"planted: {method} cannot run")

    monkeypatch.setattr(Simulator, method, boom)
    monkeypatch.setenv("ISOTOPE_MESH", "1x1")


@pytest.mark.parametrize("method, flags, out, counter", [
    ("run_attributed", ("--attribution",), "--blame-out",
     "attribution_pass_failures"),
    ("run_timeline", ("--timeline", "1s"), "--timeline-out",
     "timeline_pass_failures"),
])
def test_a_named_output_its_pass_cannot_write_fails_the_call(
        tmp_path, capsys, monkeypatch, method, flags, out, counter):
    from isotope_tpu import telemetry

    _break(monkeypatch, method)
    before = telemetry.snapshot().counters.get(counter, 0)
    named = tmp_path / "asked-for.json"
    rc = cli.main(_observed_argv(tmp_path, *flags, out, str(named)))
    cap = capsys.readouterr()
    assert rc == 1
    assert not named.exists()
    assert f"error: {out} {named} was not written" in cap.err
    assert "planted" in cap.err            # the pass's own warning
    # after the artifacts it has: the Fortio document and the exposition
    assert json.loads(cap.out)["DurationHistogram"]["Count"] >= 400
    assert (tmp_path / "run.prom").stat().st_size > 0
    assert telemetry.snapshot().counters[counter] == before + 1


@pytest.mark.parametrize("method, flags, counter", [
    ("run_attributed", ("--attribution",), "attribution_pass_failures"),
    ("run_timeline", ("--timeline", "1s"), "timeline_pass_failures"),
])
def test_a_table_on_stderr_alone_stays_best_effort(
        tmp_path, capsys, monkeypatch, method, flags, counter):
    from isotope_tpu import telemetry

    _break(monkeypatch, method)
    before = telemetry.snapshot().counters.get(counter, 0)
    rc = cli.main(_observed_argv(tmp_path, *flags))
    cap = capsys.readouterr()
    assert rc == 0
    assert "warning" in cap.err and "error:" not in cap.err
    assert json.loads(cap.out)["DurationHistogram"]["Count"] >= 400
    assert telemetry.snapshot().counters[counter] == before + 1


def test_named_outputs_are_written_where_the_passes_run(tmp_path, capsys,
                                                        monkeypatch):
    monkeypatch.setenv("ISOTOPE_MESH", "1x1")
    rc = cli.main(_observed_argv(
        tmp_path, "--attribution", "--blame-out", str(tmp_path / "b.json"),
        "--timeline", "1s", "--timeline-out", str(tmp_path / "t.json")))
    capsys.readouterr()
    assert rc == 0
    assert json.loads((tmp_path / "b.json").read_text())[
        "schema"] == "isotope-blame/v1"
    timeline = json.loads((tmp_path / "t.json").read_text())
    assert timeline["schema"] == "isotope-timeline/v1"
    # the run totals in seconds stand beside the rounded levels
    for row in timeline["services"].values():
        assert row["in_flight_s"] == pytest.approx(
            sum(row["in_flight"]) * timeline["window_s"], abs=1e-5)
        assert 0.0 < row["busy_s"] <= row["in_flight_s"] * (1 + 1e-5)
