"""Regenerate examples/topologies/ (run via `make examples`).

Mirrors the coverage of the reference's isotope/example-topologies/ — a
1-service baseline, short chains, the canonical graph (checked in by
hand), replica-heavy fan-out trees at increasing endpoint counts, and the
two tree sizes — using this package's generators.
"""
from __future__ import annotations

import pathlib

import yaml

from isotope_tpu.models import generators
from isotope_tpu.models.graph import ServiceGraph

OUT = pathlib.Path(__file__).parent.parent / "examples" / "topologies"


def dump(name: str, doc: dict) -> None:
    ServiceGraph.decode(doc)  # must validate
    (OUT / name).write_text(
        yaml.safe_dump(doc, default_flow_style=False, sort_keys=False)
    )
    print(f"wrote {OUT / name}")


def chain(n: int) -> dict:
    services = []
    for i in range(n):
        svc: dict = {"name": f"svc-{i}"}
        if i == 0:
            svc["isEntrypoint"] = True
        if i + 1 < n:
            svc["script"] = [{"call": f"svc-{i + 1}"}]
        services.append(svc)
    return {"defaults": {"requestSize": 128, "responseSize": 128},
            "services": services}


def main() -> None:
    OUT.mkdir(parents=True, exist_ok=True)
    dump("1-service.yaml", {
        "services": [{"name": "svc-0", "isEntrypoint": True,
                      "responseSize": 1024}],
    })
    dump("chain-2-services.yaml", chain(2))
    dump("chain-3-services.yaml", chain(3))

    # replica-heavy fan-out trees: 10 services x k replicas = N endpoints
    for reps in (1, 10, 100, 1000):
        dump(
            f"10-svc_{10 * reps}-end.yaml",
            generators.tree_topology(
                num_levels=3, num_branches=9,
                num_services=10, num_replicas=reps,
            ),
        )
    dump(
        "1000-svc_2000-end.yaml",
        generators.tree_topology(
            num_levels=5, num_branches=6, num_services=1000, num_replicas=2
        ),
    )

    dump("tree-13-services.yaml",
         generators.tree_topology(num_levels=3, num_branches=3,
                                  num_replicas=6))
    dump("tree-111-services.yaml",
         generators.tree_topology(num_levels=3, num_branches=10))

    # the four realistic archetypes (create_realistic_topology.py:55-99)
    for archetype in sorted(generators.ARCHETYPES):
        dump(
            f"realistic-{archetype}-50.yaml",
            generators.realistic_topology(
                num_services=50, archetype=archetype, seed=0
            ),
        )

    # the multitier archetype at a hundred services, every callee
    # failing at the rate upstream's README documents `errorRate` with:
    # the benchmark's `powerlaw100` (benchmark/configs/powerlaw100.json)
    dump(
        "realistic-multitier-100-errors.yaml",
        generators.realistic_topology(
            num_services=100, archetype="multitier", seed=0,
            callee_error_rate="0.01%",
        ),
    )

    # the multitier archetype at the fork's committed example's size,
    # the same callee error rate, and Istio's mesh-wide default retry
    # policy (2 retries) on every call: the benchmark's
    # `multitier50_retry2` (benchmark/configs/multitier50_retry2.json),
    # whose benchmark/topologies/ copy is this file byte for byte
    dump(
        "realistic-multitier-50-errors-retries2.yaml",
        generators.with_call_policy(
            generators.realistic_topology(
                num_services=50, archetype="multitier", seed=0,
                callee_error_rate="0.01%",
            ),
            retries=2,
        ),
    )

    # the same policy on the multitier archetype at a thousand
    # services (BASELINE.json configs[2]'s size): the benchmark's
    # `multitier1000_retry2` (benchmark/configs/multitier1000_retry2.json),
    # whose benchmark/topologies/ copy is this file byte for byte
    dump(
        "realistic-multitier-1000-errors-retries2.yaml",
        generators.with_call_policy(
            generators.realistic_topology(
                num_services=1000, archetype="multitier", seed=0,
                callee_error_rate="0.01%",
            ),
            retries=2,
        ),
    )

    # the star archetype at the north star's 10,000 services: the
    # benchmark's `star10k` (benchmark/configs/star10k.json), whose
    # benchmark/topologies/ copy is this file byte for byte
    dump(
        "star-10000.yaml",
        generators.realistic_topology(
            num_services=10_000, archetype="star", seed=0
        ),
    )

    # Zipf out-degree skew with heterogeneous sleeps/error rates: the
    # ingest self-closure fixture (tools/ingest_smoke.py simulates it,
    # exports the exposition, and re-fits it back)
    dump(
        "realistic-powerlaw-100.yaml",
        generators.powerlaw_topology(
            num_services=100, exponent=2.0, seed=7,
            sleep_choices=["0", "1ms", "2ms", "4ms", "8ms"],
            error_rate_choices=["0%", "0%", "1%", "2%", "5%"],
        ),
    )


if __name__ == "__main__":
    main()
