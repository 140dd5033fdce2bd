"""rollout-smoke: the progressive-delivery acceptance scenario
end-to-end.

An entry -> worker chain pushes a BAD canary (``canary:
{error_rate: 30%}``) through a 5% -> 25% -> 100% step schedule twice:

- CLOSED-LOOP (the rollout controller): the 5% step's bake window
  accumulates ``min_samples`` canary hops, the error-share gate trips
  on the canary's ~30% 500-rate, and the controller ROLLS BACK (weight
  -> 0, retries exhausted -> FAILED) before the bad push ever sees
  real traffic;
- OPEN-LOOP twin (the pre-rollout ``churn`` idiom: traffic-shift
  weights as pure clocks): the SAME schedule with its gates disabled
  promotes on every bake boundary and marches the bad canary to 100%
  of traffic, burning error budget for the rest of the run.

Asserts the acceptance criteria: the bad canary is detected and
reverted within its first bake window, the canary's traffic exposure
stays pinned low (a few percent of hops), the gate demonstrably SAW
the bad arm (observed canary error share ~30%), the closed-loop run's
total client-error share is STRICTLY below the open-loop twin's, and
the 4-shard sharded trajectory is bit-equal to the emulated twin.
``make rollout-smoke`` wires it into CI-style checks next to the
other smokes.
"""
from __future__ import annotations

import sys


TOPOLOGY = {
    "services": [
        {
            "name": "entry",
            "isEntrypoint": True,
            "numReplicas": 4,
            "script": [{"call": "worker"}],
        },
        {"name": "worker", "numReplicas": 4},
    ],
}

STEPS = ["5%", "25%", "100%"]
BAKE_S = 2.0

# the closed-loop controller: min-sample-guarded error-share gate,
# no retry budget — a trip parks the rollout FAILED at weight 0
GATED = {
    "worker": {
        "steps": STEPS,
        "bake": BAKE_S,
        "gates": {"min_samples": 100, "max_error_share": "10%"},
        "rollback": {"cooldown": 30.0, "max_retries": 0},
        "canary": {"error_rate": "30%"},
    }
}

# the open-loop twin: identical schedule and canary physics, gates
# disabled (inf thresholds, min_samples 1) — promotion becomes a pure
# bake clock, exactly the `churn` traffic-shift idiom this controller
# replaces
CLOCKED = {
    "worker": {
        **GATED["worker"],
        "gates": {
            "min_samples": 1,
            "max_error_ratio": float("inf"),
            "max_latency_ratio": float("inf"),
        },
    }
}


def main() -> int:
    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", 4)
    import numpy as np

    from isotope_tpu.compiler import compile_graph, compile_rollouts
    from isotope_tpu.models.graph import ServiceGraph
    from isotope_tpu.sim import LoadModel, SimParams, Simulator
    from isotope_tpu.sim import rollout as roll_mod

    def build(rollouts_block):
        doc = dict(TOPOLOGY, rollouts=rollouts_block)
        g = ServiceGraph.decode(doc)
        compiled = compile_graph(g)
        return compiled, compile_rollouts(g, compiled)

    params = SimParams(timeline=True, timeline_window_s=0.5)
    load = LoadModel(kind="open", qps=2_000.0)
    n, block = 30_000, 2_000
    key = jax.random.PRNGKey(11)
    args = dict(block_size=block, window_s=0.5)

    compiled_g, tables_g = build(GATED)
    closed = Simulator(compiled_g, params, rollouts=tables_g)
    s_c, tl_c, roll_c = closed.run_rollouts(load, n, key, **args)

    compiled_o, tables_o = build(CLOCKED)
    open_sim = Simulator(compiled_o, params, rollouts=tables_o)
    s_o, tl_o, roll_o = open_sim.run_rollouts(load, n, key, **args)

    rc = 0

    def check(name, ok, detail):
        nonlocal rc
        status = "ok" if ok else "FAIL"
        print(f"  {status:<5} {name}: {detail}")
        if not ok:
            rc = 1

    doc_c = roll_mod.to_doc(compiled_g, roll_c, tables_g)
    doc_o = roll_mod.to_doc(compiled_o, roll_o, tables_o)
    w_c, w_o = doc_c["services"]["worker"], doc_o["services"]["worker"]

    onsets = w_c["rollback_onsets_s"]
    check(
        "rollback within the bake window",
        w_c["rollbacks"] == 1.0 and onsets
        and 0.0 < onsets[0] <= BAKE_S,
        f"rolled back at t={onsets[0] if onsets else None}s "
        f"(bake {BAKE_S:g}s)",
    )
    check(
        "retries exhausted -> FAILED at weight 0",
        w_c["state"] == "failed" and w_c["final_weight"] == 0.0,
        f"state={w_c['state']!r} final_weight={w_c['final_weight']}",
    )
    share_seen = max(w_c["canary_error_share"], default=0.0)
    check(
        "gate saw the bad arm",
        share_seen >= 0.2,
        f"observed canary error share {share_seen:.1%} "
        "(configured 30%)",
    )
    arr = np.asarray(roll_c.ver_arrivals, np.float64)
    widx = list(tables_g.names).index("worker")
    exposure = arr[widx, 1].sum() / max(arr[widx].sum(), 1.0)
    check(
        "canary exposure pinned low",
        exposure < 0.05,
        f"canary served {exposure:.2%} of worker hops "
        "(weight capped at the 5% step)",
    )
    # total error share is HOP-level (the 500s the worker's callers
    # observe): per executable.go:132-143 semantics a callee 500 does
    # not fail the caller, so client_error would hide the burn
    arr_o = np.asarray(roll_o.ver_arrivals, np.float64)
    err_o = np.asarray(roll_o.ver_errors, np.float64)
    err_c_tot = np.asarray(roll_c.ver_errors, np.float64)
    share_closed = err_c_tot[widx].sum() / max(arr[widx].sum(), 1.0)
    share_open = err_o[widx].sum() / max(arr_o[widx].sum(), 1.0)
    check(
        "closed-loop beats the open-loop twin",
        share_closed < share_open and share_closed < 0.05,
        f"worker error share {share_closed:.2%} < open-loop "
        f"{share_open:.2%}",
    )
    check(
        "open-loop twin marched to 100%",
        w_o["final_weight"] == 1.0 and w_o["rollbacks"] == 0.0,
        f"twin final weight {w_o['final_weight']:.0%} "
        f"({w_o['promotions']:.0f} clock promotes)",
    )

    # 4-shard mesh trajectory == emulated twin, bit for bit
    from isotope_tpu.parallel import MeshSpec, ShardedSimulator, build_mesh

    sh = ShardedSimulator(
        compiled_g, build_mesh(MeshSpec(data=4, svc=1)), params,
        rollouts=tables_g,
    )
    dev = sh.run_rollouts(load, 8_000, key, **args)
    emu = sh.run_rollouts_emulated(load, 8_000, key, **args)
    leaves_d, leaves_e = jax.tree.leaves(dev), jax.tree.leaves(emu)
    bit_equal = len(leaves_d) == len(leaves_e) and all(
        np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(leaves_d, leaves_e)
    )
    check(
        "sharded == emulated twin",
        bit_equal and np.asarray(dev[2].rollbacks).sum() >= 1.0,
        f"{len(leaves_d)} leaves bit-equal across 4 shards, "
        "trip on the merged trajectory",
    )

    print()
    print(roll_mod.format_table(doc_c))
    print(
        "rollout-smoke:"
        + (" all checks passed" if rc == 0 else " FAILURES above")
    )
    return rc


if __name__ == "__main__":
    sys.exit(main())
