#!/usr/bin/env python3
"""The readings the limits of ``correct`` are set from, on the chip, at
the cell's own size, in ONE process (set-up is paid once):

    python benchmark/limits.py --workload <name> --seeds 12 --first-seed <n>
    python benchmark/limits.py --workload <name> --seeds 3 --control bf16

Once: the deterministic pre-check of the cell's mix.  For each seed: one
served call of the cell.  Both are checked as run.py checks them.
Printed: per seed every number compared; at the end the largest (or, for
a floor, the smallest) each number reached.

Without ``--control`` these are the sound runs' readings, and beside
them the exact integer comparisons' controls: the served call's own
artifacts with one guarantee broken, seed by seed - one hop-event of one
service lost from the exposition, and one request dropped from the
client's count.

With ``--control bf16`` the program itself runs with a lower-precision
path planted in its collector, before anything is traced: the duration
sums and response-size sums are accumulated in bfloat16, the precision
below the float32 the configuration states.  The same calls, the same
checks: these are the control's readings, and every call has to come out
as not correct (exit code 0 then, 1 if a call passed).

This is not part of a benchmark run; run.py never calls it.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
if ROOT not in sys.path[:1]:
    sys.path.insert(0, ROOT)


def plant_bf16_collector() -> None:
    """Replace the collector's float32 accumulators of durations and
    response sizes by bfloat16 ones (the scatter-add itself runs in
    bfloat16; the counts and histograms are left alone)."""
    import jax.numpy as jnp

    from isotope_tpu.metrics import prometheus

    sound = prometheus.MetricsCollector.collect

    def collect(self, res):
        m = sound(self, res)
        sent = res.hop_sent
        svc = jnp.broadcast_to(self._hop_service, sent.shape)
        code = res.hop_error.astype(jnp.int32)

        def accumulate(values):
            return (jnp.zeros(m.duration_sum.shape, jnp.bfloat16)
                    .at[svc, code]
                    .add(jnp.where(sent, values, 0.0).astype(jnp.bfloat16))
                    .astype(jnp.float32))

        return m._replace(
            duration_sum=accumulate(res.hop_latency),
            response_size_sum=accumulate(
                self._svc_resp_size[self.compiled.hop_service]))

    prometheus.MetricsCollector.collect = collect


def main(argv=None, *, platform: str = "tpu", edit_cell=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, default=2 ** 31 + 1000)
    ap.add_argument("--control", choices=("none", "bf16"), default="none")
    args = ap.parse_args(argv)

    from benchmark import run
    from benchmark.harness import cells, checks
    cell = cells.load_cell(args.workload)
    if edit_cell is not None:
        cell = edit_cell(cell)
    device = run.device_doc()
    if device["platform"] != platform or device["count"] != cell.chips:
        return run.refuse(
            f"{cell.name} needs {cell.chips} {platform} device(s)", device)
    refs = run.walks(cell)
    if args.control == "bf16":
        plant_bf16_collector()

    readings = {}
    broken_readings = {}

    def note(table, compared):
        for name, value, _, _ in compared:
            table.setdefault(name, []).append(value)

    passed = 0
    with tempfile.TemporaryDirectory(prefix="benchmark-limits-") as tmp:
        runner = run.Runner(cell, args.first_seed, tmp)
        mix = cell.traffic["precheck"]
        pre = runner.call(mix, "pre")
        compared, wrong, _ = run.check_call(
            refs, runner, pre, mix, checks.precheck)
        note(readings, compared)
        run.emit("precheck", wall_s=pre.wall_s, compared=compared,
                 problems=wrong[:8])
        pre_wrong = bool(wrong)
        for i in range(args.seeds):
            runner = run.Runner(cell, args.first_seed + i, tmp,
                                issued=1 + i)
            call = runner.call(cell.traffic, "call")
            compared, wrong, _ = run.check_call(
                refs, runner, call, cell.traffic, checks.conservation)
            note(readings, compared)
            passed += not wrong and not pre_wrong
            if args.control == "none" and i < 3:
                note(broken_readings, broken(runner, call, cell, refs))
            run.emit("seed", seed=args.first_seed + i, wall_s=call.wall_s,
                     compared=compared, problems=wrong[:8])
    run.emit("readings", workload=cell.name, seeds=args.seeds,
             control=args.control, device=device, calls_passed=passed,
             readings={k: {"largest": max(v), "smallest": min(v),
                           "n": len(v)} for k, v in readings.items()},
             broken_artifacts={k: {"largest": max(v), "smallest": min(v),
                                   "n": len(v)}
                               for k, v in broken_readings.items()})
    if args.control == "none":
        return 0 if passed == args.seeds else 1
    return 0 if passed == 0 else 1


def broken(runner, call, cell, refs):
    """The served artifacts of one call with a guarantee broken."""
    from benchmark import run
    from benchmark.harness import checks, served

    out = []
    runs, _ = served.artifacts(call, cell.traffic["artifacts"],
                               runner.values(call.tmp, call.seed))
    requests = cell.traffic["requests"]
    for label, doc, prom in runs:
        ref = run.walk_for(refs, label)
        if doc is None or prom is None or ref is None:
            continue
        victim = sorted(ref.visits)[-1]
        sample = f'service_incoming_requests_total{{service="{victim}"}} '
        with open(prom) as f:
            text = f.read()
        at = text.index(sample) + len(sample)
        end = text.index("\n", at)
        with open(prom + ".broken", "w") as f:
            f.write(text[:at] + str(int(float(text[at:end])) - 1)
                    + text[end:])
        out += [c for c in checks.conservation(
            doc, prom + ".broken", ref, requests)[0]
            if c[0] in ("hop_events_off", "services_incoming_off")]
        dropped = json.loads(json.dumps(doc))
        dropped["DurationHistogram"]["Count"] = requests - 1
        out += [c for c in checks.conservation(
            dropped, prom, ref, requests)[0]
            if c[0] == "count_off_requested"]
    return out


if __name__ == "__main__":
    sys.exit(main())
