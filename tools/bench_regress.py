"""Per-case bench regression gate.

VERDICT r4 found svc1000 sliding 2.50B -> 2.05B -> 1.50B across rounds
with nothing noticing: ``bench.py`` reported best-of-3 and no check
compared against the previous round's driver capture.  This tool diffs
a fresh bench capture against the newest ``BENCH_r*.json`` in the repo
root and fails on any per-case regression beyond the threshold.

Usage:
    python bench.py | tee /tmp/bench.json
    python tools/bench_regress.py /tmp/bench.json

The driver's BENCH files wrap the parsed line under ``"parsed"``; a raw
``bench.py`` line is accepted too.  Only numeric, per-case rate keys
present in both captures are compared (evidence keys like
``*_inflight`` and spread keys are skipped); the headline ``value`` is
compared as case ``tree121``.

Optional telemetry gates — each armed by setting its env var to a
threshold (unset = not gated), compared per case over the
``<case>_telemetry`` blocks bench.py embeds:

- ``BENCH_REGRESS_COMPILE_THRESHOLD``: relative increase allowed on
  first-call compile seconds (``<case>_compile_s``, falling back to
  the telemetry block's ``compile_s``), e.g. ``0.5`` = +50%;
- ``BENCH_REGRESS_MEM_THRESHOLD``: relative increase allowed on
  ``peak_device_bytes``;
- ``BENCH_REGRESS_WASTE_THRESHOLD``: ABSOLUTE increase allowed on
  ``padding_waste_fraction`` (it is already a ratio);
- ``BENCH_REGRESS_VET_GATE=1``: fail a capture whose static-analysis
  pass (``vet_errors`` in the telemetry block — bench runs the
  no-trace vet per case) reports MORE errors than the previous
  capture's; captures without vet data on either side are skipped.
- ``BENCH_REGRESS_SPREAD_THRESHOLD``: relative spread bound on
  ``<case>_spread`` — a case past it that also got noisier than the
  previous capture fails (keeps bench.py's steady-state warmup
  discipline from silently regressing);
- ``BENCH_REGRESS_BLAME_THRESHOLD``: ABSOLUTE per-service drift
  allowed on the critical-path blame shares (``<case>_blame`` blocks
  from bench's attributed probe), e.g. ``0.1`` = 10 share points; a
  case's throughput can hold while its critical path migrates, which
  only this gate sees.
- ``BENCH_REGRESS_TIMELINE_THRESHOLD``: ABSOLUTE bound on the
  flight-recorder overhead (``<case>_timeline_overhead`` — bench's
  timeline-on vs timeline-off steady-state delta), e.g. ``0.05`` =
  the 5% svc1000 acceptance bar.
- ``BENCH_REGRESS_LAYOUT_GATE=1``: fail a capture whose automatic
  mesh-layout search picked a WORSE-scoring factorization than the
  baseline's (``_mesh_layout`` / ``_mesh_layout_score`` — bench
  embeds the ``--mesh auto`` choice and its comm-cost-model score).

Always armed (no env var): a case whose telemetry block carries
``degraded_to`` — the resilience supervisor served it from a
degradation-ladder rung — fails the gate if the previous round's
capture ran that case clean (a degraded number is not comparable).
"""
from __future__ import annotations

import glob
import json
import os
import re
import sys

# Fail when new < (1 - THRESHOLD) * old.  NOTE the instrument: the
# r5 capture host drifted by up to ~2x across sessions (interleaved
# A/B of r4-vs-r5 binaries measured both orderings within minutes),
# so the default gate is meaningful for SAME-SESSION comparisons
# (pre/post an optimization); across rounds, expect noise-fired
# alarms and read them against the per-case ``_spread`` evidence.
THRESHOLD = float(os.environ.get("BENCH_REGRESS_THRESHOLD", "0.15"))
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_capture(path: str) -> dict:
    with open(path) as f:
        text = f.read()
    # accept either a driver BENCH_r*.json wrapper or a raw bench line
    # (possibly preceded by jax warnings on stderr-merged logs)
    try:
        doc = json.loads(text)
    except json.JSONDecodeError:
        doc = None
        for line in text.splitlines():
            line = line.strip()
            if line.startswith("{"):
                try:
                    doc = json.loads(line)
                except json.JSONDecodeError:
                    continue
        if doc is None:
            raise
    if "parsed" in doc:
        doc = doc["parsed"]
    return doc


def _cases(doc: dict, prefer_best: bool = False) -> dict:
    """Per-case rates from a capture.

    ``prefer_best=True`` (applied to the NEW capture) compares the
    best-window statistic against older rounds: captures before r5
    reported best-of-3, so the r5 median would read as a spurious
    across-methodology "regression" otherwise.
    """
    extra = doc.get("extra", {})
    cases = {"tree121": float(doc["value"])}
    for k, v in extra.items():
        if not isinstance(v, (int, float)):
            continue
        if k.endswith(("_inflight", "_spread", "_census", "_best",
                       "_compile_s", "_warmup_windows",
                       "_timeline_overhead", "_blame_overhead",
                       "_mesh_layout_score",
                       "_rollout", "_lb", "_ensemble_members",
                       "_ensemble_traces", "_ensemble_solo_rate",
                       "_ensemble_speedup",
                       "_chaosfleet_members", "_chaosfleet_traces",
                       "_chaosfleet_worst_severity",
                       "_chaosfleet_split_p",
                       "_chaosfleet_split_evals",
                       "_composed", "_composed_members",
                       "_composed_traces",
                       "_composed_worst_severity",
                       "_search_candidates", "_search_rungs",
                       "_search_traces", "_search_sequential_rate",
                       "_search_speedup",
                       "_ingest_fit_s", "_ingest_services",
                       "_ingest_edges", "_ingest_lines",
                       "_ingest_qps")):
            # evidence / variance keys, not rates — "_composed" also
            # drops the svc1000_composed COVERAGE case's rate (its
            # telemetry degraded_to gate still applies)
            continue
        cases[k] = float(v)
    if prefer_best:
        for k in list(cases):
            b = extra.get(f"{k}_best")
            if isinstance(b, (int, float)):
                cases[k] = float(b)
    return cases


def _telemetry_value(extra: dict, case: str, field: str):
    """A case's telemetry field: the legacy flat ``<case>_compile_s``
    key wins for compile seconds (it predates the telemetry block),
    then the ``<case>_telemetry`` dict."""
    if field == "compile_s":
        flat = extra.get(f"{case}_compile_s")
        if isinstance(flat, (int, float)) and flat > 0:
            return float(flat)
    blk = extra.get(f"{case}_telemetry")
    if isinstance(blk, dict):
        v = blk.get(field)
        if isinstance(v, (int, float)):
            return float(v)
    return None


def telemetry_failures(prev_doc: dict, new_doc: dict) -> list:
    """Env-armed per-case gates on the embedded telemetry fields.

    Reads the thresholds at call time (not import) so one process can
    evaluate several configurations; an unset env var disarms its gate.
    """
    gates = (
        # (field, env var, relative?)
        ("compile_s", "BENCH_REGRESS_COMPILE_THRESHOLD", True),
        ("peak_device_bytes", "BENCH_REGRESS_MEM_THRESHOLD", True),
        ("padding_waste_fraction", "BENCH_REGRESS_WASTE_THRESHOLD",
         False),
    )
    prev_extra = prev_doc.get("extra", {})
    new_extra = new_doc.get("extra", {})
    cases = sorted(
        {k[: -len("_telemetry")] for k in prev_extra if
         k.endswith("_telemetry")}
        | {k[: -len("_compile_s")] for k in prev_extra if
           k.endswith("_compile_s")}
    )
    failures = []
    for field, env, relative in gates:
        raw = os.environ.get(env)
        if raw is None or raw == "":
            continue
        thr = float(raw)
        for case in cases:
            old = _telemetry_value(prev_extra, case, field)
            new = _telemetry_value(new_extra, case, field)
            if old is None or new is None:
                continue
            if relative:
                if old <= 0:
                    continue
                bad = new > old * (1.0 + thr)
                delta = f"{(new / old - 1) * 100:+.1f}%"
            else:
                bad = new > old + thr
                delta = f"{new - old:+.4f}"
            verdict = "REGRESSION" if bad else "OK"
            print(f"bench_regress: {case}.{field}: {old:.4g} -> "
                  f"{new:.4g} ({delta}) {verdict}")
            if bad:
                failures.append(f"{case}.{field}")
    return failures


def vet_failures(prev_doc: dict, new_doc: dict) -> list:
    """Opt-in gate (``BENCH_REGRESS_VET_GATE=1``): a case whose vet run
    reports MORE errors than the previous capture's vet run regressed.

    Both captures must carry vet data (``vet_errors`` in the
    ``<case>_telemetry`` block — present only when the capture actually
    vetted, telemetry/core.py summary_block): a baseline from before
    vet existed is skipped, never read as "zero errors".
    """
    if os.environ.get("BENCH_REGRESS_VET_GATE", "") not in (
        "1", "true", "on", "yes",
    ):
        return []
    prev_extra = prev_doc.get("extra", {})
    new_extra = new_doc.get("extra", {})
    failures = []
    for k, blk in sorted(new_extra.items()):
        if not k.endswith("_telemetry") or not isinstance(blk, dict):
            continue
        new_errs = blk.get("vet_errors")
        prev_blk = prev_extra.get(k)
        old_errs = (
            prev_blk.get("vet_errors")
            if isinstance(prev_blk, dict)
            else None
        )
        if new_errs is None or old_errs is None:
            continue  # one side never vetted: nothing comparable
        case = k[: -len("_telemetry")]
        bad = int(new_errs) > int(old_errs)
        verdict = "REGRESSION" if bad else "OK"
        print(f"bench_regress: {case}.vet_errors: {int(old_errs)} -> "
              f"{int(new_errs)} {verdict}")
        if bad:
            failures.append(f"{case}.vet_errors")
    return failures


def blame_failures(prev_doc: dict, new_doc: dict) -> list:
    """Opt-in gate (``BENCH_REGRESS_BLAME_THRESHOLD=<abs drift>``): a
    case whose per-service critical-path blame SHARE moved by more
    than the threshold (absolute, shares are in [0, 1]) vs the
    previous capture regressed.

    Blame shares localize *where* latency comes from — a case can hold
    its throughput while its critical path silently migrates (e.g. a
    queueing change moving p99 blame from a leaf to the entry), which
    the rate gates cannot see.  Both captures must carry the case's
    ``<case>_blame`` block (bench embeds it via a small attributed
    run); baselines from before attribution existed are skipped.
    """
    raw = os.environ.get("BENCH_REGRESS_BLAME_THRESHOLD")
    if raw is None or raw == "":
        return []
    thr = float(raw)
    prev_extra = prev_doc.get("extra", {})
    new_extra = new_doc.get("extra", {})
    failures = []
    for k, blk in sorted(new_extra.items()):
        if not k.endswith("_blame") or not isinstance(blk, dict):
            continue
        prev_blk = prev_extra.get(k)
        if not isinstance(prev_blk, dict):
            continue  # baseline never carried blame: nothing comparable
        case = k[: -len("_blame")]
        new_sv = blk.get("services") or {}
        old_sv = prev_blk.get("services") or {}
        worst_svc, worst = None, 0.0
        for svc in set(new_sv) | set(old_sv):
            drift = abs(
                float(new_sv.get(svc, 0.0)) - float(old_sv.get(svc, 0.0))
            )
            if drift > worst:
                worst_svc, worst = svc, drift
        bad = worst > thr
        verdict = "REGRESSION" if bad else "OK"
        print(
            f"bench_regress: {case}.blame: max share drift "
            f"{worst:+.4f}"
            + (f" ({worst_svc})" if worst_svc else "")
            + f" {verdict}"
        )
        if bad:
            failures.append(f"{case}.blame")
    return failures


def timeline_failures(new_doc: dict) -> list:
    """Opt-in gate (``BENCH_REGRESS_TIMELINE_THRESHOLD=<max overhead>``):
    a case whose measured flight-recorder overhead
    (``<case>_timeline_overhead``, the timeline-on vs timeline-off
    steady-state delta bench.py embeds) exceeds the threshold fails.

    An ABSOLUTE bound, not a vs-baseline diff: the acceptance bar is
    "timeline-on costs <= X of timeline-off" (5% on svc1000), which
    holds or it doesn't — comparing drifting overheads against each
    other would let the bound creep."""
    raw = os.environ.get("BENCH_REGRESS_TIMELINE_THRESHOLD")
    if raw is None or raw == "":
        return []
    thr = float(raw)
    failures = []
    for k, v in sorted(new_doc.get("extra", {}).items()):
        if not k.endswith("_timeline_overhead") or not isinstance(
            v, (int, float)
        ):
            continue
        case = k[: -len("_timeline_overhead")]
        bad = float(v) > thr
        verdict = "REGRESSION" if bad else "OK"
        print(f"bench_regress: {case}.timeline_overhead: "
              f"{float(v):+.3f} (threshold {thr:.3f}) {verdict}")
        if bad:
            failures.append(f"{case}.timeline_overhead")
    return failures


def fleetblame_failures(new_doc: dict) -> list:
    """Opt-in gate (``BENCH_REGRESS_FLEETBLAME_THRESHOLD=<max
    overhead>``): a fleet case whose measured blame-pass overhead
    (``<case>_blame_overhead``, the attribution-on vs attribution-off
    fleet steady-state delta bench.py embeds) exceeds the threshold
    fails.

    Same discipline as :func:`timeline_failures` — an ABSOLUTE bound:
    "blame-on costs <= X of blame-off" holds or it doesn't; diffing
    drifting overheads against each other would let the bound creep.
    """
    raw = os.environ.get("BENCH_REGRESS_FLEETBLAME_THRESHOLD")
    if raw is None or raw == "":
        return []
    thr = float(raw)
    failures = []
    for k, v in sorted(new_doc.get("extra", {}).items()):
        if not k.endswith("_blame_overhead") or not isinstance(
            v, (int, float)
        ):
            continue
        case = k[: -len("_blame_overhead")]
        bad = float(v) > thr
        verdict = "REGRESSION" if bad else "OK"
        print(f"bench_regress: {case}.blame_overhead: "
              f"{float(v):+.3f} (threshold {thr:.3f}) {verdict}")
        if bad:
            failures.append(f"{case}.blame_overhead")
    return failures


def ensemble_failures(prev_doc: dict, new_doc: dict) -> list:
    """Opt-in gate (``BENCH_REGRESS_ENSEMBLE_THRESHOLD=<ratio>``): a
    fleet case whose PER-MEMBER throughput (case rate divided by its
    ``<case>_ensemble_members``) regressed beyond the threshold vs the
    previous capture fails.

    The aggregate rate alone can hide a per-member regression behind a
    member-count change (double the members, tank each member 40%, still
    "faster") — normalizing by the fleet width keeps the comparison
    per-scenario-honest.  Captures without the members key on either
    side are skipped (pre-ensemble baselines).
    """
    raw = os.environ.get("BENCH_REGRESS_ENSEMBLE_THRESHOLD")
    if raw is None or raw == "":
        return []
    thr = float(raw)
    prev_extra = prev_doc.get("extra", {})
    new_extra = new_doc.get("extra", {})
    prev_rates = _cases(prev_doc)
    new_rates = _cases(new_doc)
    failures = []
    for k, new_m in sorted(new_extra.items()):
        if not k.endswith("_ensemble_members") or not isinstance(
            new_m, (int, float)
        ):
            continue
        case = k[: -len("_ensemble_members")]
        old_m = prev_extra.get(k)
        if not isinstance(old_m, (int, float)) or old_m <= 0 \
                or new_m <= 0:
            continue
        if case not in prev_rates or case not in new_rates:
            continue
        old_pm = prev_rates[case] / float(old_m)
        new_pm = new_rates[case] / float(new_m)
        bad = old_pm > 0 and new_pm < old_pm * (1.0 - thr)
        verdict = "REGRESSION" if bad else "OK"
        print(f"bench_regress: {case}.per_member: {old_pm:.4g} -> "
              f"{new_pm:.4g} "
              f"({(new_pm / old_pm - 1) * 100:+.1f}%) {verdict}")
        if bad:
            failures.append(f"{case}.per_member")
    return failures


def search_failures(new_doc: dict) -> list:
    """Opt-in gate (``BENCH_REGRESS_SEARCH_THRESHOLD=<ratio>``): a
    config-search bracket case whose measured speedup over the
    sequential sweep (``<case>_search_speedup``) fell under the
    threshold fails the round.

    Like the timeline-overhead gate this is an absolute bound on the
    NEW capture, not a ratio against the previous one — the bracket's
    perf claim (the ISSUE's >= 3x bar) either holds or it doesn't;
    comparing drifting speedups would let the bound creep.  The trace
    bound rides along: a bracket that compiled more executables than
    rungs (``_search_traces`` > ``_search_rungs``) lost the
    one-compile-per-rung-shape property the speedup rests on.
    """
    raw = os.environ.get("BENCH_REGRESS_SEARCH_THRESHOLD")
    if raw is None or raw == "":
        return []
    thr = float(raw)
    failures = []
    new_extra = new_doc.get("extra", {})
    for k, v in sorted(new_extra.items()):
        if not k.endswith("_search_speedup") or not isinstance(
            v, (int, float)
        ):
            continue
        case = k[: -len("_search_speedup")]
        bad = float(v) < thr
        verdict = "REGRESSION" if bad else "OK"
        print(f"bench_regress: {case}.search_speedup: {float(v):.3f} "
              f"(threshold {thr:.3f}) {verdict}")
        if bad:
            failures.append(f"{case}.search_speedup")
        traces = new_extra.get(f"{case}_search_traces")
        rungs = new_extra.get(f"{case}_search_rungs")
        if isinstance(traces, (int, float)) and isinstance(
            rungs, (int, float)
        ) and traces > rungs:
            print(f"bench_regress: {case}.search_traces: "
                  f"{int(traces)} > {int(rungs)} rung shapes "
                  "REGRESSION")
            failures.append(f"{case}.search_traces")
    return failures


def layout_failures(prev_doc: dict, new_doc: dict) -> list:
    """Opt-in gate (``BENCH_REGRESS_LAYOUT_GATE=1``): the automatic
    mesh-layout search (parallel/layout.py — bench embeds the chosen
    factorization and its cost-model score as ``_mesh_layout`` /
    ``_mesh_layout_score``) must never pick a WORSE-scoring mesh than
    the recorded baseline's.  A higher score means a search or
    cost-model change regressed the chosen layout — visible here
    before any multi-host run pays for it.  Captures without layout
    data on either side are skipped (pre-gate baselines)."""
    if os.environ.get("BENCH_REGRESS_LAYOUT_GATE", "") not in (
        "1", "true", "on", "yes",
    ):
        return []
    prev_extra = prev_doc.get("extra", {})
    new_extra = new_doc.get("extra", {})
    old = prev_extra.get("_mesh_layout_score")
    new = new_extra.get("_mesh_layout_score")
    if not isinstance(old, (int, float)) or not isinstance(
        new, (int, float)
    ):
        print("bench_regress: layout gate: no _mesh_layout_score on "
              "one side — skipped")
        return []
    bad = float(new) > float(old) * (1.0 + 1e-9)
    verdict = "REGRESSION" if bad else "OK"
    print(f"bench_regress: _mesh_layout: "
          f"{prev_extra.get('_mesh_layout')!r} ({float(old):.3g}s) -> "
          f"{new_extra.get('_mesh_layout')!r} ({float(new):.3g}s) "
          f"{verdict}")
    return ["_mesh_layout"] if bad else []


def spread_failures(prev_doc: dict, new_doc: dict) -> list:
    """Opt-in gate (``BENCH_REGRESS_SPREAD_THRESHOLD=<ratio>``): a case
    whose window-to-window relative spread (``<case>_spread``) exceeds
    the threshold AND got noisier than the previous capture regressed.

    This keeps noise fixes fixed: once a case's steady-state discipline
    (bench.py warmup windows) brings its spread under the threshold, a
    later change that re-noises it fails the round — deltas measured
    through a 25% spread cannot clear the 15% rate gate honestly.  A
    case already past the threshold in the baseline only fails when it
    gets WORSE (no permanent alarm on known-noisy cases).
    """
    raw = os.environ.get("BENCH_REGRESS_SPREAD_THRESHOLD")
    if raw is None or raw == "":
        return []
    thr = float(raw)
    prev_extra = prev_doc.get("extra", {})
    new_extra = new_doc.get("extra", {})
    failures = []
    for k, v in sorted(new_extra.items()):
        if not k.endswith("_spread") or not isinstance(v, (int, float)):
            continue
        case = k[: -len("_spread")]
        old = prev_extra.get(k)
        old_ok = isinstance(old, (int, float))
        bad = float(v) > thr and (not old_ok or float(v) > float(old))
        verdict = "REGRESSION" if bad else "OK"
        prev_txt = f"{float(old):.3f}" if old_ok else "n/a"
        print(f"bench_regress: {case}.spread: {prev_txt} -> "
              f"{float(v):.3f} (threshold {thr:.3f}) {verdict}")
        if bad:
            failures.append(f"{case}.spread")
    return failures


def degradation_failures(prev_doc: dict, new_doc: dict) -> list:
    """Always-armed gate: a case that DEGRADED in the new capture but
    ran clean in the previous round is a regression.

    The resilience supervisor (isotope_tpu/resilience/) lets an OOM'd
    case complete on a fallback rung instead of crashing — which must
    never silently normalize: a benchmark number produced by the
    half-block or single-device rung is not comparable to the mesh
    path's, so bench gates on the ``degraded_to`` key the telemetry
    block carries only when a degradation happened.
    """
    prev_extra = prev_doc.get("extra", {})
    new_extra = new_doc.get("extra", {})
    failures = []
    for k, blk in sorted(new_extra.items()):
        if not k.endswith("_telemetry") or not isinstance(blk, dict):
            continue
        degraded = blk.get("degraded_to")
        if not degraded:
            continue
        case = k[: -len("_telemetry")]
        prev_blk = prev_extra.get(k)
        prev_degraded = (
            prev_blk.get("degraded_to")
            if isinstance(prev_blk, dict)
            else None
        )
        if prev_degraded:
            print(f"bench_regress: {case}: degraded to {degraded!r} "
                  f"(previously {prev_degraded!r}) OK")
            continue
        print(f"bench_regress: {case}: DEGRADED to {degraded!r} on a "
              "previously clean case REGRESSION")
        failures.append(f"{case}.degraded_to")
    return failures


def previous_capture() -> tuple:
    """(path, parsed_doc) of the newest BENCH_r*.json, or (None, None)."""
    files = sorted(
        glob.glob(os.path.join(REPO_ROOT, "BENCH_r*.json")),
        # match against the BASENAME only: a checkout path containing
        # "r<digit>" (e.g. /home/r2/repo) must not key the ordering
        key=lambda p: int(
            re.search(r"r(\d+)", os.path.basename(p)).group(1)
        ),
    )
    if not files:
        return None, None
    path = files[-1]
    return path, _load_capture(path)


def main() -> int:
    if len(sys.argv) != 2:
        print(__doc__)
        return 2
    new_doc = _load_capture(sys.argv[1])
    prev_path, prev_doc = previous_capture()
    if prev_doc is None:
        print("bench_regress: no BENCH_r*.json baseline found — skipping")
        return 0
    prev = _cases(prev_doc)
    # like-for-like statistics: an r5+ baseline carries medians (and
    # *_best evidence keys) — compare median vs median; a pre-r5
    # baseline reported best-of-window, so compare the NEW capture's
    # best against it (new-best-vs-old-median would mask a real median
    # regression behind the +-40% window spread)
    baseline_has_best = any(
        k.endswith("_best") for k in prev_doc.get("extra", {})
    )
    new = _cases(new_doc, prefer_best=not baseline_has_best)
    new_extra = new_doc.get("extra", {})
    failures = []
    for case, old_rate in sorted(prev.items()):
        if case in new_extra and new_extra[case] is None:
            # the case crashed or timed out inside bench.py — a
            # vanished case must fail the gate, not be skipped
            print(f"bench_regress: {case}: FAILED in the new capture "
                  f"(was {old_rate:.3g})")
            failures.append(case)
            continue
        if case not in new:
            print(f"bench_regress: {case}: dropped from capture "
                  f"(was {old_rate:.3g}) — not compared")
            continue
        ratio = new[case] / old_rate if old_rate > 0 else float("inf")
        verdict = "OK"
        if ratio < 1.0 - THRESHOLD:
            verdict = "REGRESSION"
            failures.append(case)
        print(f"bench_regress: {case}: {old_rate:.4g} -> "
              f"{new[case]:.4g} ({(ratio - 1) * 100:+.1f}%) {verdict}")
    failures.extend(telemetry_failures(prev_doc, new_doc))
    failures.extend(degradation_failures(prev_doc, new_doc))
    failures.extend(vet_failures(prev_doc, new_doc))
    failures.extend(blame_failures(prev_doc, new_doc))
    failures.extend(spread_failures(prev_doc, new_doc))
    failures.extend(timeline_failures(new_doc))
    failures.extend(fleetblame_failures(new_doc))
    failures.extend(ensemble_failures(prev_doc, new_doc))
    failures.extend(search_failures(new_doc))
    failures.extend(layout_failures(prev_doc, new_doc))
    if failures:
        print(f"bench_regress: FAIL vs {prev_path}: "
              f"{', '.join(failures)} regressed >"
              f"{THRESHOLD:.0%}")
        return 1
    print(f"bench_regress: PASS vs {prev_path}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
