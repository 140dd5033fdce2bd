# Developer drivers — the shape of the reference's isotope/Makefile
# (generate topology -> convert/deploy -> drive load), with simulation
# replacing kubectl apply.

PY ?= python
QPS ?= 1000
DURATION ?= 120s

.PHONY: test lint vet-smoke grad-smoke telemetry-smoke \
	resilience-smoke \
	attribution-smoke sparse-smoke timeline-smoke multihost-smoke \
	policies-smoke rollout-smoke lb-smoke ensemble-smoke \
	chaosfleet-smoke chaosgrid-smoke search-smoke explain-smoke \
	ingest-smoke \
	examples \
	canonical tree star multitier auxiliary-services star-auxiliary \
	latency cpu_mem dot clean

test:
	$(PY) -m pytest tests/ -x -q

# ruff (lint + format check) and the permissive mypy baseline from
# pyproject.toml when installed; everywhere else tools/lint.py's
# built-in floor (syntax + unused imports) still gates.  Nonzero exit
# on any finding, so this composes into CI exactly like the smokes.
lint:
	$(PY) tools/lint.py

# static-analysis end-to-end check: the shipped examples must vet
# clean, and a seeded-defect run (injected host callback + f64 leak,
# plus a tiny fake device capacity to trip the OOM verdict) must
# report the planted rules and exit nonzero.  The graddead injection
# quantizes cpu_scale through floor, so the gradient audit must flip
# cpu_time_s to gradient-dead (VET-G001) — strict promotes the warn
# to blocking, hence the leading `!`.
vet-smoke: lint
	$(PY) -m isotope_tpu vet examples/topologies/canonical.yaml \
		examples/topologies/tree-13-services.yaml
	! ISOTOPE_VET_INJECT=callback,f64 ISOTOPE_VET_DEVICE_BYTES=65536 \
		$(PY) -m isotope_tpu vet \
		examples/topologies/chain-3-services.yaml \
		> /tmp/isotope_vet_smoke.txt 2>&1
	@grep -q "VET-J001" /tmp/isotope_vet_smoke.txt
	@grep -q "VET-J002" /tmp/isotope_vet_smoke.txt
	@grep -q "VET-M001" /tmp/isotope_vet_smoke.txt
	! ISOTOPE_VET_INJECT=graddead $(PY) -m isotope_tpu vet \
		--grad --strict --suppress "VET-G002,VET-G004" \
		examples/topologies/chain-3-services.yaml \
		> /tmp/isotope_vet_grad_inject.txt 2>&1
	@grep -q "VET-G001" /tmp/isotope_vet_grad_inject.txt
	@grep -q "floor" /tmp/isotope_vet_grad_inject.txt
	@echo "vet-smoke: clean examples pass, seeded defects caught"

# gradient-audit end-to-end check: `vet --grad` classifies every
# registered design knob on the canonical examples (exit 0 — VET-G
# findings are warn/info), and the isotope-gradaudit/v1 artifact
# demonstrates all three classes, with the gradient-dead finding
# naming its killing primitive and jaxpr path.
grad-smoke:
	$(PY) -m isotope_tpu vet --grad \
		--grad-json /tmp/isotope_gradaudit.json \
		examples/topologies/canonical.yaml \
		examples/topologies/canonical-errors.yaml
	$(PY) -c "import json; \
		doc = json.load(open('/tmp/isotope_gradaudit.json')); \
		assert doc['schema'] == 'isotope-gradaudit/v1', doc['schema']; \
		from isotope_tpu.sim.config import DESIGN_PARAMS; \
		names = {p.name for p in DESIGN_PARAMS}; \
		audits = doc['audits']; \
		assert all(set(a['classes']) == names for a in audits); \
		classes = {c for a in audits for c in a['classes'].values()}; \
		assert classes == {'differentiable', 'gradient-dead', \
		                   'trace-constant'}, classes; \
		err = [k for a in audits for k in a['knobs'] \
		       if k['name'] == 'error_rate_scale' and k['kills']]; \
		assert any('lt' in k['kills'][0] for k in err), err; \
		print('grad-smoke: all', len(names), 'knobs classified,', \
		      'killer named:', err[0]['kills'][0])"

# tiny end-to-end engine-telemetry check: run a 3-service chain with
# --telemetry=detail (segment fences armed) and validate the emitted
# JSONL against the schema (telemetry/core.py validate_jsonl).
telemetry-smoke:
	rm -f /tmp/isotope_telemetry_smoke.jsonl
	$(PY) -m isotope_tpu simulate examples/topologies/chain-3-services.yaml \
		--qps 50 --duration 2s --load-kind open --max-requests 256 \
		--telemetry=detail \
		--telemetry-out /tmp/isotope_telemetry_smoke.jsonl --flat \
		> /dev/null
	$(PY) -c "from isotope_tpu.telemetry import validate_jsonl; \
		n = validate_jsonl('/tmp/isotope_telemetry_smoke.jsonl'); \
		print(f'telemetry-smoke: {n} valid record(s)')"

# engine-chaos end-to-end check: inject a transient failure AND an OOM
# into the run phase (resilience/faults.py), then assert the run still
# produced output — retried (retries_total >= 1) and degraded down the
# ladder (degradations_total >= 1, degraded_to recorded) instead of
# crashing.  The injected faults are deterministic; no flakiness.
resilience-smoke:
	rm -f /tmp/isotope_resilience_smoke.jsonl
	ISOTOPE_FAULT_INJECT=transient:engine.run:1,oom:engine.run:1 \
	$(PY) -m isotope_tpu simulate examples/topologies/chain-3-services.yaml \
		--qps 50 --duration 2s --load-kind open --max-requests 256 \
		--telemetry --compile-cache off \
		--telemetry-out /tmp/isotope_resilience_smoke.jsonl --flat \
		> /tmp/isotope_resilience_smoke.json
	$(PY) -c "import json; from isotope_tpu.telemetry import iter_jsonl; \
		rec = list(iter_jsonl('/tmp/isotope_resilience_smoke.jsonl'))[-1]; \
		assert rec.counters.get('retries_total', 0) >= 1, rec.counters; \
		assert rec.counters.get('degradations_total', 0) >= 1, rec.counters; \
		assert rec.meta.get('degraded_to'), rec.meta; \
		doc = json.load(open('/tmp/isotope_resilience_smoke.json')); \
		assert float(doc['ActualQPS']) > 0, doc; \
		print('resilience-smoke: degraded to', rec.meta['degraded_to'], \
		      '| retries', int(rec.counters['retries_total']), \
		      '| output intact (ActualQPS', doc['ActualQPS'], ')')"

# attribution end-to-end check: an example topology runs with
# --attribution=tail, then the artifacts are validated — blame shares
# present and summing to ~1, residual at f32 noise level, the
# flamegraph parsing as collapsed stacks, and the exemplar trace
# matching the jaeger_trace shape with tail_rank tags.
attribution-smoke:
	rm -f /tmp/isotope_attr_blame.json /tmp/isotope_attr_flame.txt \
		/tmp/isotope_attr_exemplars.json
	$(PY) -m isotope_tpu simulate examples/topologies/tree-13-services.yaml \
		--qps 50 --duration 4s --load-kind open --max-requests 512 \
		--attribution=tail --blame-out /tmp/isotope_attr_blame.json \
		--flamegraph /tmp/isotope_attr_flame.txt \
		--exemplar-trace /tmp/isotope_attr_exemplars.json --flat \
		> /dev/null
	$(PY) -c "import json; \
		doc = json.load(open('/tmp/isotope_attr_blame.json')); \
		shares = sum(r['share'] for r in doc['services']); \
		assert abs(shares - 1.0) < 1e-6, shares; \
		assert doc['residual_abs_s_per_request'] < 1e-6, doc; \
		assert doc['tail_cut_s'] and doc['tail_services'], doc; \
		lines = open('/tmp/isotope_attr_flame.txt').read().splitlines(); \
		assert lines and all(len(ln.rsplit(' ', 1)) == 2 and \
			ln.rsplit(' ', 1)[1].isdigit() and \
			ln.rsplit(' ', 1)[0].startswith('client;') \
			for ln in lines), lines[:3]; \
		ex = json.load(open('/tmp/isotope_attr_exemplars.json')); \
		tr = ex['data'][0]; \
		assert tr['spans'] and tr['processes'], tr; \
		tags = {t['key'] for t in tr['spans'][0]['tags']}; \
		assert {'tail_rank', 'tail_cut_s'} <= tags, tags; \
		print('attribution-smoke: blame sums to 1, flamegraph parses,', \
		      len(ex['data']), 'exemplar trace(s) validate')"

# flight-recorder end-to-end check: the timeline subcommand records a
# short run into windowed series, then the artifacts are validated —
# window counts reconciling with the run total, the timestamped
# Prometheus exposition parsing (with timestamps) and round-tripping
# through the query layer, and per-window alarm rows carrying sim-time
# stamps.
timeline-smoke:
	rm -f /tmp/isotope_tl.json /tmp/isotope_tl.prom \
		/tmp/isotope_tl_monitor.jsonl
	$(PY) -m isotope_tpu timeline examples/topologies/tree-13-services.yaml \
		--qps 200 --duration 6s --load-kind open --max-requests 1024 \
		--window 1s --out /tmp/isotope_tl.json \
		--prometheus /tmp/isotope_tl.prom \
		--alarms --alarm-sink /tmp/isotope_tl_monitor.jsonl \
		> /dev/null
	$(PY) -c "import json; \
		doc = json.load(open('/tmp/isotope_tl.json')); \
		assert doc['schema'] == 'isotope-timeline/v1', doc['schema']; \
		wins = doc['windows']; \
		total = sum(w['arrivals'] for w in wins); \
		assert total == doc['count'], (total, doc['count']); \
		assert doc['services'], 'no service series'; \
		from isotope_tpu.metrics.query import MetricStore; \
		store = MetricStore.from_text(open('/tmp/isotope_tl.prom').read(), 1.0); \
		v = store.query_value('timeline_client_requests_total'); \
		assert v == total, (v, total); \
		from isotope_tpu.metrics.monitor import MonitorSink; \
		rows = MonitorSink('/tmp/isotope_tl_monitor.jsonl').read(); \
		assert rows and all(r.window_index is not None for r in rows), \
			rows[:2]; \
		print('timeline-smoke:', len(wins), 'windows reconcile,', \
		      len(rows), 'window-stamped monitor rows')"

# sparse-executor end-to-end check: force the non-dense encodings
# (sparse_level_elems lowered) on a small star graph, run the dense /
# tiled / sparse executors, and diff their summaries —
# counts must be equal, latency sums within f32 reduction noise.
sparse-smoke:
	$(PY) tools/sparse_smoke.py

# multi-host end-to-end check: the 2 hosts x 8 devices EMULATED twin
# (16 shards on one CPU device) reconciles, the (slice, data, svc)
# shard_map program matches its emulated replay within 1 ULP,
# collective/compute overlap matches the single-merge path, the
# --mesh auto layout search scores <= the hand-picked {2,2,2} mesh,
# and an injected sharded.dcn_collective transient is retried.
multihost-smoke:
	$(PY) tools/multihost_smoke.py

# resilience-policy end-to-end check: a chaos kill phase on a retry
# chain runs unprotected vs. with breaker + retry budget + autoscaler;
# the protected run's retry-amplified hop events and error share must
# be STRICTLY lower, the breaker trip/recovery must land as sim-time
# onsets on the timeline window axis, and the autoscaler's replica
# series must recover the killed capacity.
policies-smoke:
	$(PY) tools/policies_smoke.py

# progressive-delivery end-to-end check (sim/rollout.py): a seeded bad
# canary must roll back inside its first bake window, its traffic
# exposure and error burn must stay strictly below the open-loop
# `churn`-equivalent twin's, and the 4-shard sharded trajectory must
# be bit-equal to the emulated twin.
rollout-smoke:
	$(PY) tools/rollout_smoke.py

# load-balancing end-to-end check (sim/lb.py): least-request beats the
# shared-queue fifo tail (and the mis-weighted hot pool) at rho ~0.9,
# prints the per-window per-backend load split, and panic routing
# keeps goodput nonzero through a 3/4-replica ejection storm
lb-smoke:
	$(PY) tools/lb_smoke.py

# scenario-ensemble end-to-end check (sim/ensemble.py): a 32-member
# svc-scale fleet on CPU — exactly one compile serves every member
# (telemetry trace/cache counters), the P(SLO-violation) estimate
# with its Wilson CI matches the brute-force per-seed loop exactly
# (member k bit-equals the solo run with that folded seed), and the
# fleet's aggregate wall-clock beats the sequential dispatch loop.
ensemble-smoke:
	$(PY) tools/ensemble_smoke.py

# chaos-fleet end-to-end check (PR 15): protected fleet over a
# retry-storm topology with per-member kill timing, member k bit-equal
# to its solo run_policies, importance splitting resolving a
# forced-rare outage at <= 10% of the brute-force budget, and the
# worst member's jittered schedule replaying solo bit-for-bit
chaosfleet-smoke:
	$(PY) tools/chaosfleet_smoke.py

# universal-member composition check (PR 18): the four compositions
# the pre-universal member rejected (ungraceful kills, LB panic,
# saturated -qps max, rollout kill splits) each run as member-jittered
# fleets bit-equal to their solo twins, then the ALL-ON fleet
# (policies + LB panic + rollouts + ungraceful member chaos in one
# program) with the worst member's postmortem replaying bit-for-bit
chaosgrid-smoke:
	$(PY) tools/chaosgrid_smoke.py

# config-search end-to-end check (sim/search.py): a 16-candidate
# successive-halving bracket over the svc-scale fan-out — the planted
# near-zero-error candidate wins, the bracket compiles <= once per
# rung (a repeat bracket adds zero traces), rung 0 bit-equals the
# plain screening fleet, and the winner's carry-continued segments
# replay the unbroken full-horizon member exactly
search-smoke:
	$(PY) tools/search_smoke.py

# fleet-observability end-to-end check (PR 17): a fleet with a
# planted slow-hop member (3/4 worker replicas killed at 0.3s) runs
# blame + recorder through ONE dispatch; the fleet-blame artifact +
# `isotope-tpu explain` must name the hop, the onset window, and the
# band departure from the artifact alone, and the worst member's
# blame must replay solo
explain-smoke:
	$(PY) tools/explain_smoke.py

# trace-driven ingest self-closure check (PR 20): simulate the
# power-law fixture with the timeline recorder armed, export the two
# Prometheus expositions a real scrape would see, ingest them back
# through readers -> fitters, and pin the reconstruction — per-service
# error share, mean self-time (90% band share), exact fan-out degree
# sequence, windowed qps schedule — within report.CLOSURE_TOLERANCES;
# coverage counters must partition every input line, the emitted TOML
# must decode through load_toml, vet must be clean, and the fitted
# topology must re-simulate to the source's client error share
ingest-smoke:
	$(PY) tools/ingest_smoke.py

examples:
	$(PY) tools/gen_examples.py

# -- single-topology runs (reference Makefile:30-72 targets) -------------

canonical:
	isotope-tpu simulate examples/topologies/canonical.yaml \
		--qps $(QPS) --duration $(DURATION) --load-kind open

tree:
	isotope-tpu generate tree --levels 4 --branches 3 -o /tmp/tree.yaml
	isotope-tpu simulate /tmp/tree.yaml --qps $(QPS) --duration $(DURATION) \
		--load-kind open

star multitier auxiliary-services star-auxiliary:
	isotope-tpu generate realistic --services 50 --type $@ -o /tmp/$@.yaml
	isotope-tpu simulate /tmp/$@.yaml --qps $(QPS) --duration $(DURATION) \
		--load-kind open

# -- benchmark sweeps (perf/benchmark/configs shapes) --------------------

latency:
	isotope-tpu sweep configs/latency.toml -o results/latency
	isotope-tpu plot results/latency/benchmark.csv --x conn \
		-o results/latency/latency.png

cpu_mem:
	isotope-tpu sweep configs/cpu_mem.toml -o results/cpu_mem
	isotope-tpu plot results/cpu_mem/benchmark.csv --x qps \
		--metrics p50,p99 -o results/cpu_mem/latency.png

dot:
	isotope-tpu graphviz examples/topologies/canonical.yaml canonical.dot

clean:
	rm -rf results canonical.dot /tmp/tree.yaml
