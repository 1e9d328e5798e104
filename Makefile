# Dev ergonomics (cf. the reference's Makefile targets).

PY ?= python
DOCKER ?= docker
TAG ?= latest

.PHONY: test test-fast test-unit test-k8s chip-smoke bench bench-tiny bench-trend chaos chaos-soak cold-start dryrun loadgen loadgen-demo native clean charts images images-check fleet-snapshot perf-gate disagg-bench incident-drill incident-report qos-drill gray-drill kv-bench forecast-drill spike-drill

test:
	$(PY) -m pytest tests/ -q

test-fast:  ## skip the slow e2e/model-parity suites
	$(PY) -m pytest tests/ -q --ignore=tests/test_e2e_local.py \
	    --ignore=tests/test_e2e_chaos.py --ignore=tests/test_finetune.py

chaos:  ## deterministic chaos + recovery suites (failpoints armed, fake clocks)
	JAX_PLATFORMS=cpu KUBEAI_DEBUG_FAULTS=1 $(PY) -m pytest \
	    tests/test_chaos.py tests/test_e2e_chaos.py -q

chaos-soak: ## seeded randomized multi-fault soak: 200 episodes vs a live stack, global invariants, ddmin shrink on violation -> build/chaos/CHAOS.json
	@# Each episode draws a fault schedule from a seeded PRNG (flaps,
	@# mid-stream kills, disk faults, reconcile errors...), drives a
	@# mixed QoS/tenant workload through store->reconciler->LB->proxy->
	@# real CPU engines, quiesces, and asserts the global invariants:
	@# byte-identical deterministic streams, KV/slot/thread conservation,
	@# client==accountant==engine token conservation, breaker recovery,
	@# per-episode incident capture. A violation prints the seed + a
	@# ddmin-shrunk minimal schedule and the one-command replay line.
	@# Exits nonzero on any violation or if coverage floors (>=4 fault
	@# sites across >=3 subsystems) are missed. The fast fixed-seed
	@# variant runs in tier-1 (tests/test_chaos_campaign.py). See
	@# docs/robustness.md "Chaos campaigns". Override: EPISODES=, SEED=.
	JAX_PLATFORMS=cpu $(PY) benchmarks/chaos_soak.py \
	    --episodes $(or $(EPISODES),200) --seed $(or $(SEED),1)

chip-smoke: ## serve a 7B model at published widths through the operator on ONE TPU chip
	@# Needs a chip (exits non-zero without one); `CHIPS=4` runs the
	@# tensor-parallel comparison on a four-chip host instead. The last
	@# stdout line is the result. See README "Running it".
	$(PY) chip_smoke.py $(if $(CHIPS),--chips $(CHIPS))

bench: ## one chip preset (default 8b-int8); needs a TPU, exits non-zero without one
	$(PY) bench.py

bench-tiny: ## the CPU smoke of bench.py (its result names the cpu device)
	$(PY) bench.py --tiny

BENCH ?=
BASELINES ?=
perf-gate: ## schema-validate a bench JSON (+ compare vs prior ones, if any are given)
	@# Usage: make perf-gate BENCH=new.json [BASELINES='old-*.json'].
	@# No chip record is committed (PERF_LEDGER.jsonl is the driver's),
	@# so there is no default baseline. Exits 1 on tok/s / MFU / TTFT
	@# regression, 2 on schema violation (see benchmarks/BENCH_SCHEMA.md).
	$(PY) benchmarks/perf_gate.py $(BENCH) $(if $(BASELINES),--baseline-glob '$(BASELINES)')

cold-start: ## scale-from-zero SLO: serial vs streamed+warmed vs parked attach
	JAX_PLATFORMS=cpu $(PY) benchmarks/cold_start.py --json BENCH_cold_start.json

disagg-bench: ## unified vs disaggregated A/B at mixed prompt lengths -> BENCH_disagg.json
	@# Decode TPOT p95 for short streams while long prefills arrive;
	@# comparison block schema: benchmarks/BENCH_SCHEMA.md (perf_gate.py
	@# validates it). See docs/disaggregation.md.
	JAX_PLATFORMS=cpu $(PY) benchmarks/disagg_bench.py --json BENCH_disagg.json

kv-bench: ## KV restore vs replay resume latency at 512/2k/8k-token prefixes -> BENCH_kv_restore.json
	@# Parks serialized KV pages on a prefill engine, resumes on a cold
	@# decode engine with and without the page transfer; the resume-gap
	@# comparison block is validated by perf_gate.py (schema:
	@# benchmarks/BENCH_SCHEMA.md). See docs/robustness.md "State restore".
	JAX_PLATFORMS=cpu $(PY) benchmarks/kv_restore_bench.py --json BENCH_kv_restore.json
	$(PY) benchmarks/perf_gate.py BENCH_kv_restore.json

loadgen: ## tenant-mix load demo: real proxy+engine, weighted tenant population + mid-run heavy hitter -> /debug/tenants conservation + tenant_flood incident
	@# Exits nonzero unless >=3 tenants appear at /debug/tenants with
	@# conserved token totals AND the injected heavy hitter produces a
	@# tenant_flood incident whose snapshot carries the tenant
	@# breakdown. Summary under build/tenant-drill/. The fast variant
	@# runs in tier-1 (tests/test_tenants.py).
	JAX_PLATFORMS=cpu $(PY) benchmarks/tenant_drill.py

qos-drill: ## QoS isolation proof: batch flood vs interactive p99 TTFT, preemption with byte-correct resume
	@# Exits nonzero unless interactive p99 TTFT under a batch flood
	@# stays within tolerance of baseline, >=1 batch stream is preempted
	@# AND resumed byte-identically, and /debug/qos + the kubeai_qos_*
	@# counters report it. Summary under build/qos-drill/. The fast
	@# variant runs in tier-1 (tests/test_qos.py). See docs/qos.md.
	JAX_PLATFORMS=cpu $(PY) benchmarks/qos_drill.py

forecast-drill: ## predictive-scaling proof: seeded diurnal history, forecast-ahead scale-up beats the ramp by one cold-start lead -> BENCH_forecast.json
	@# Replays a compressed diurnal day against a real operator stack.
	@# Exits nonzero unless the source=forecast scale-up decision lands
	@# >= one MEASURED cold-start lead before the ramp peak, the A/B
	@# ramp p99 TTFT improves over reactive-only, the off-schedule
	@# flood raises traffic_anomaly (forecast section rendered in the
	@# postmortem), and the poisoned model holds the reactive floor
	@# while MAPE auto-disable engages. Summary under
	@# build/forecast-drill/; comparison block validated by perf_gate.py
	@# (schema: benchmarks/BENCH_SCHEMA.md). The fast variant runs in
	@# tier-1 (tests/test_forecast.py). See docs/autoscaling.md
	@# "Predictive scaling".
	JAX_PLATFORMS=cpu $(PY) benchmarks/forecast_drill.py --json BENCH_forecast.json
	$(PY) benchmarks/perf_gate.py BENCH_forecast.json

spike-drill: ## flash-crowd proof: quiet baseline -> 12x arrival burst -> recovery, per-phase p99 TTFT step -> BENCH_spike.json
	@# Replays one compressed spike day (loadgen --pattern spike with
	@# the burst multiplier raised to the 0->hundreds regime, rate-
	@# compressed for CPU engines) through a real 3-replica stack,
	@# bracketed by identical quiet baselines. Exits nonzero unless the
	@# burst actually delivered (>=3x base arrival rate in the spike
	@# window), ZERO requests were shed, quiet p99 TTFT recovered after
	@# the day drained, and the fleet quiesced. Summary under
	@# build/spike-drill/; BENCH_spike.json validated by perf_gate.py
	@# (schema: benchmarks/BENCH_SCHEMA.md). See docs/autoscaling.md.
	JAX_PLATFORMS=cpu $(PY) benchmarks/spike_drill.py --bench-json BENCH_spike.json
	$(PY) benchmarks/perf_gate.py BENCH_spike.json

gray-drill: ## gray-failure proof: 1-of-3 real replicas turns straggler, scorer soft-ejects it, p99 contained, batch tier still served
	@# Exits nonzero unless the per-token-slowed replica is soft-ejected
	@# by the latency scorer, fleet p99 TTFT stays within 1.25x the
	@# healthy baseline (+CPU noise grace), ZERO requests hard-fail, the
	@# straggler serves >=1 batch-class request, and the
	@# endpoint_degraded incident lands. Summary under build/gray-drill/.
	@# The fast variant runs in tier-1 (tests/test_gray_failure.py).
	@# See docs/robustness.md#gray-failures.
	JAX_PLATFORMS=cpu $(PY) benchmarks/gray_drill.py

incident-drill: ## e2e incident-black-box smoke: real proxy+engine, injected mid-stream kill, canary detection, persisted incident + rendered report
	@# Exits nonzero unless an incident lands with >=3 correlated
	@# sections AND the canary flags the failure within one probe
	@# period. Artifacts under build/incident-drill/.
	JAX_PLATFORMS=cpu KUBEAI_DEBUG_FAULTS=1 $(PY) benchmarks/incident_drill.py

INCIDENT_DIR ?=
INCIDENT_ID ?=
incident-report: ## render the latest captured incident as a correlated timeline
	@# Usage: make incident-report [INCIDENT_DIR=/path] [INCIDENT_ID=...]
	@# Default dir: $$KUBEAI_INCIDENT_DIR or /tmp/kubeai-incidents.
	$(PY) -m kubeai_tpu.obs.incident_report \
	    $(if $(INCIDENT_DIR),--dir $(INCIDENT_DIR)) \
	    $(if $(INCIDENT_ID),--id $(INCIDENT_ID))

OPERATOR_URL ?= http://localhost:8000
fleet-snapshot: ## dump EVERY surface the operator's GET /debug index lists (runbook capture)
	@# Usage: make fleet-snapshot [OPERATOR_URL=http://host:8000] — prints
	@# one JSON document keyed by path; redirect to a file for incident
	@# timelines. Surfaces come from the live /debug index, so new debug
	@# endpoints ride along without Makefile edits.
	$(PY) benchmarks/fleet_snapshot.py --url $(OPERATOR_URL)

bench-trend: ## render bench.py result files as one table: make bench-trend GLOB='results/*.json'
	@# tok/s, MFU, rate-controlled TTFT per file; CPU and failed runs
	@# are flagged, not plotted as real numbers. No result files are
	@# committed, so name them.
	$(PY) benchmarks/bench_trend.py --glob '$(GLOB)'

dryrun:  ## multi-chip sharding dryrun on 8 virtual CPU devices
	$(PY) __graft_entry__.py 8

native:  ## build the C++ fasthash extension explicitly
	$(PY) -c "from kubeai_tpu.utils.native import load; print(load())"

clean:
	rm -rf build .pytest_cache
	find . -name __pycache__ -type d -exec rm -rf {} +

charts: ## Render both Helm charts to build/manifests (helm-less template check)
	mkdir -p build/manifests
	$(PY) -m kubeai_tpu.utils.helmlite template charts/kubeai-tpu > build/manifests/operator.yaml
	$(PY) -m kubeai_tpu.utils.helmlite template charts/models > build/manifests/models.yaml

test-unit:  ## the fast tier (no engine e2e, no multi-process gangs)
	$(PY) -m pytest tests/ -q --ignore=tests/test_e2e_local.py \
	    --ignore=tests/test_e2e_chaos.py --ignore=tests/test_e2e_gang.py \
	    --ignore=tests/test_finetune.py --ignore=tests/test_gang_protocol.py

test-k8s:  ## replay the control-plane integration tests against a REAL cluster
	@# Usage: make test-k8s KUBECONFIG=~/.kube/config
	@# Spawns `kubectl proxy`, applies deploy/crds/, points KubeStore at it.
	@# Closes the env-blocked real-apiserver gap the day a cluster exists
	@# (ref: test/integration/main_test.go:77-114 is the envtest model).
	KUBEAI_K8S_TEST=1 $(PY) -m pytest tests/test_k8s_real.py -q -x

images: ## build operator, engine, and model-loader images
	$(DOCKER) build -t kubeai-tpu/operator:$(TAG) .
	$(DOCKER) build -f Dockerfile.engine -t kubeai-tpu/engine:$(TAG) .
	$(DOCKER) build -f components/model-loader/Dockerfile -t kubeai-tpu/model-loader:$(TAG) .

images-check: ## daemonless sanity: Dockerfiles reference only files that exist
	$(PY) tests/check_dockerfiles.py
