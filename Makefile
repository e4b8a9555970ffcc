# Tier-1 verification: the full test suite exactly as CI runs it.
PY ?= python

# every bench/validate step below names this EXACT file — the bench
# prints the path it wrote and the validate step consumes the same
# variable, so a redirected --out can never validate a stale artifact
BENCH_OUT ?= artifacts/benchmarks/BENCH_scale.json
BENCH_BASELINE ?= benchmarks/baselines/BENCH_scale.baseline.json
BENCH_TOLERANCE ?= 0.25

.PHONY: verify test lint analyze bench-fig4 bench-scale \
	bench-scale-smoke bench-baseline experiments-smoke \
	elastic-emulated-smoke online-smoke faults-smoke calibration-smoke

verify test:
	PYTHONPATH=src $(PY) -m pytest -x -q

# the CI lint tier (ruff E/F + isort + bugbear; see pyproject)
lint:
	ruff check .

# repo-invariant static analysis: parity-oracle registry, RNG-stream
# discipline, jit/cache-key hygiene, determinism sources (RPL0xx rules;
# see src/repro/analysis/__init__.py for the catalog)
analyze:
	PYTHONPATH=src $(PY) -m repro.analysis

bench-fig4:
	PYTHONPATH=src $(PY) benchmarks/bench_fig4_cluster.py --rounds 50

# swarm-scale sweep: scalar vs exact-fast vs batched, 1k -> 10k
# fully-participating clients plus the sampled 100k/1M-pool rungs;
# writes + schema-checks $(BENCH_OUT)
bench-scale:
	PYTHONPATH=src $(PY) benchmarks/bench_scale.py --out $(BENCH_OUT)
	PYTHONPATH=src $(PY) benchmarks/bench_scale.py --validate $(BENCH_OUT)

# CI smoke: schema gate + wall-clock regression gate against the
# checked-in baseline (fails past $(BENCH_TOLERANCE) normalized drift)
bench-scale-smoke:
	PYTHONPATH=src $(PY) benchmarks/bench_scale.py --smoke --out $(BENCH_OUT)
	PYTHONPATH=src $(PY) benchmarks/bench_scale.py --validate $(BENCH_OUT) \
		--compare-baseline $(BENCH_BASELINE) \
		--tolerance $(BENCH_TOLERANCE)

# refresh the checked-in regression baseline after INTENTIONAL perf
# changes (commit the result)
bench-baseline:
	PYTHONPATH=src $(PY) benchmarks/bench_scale.py --smoke \
		--out $(BENCH_BASELINE)

# the CI smoke job, runnable locally: both paper tracks + one event
# scenario through the experiments CLI, then schema validation
experiments-smoke:
	PYTHONPATH=src $(PY) -m repro.experiments run paper-fig4 \
		--rounds 3 --strategies pso,random \
		--out artifacts/experiments/fig4_smoke.json
	PYTHONPATH=src $(PY) -m repro.experiments run paper-fig3 \
		--rounds 10 --strategies pso --set depth=3 --set width=4 \
		--out artifacts/experiments/fig3_smoke.json
	PYTHONPATH=src $(PY) -m repro.experiments run churn \
		--rounds 10 --seeds 0,1 --strategies pso,random \
		--out artifacts/experiments/churn_smoke.json
	PYTHONPATH=src $(PY) -m repro.experiments run flash-crowd \
		--rounds 25 --seeds 0 --strategies pso,random \
		--set eval.mode=sequential \
		--out artifacts/experiments/flash_crowd_seq_smoke.json
	PYTHONPATH=src $(PY) -m repro.experiments run flash-crowd \
		--rounds 25 --seeds 0 --strategies pso,random \
		--set eval.mode=batched \
		--out artifacts/experiments/flash_crowd_bat_smoke.json
	PYTHONPATH=src $(PY) -m repro.experiments run composite-storm \
		--rounds 40 --seeds 0,1 --strategies pso,random \
		--set eval.mode=batched \
		--out artifacts/experiments/composite_storm_smoke.json
	PYTHONPATH=src $(PY) -m repro.experiments validate \
		artifacts/experiments/fig4_smoke.json \
		artifacts/experiments/fig3_smoke.json \
		artifacts/experiments/churn_smoke.json \
		artifacts/experiments/flash_crowd_seq_smoke.json \
		artifacts/experiments/flash_crowd_bat_smoke.json \
		artifacts/experiments/composite_storm_smoke.json

# the elastic presets on the EMULATED track (orchestrator-level
# admit/retire): small model, <=5 rounds, event timing tightened so the
# capacity window is crossed inside the smoke budget; artifacts are
# schema-v2 with a topology_version series showing the
# re-hierarchizations
elastic-emulated-smoke:
	PYTHONPATH=src $(PY) -m repro.experiments run flash-crowd \
		--env emulated --rounds 5 --seeds 0 --strategies pso,random \
		--set model=mlp-smoke --set local_steps=1 --set batch_size=16 \
		--set 'events=[{"event":"ClientJoin","every":1,"count":8,"first_round":1,"last_round":3}]' \
		--out artifacts/experiments/flash_crowd_emulated_smoke.json
	PYTHONPATH=src $(PY) -m repro.experiments run ebb-and-flow \
		--env emulated --rounds 5 --seeds 0 --strategies pso,random \
		--set model=mlp-smoke --set local_steps=1 --set batch_size=16 \
		--set 'events=[{"event":"ClientJoin","every":2,"count":10,"first_round":1},{"event":"ClientLeave","every":2,"count":10,"first_round":2,"min_clients":11}]' \
		--out artifacts/experiments/ebb_and_flow_emulated_smoke.json
	PYTHONPATH=src $(PY) -m repro.experiments validate \
		artifacts/experiments/flash_crowd_emulated_smoke.json \
		artifacts/experiments/ebb_and_flow_emulated_smoke.json

# the asynchronous online track end-to-end: the jittered async preset,
# the delay-triggered re-optimization preset, and the degenerate
# lockstep twin — small model, <=5 rounds, schema-validated artifacts,
# plus the BENCH_online.json smoke (overlap/staleness/rounds-per-sec +
# the degenerate==emulated parity claim)
online-smoke:
	PYTHONPATH=src $(PY) -m repro.experiments run online-fig4 \
		--rounds 5 --seeds 0 --strategies pso,random \
		--set model=mlp-smoke \
		--out artifacts/experiments/online_fig4_smoke.json
	PYTHONPATH=src $(PY) -m repro.experiments run online-straggler \
		--rounds 5 --seeds 0 --strategies pso,random \
		--set model=mlp-smoke \
		--out artifacts/experiments/online_straggler_smoke.json
	PYTHONPATH=src $(PY) -m repro.experiments run online-sync \
		--rounds 3 --seeds 0 --strategies pso \
		--set model=mlp-smoke \
		--out artifacts/experiments/online_sync_smoke.json
	PYTHONPATH=src $(PY) -m repro.experiments validate \
		artifacts/experiments/online_fig4_smoke.json \
		artifacts/experiments/online_straggler_smoke.json \
		artifacts/experiments/online_sync_smoke.json
	PYTHONPATH=src $(PY) benchmarks/bench_online.py --smoke \
		--out artifacts/benchmarks/BENCH_online.json
	PYTHONPATH=src $(PY) benchmarks/bench_online.py \
		--validate artifacts/benchmarks/BENCH_online.json

# the fault track end-to-end: both fault presets (seeded crashes,
# drops+retries, link degradation, partitions, aggregator failovers,
# quorum-gated merges) — small model, <=5 rounds, schema-v3-validated
# artifacts, plus the BENCH_faults.json smoke (survivability /
# recovery-overhead rows + the zero-fault bit-identity claim)
faults-smoke:
	PYTHONPATH=src $(PY) -m repro.experiments run online-faulty \
		--rounds 5 --seeds 0 --strategies pso,random \
		--set model=mlp-smoke \
		--out artifacts/experiments/online_faulty_smoke.json
	PYTHONPATH=src $(PY) -m repro.experiments run chaos \
		--rounds 5 --seeds 0 --strategies pso,random \
		--set model=mlp-smoke \
		--out artifacts/experiments/chaos_smoke.json
	PYTHONPATH=src $(PY) -m repro.experiments validate \
		artifacts/experiments/online_faulty_smoke.json \
		artifacts/experiments/chaos_smoke.json
	PYTHONPATH=src $(PY) benchmarks/bench_faults.py --smoke \
		--out artifacts/benchmarks/BENCH_faults.json
	PYTHONPATH=src $(PY) benchmarks/bench_faults.py \
		--validate artifacts/benchmarks/BENCH_faults.json

# the trace-calibration loop end-to-end: record an emulated mlp-smoke
# trace through the CLI, fit with a held-out tail, replay-compare the
# fitted calibration against the analytic baseline, then the
# BENCH_calibration.json smoke (asserts the fitted model strictly beats
# analytic on held-out rounds)
calibration-smoke:
	PYTHONPATH=src $(PY) -m repro.calibration record paper-fig4 \
		--rounds 4 --set model=mlp-smoke --set local_steps=1 \
		--set batch_size=16 \
		--out artifacts/calibration/trace_fig4_smoke.json
	PYTHONPATH=src $(PY) -m repro.calibration validate \
		artifacts/calibration/trace_fig4_smoke.json
	PYTHONPATH=src $(PY) -m repro.calibration fit \
		artifacts/calibration/trace_fig4_smoke.json --holdout 1 \
		--out artifacts/calibration/cal_fig4_smoke.json
	PYTHONPATH=src $(PY) -m repro.calibration report \
		artifacts/calibration/trace_fig4_smoke.json \
		--calibration artifacts/calibration/cal_fig4_smoke.json \
		--rounds 3
	PYTHONPATH=src $(PY) benchmarks/bench_calibration.py --smoke \
		--out artifacts/benchmarks/BENCH_calibration.json
	PYTHONPATH=src $(PY) benchmarks/bench_calibration.py \
		--validate artifacts/benchmarks/BENCH_calibration.json
