#!/usr/bin/env bash
# CI entry (ref: paddle/scripts/paddle_build.sh) — build native components,
# run the test suite on the 8-device virtual CPU mesh, gate the public API
# surface, and smoke the benchmark in a tiny configuration.
set -euo pipefail
cd "$(dirname "$0")/.."

echo "== native components =="
make -C paddle_tpu/native

echo "== api surface =="
python tools/print_signatures.py --check API.spec

echo "== program lint over models/ (passes verifier; errors fail the build) =="
JAX_PLATFORMS=cpu python tools/program_lint.py --models

echo "== program doctor over models/ (dataflow engine: liveness, hazards, peak-bytes, donation plan; any NEW hazard vs the checked-in baseline fails) =="
JAX_PLATFORMS=cpu PTPU_STRICT_VERIFY=1 \
python tools/program_doctor.py --models --check-baseline tools/doctor_baseline.json

echo "== tests (8-device virtual cpu mesh, tier-1: not slow) =="
# tier-1 includes tests/test_multi_step.py (K-step dispatch bit-identity)
# and the prefetch-ring units in test_data_pipeline.py; the threaded ring
# stress variant is slow-marked and runs in the slow tier below
python -m pytest tests/ -q -m 'not slow'

echo "== multi-step dispatch smoke (CPU, K=4 smallnet + fc dispatch A/B) =="
JAX_PLATFORMS=cpu python scripts/multi_step_smoke.py

echo "== bulk-inference loop smoke (CPU, run_batches bit-identity + >=3x dispatch A/B) =="
JAX_PLATFORMS=cpu python scripts/infer_loop_smoke.py

echo "== mfu pass smoke (googlenet horizontal_fuse + stacked-LSTM fuse_layers A/B in one session: numeric parity asserted; CPU speedups emitted, not asserted — the MXU-padding/scan-dispatch wins are TPU-only, PERF_NOTES round 18) =="
JAX_PLATFORMS=cpu python scripts/mfu_smoke.py

echo "== warm-start smoke (persistent compile cache: cold A/B warm in fresh processes, >=3x artifact cold-start cut, cache_ctl stats/prune/prewarm) =="
JAX_PLATFORMS=cpu python scripts/warm_start_smoke.py

echo "== donation smoke (certified warm-path state donation: 0 compiles, in-place state update recovered, bit-identity across donated/undonated/uncached arms) =="
JAX_PLATFORMS=cpu python scripts/donation_smoke.py

echo "== remat smoke (activation recompute A/B on BERT-tiny: bitwise loss parity with dropout on + >=30% measured XLA temp-bytes reduction for the compiled train step) =="
JAX_PLATFORMS=cpu python scripts/remat_smoke.py

echo "== crash-resume smoke (SIGKILL mid-epoch -> seconds-scale resume with bit/loss parity; chaos kill+corrupt rounds; checkpoint stall < 2%) =="
JAX_PLATFORMS=cpu python scripts/crash_resume_smoke.py

echo "== pod fault-tolerance smoke (2-process composed-mesh kill-one-host + full-pod resume in seconds off the warm compile cache; sharded two-phase checkpoints, stall < 2%, chaos --pod round with corruption) =="
JAX_PLATFORMS=cpu python scripts/pod_ft_smoke.py

echo "== elastic resume smoke (topology-change restore: 4-host run killed mid-epoch, resumed on 2 AND 8 hosts with loss parity within float tolerance + exactly-once epoch digests; same-shape resume bit-exact with 0 resharding programs; chaos --resize round) =="
JAX_PLATFORMS=cpu python scripts/elastic_resume_smoke.py

echo "== data plane smoke (sharded streaming input: serial-vs-pooled feeder A/B >=3x with bit-identical epochs, exactly-once journal resume, host-stall < 2% on the smallnet loop) =="
JAX_PLATFORMS=cpu python scripts/data_plane_smoke.py

echo "== slow tier (threaded stress) =="
python -m pytest tests/ -q -m slow

echo "== serving bench smoke (serve.py bench on a tiny artifact) =="
python scripts/serve_bench_smoke.py

echo "== decode serving smoke (continuous in-flight batching: Poisson A/B >=3x tokens/s vs sequential decode, bit-identical transcripts, 0-compile warm replica; block tier: prefix-share A/B >=1.5x effective capacity at fixed cache HBM, beam reorder >=10x fewer dispatch bytes block-level) =="
JAX_PLATFORMS=cpu python scripts/decode_serve_smoke.py

echo "== speculative decode smoke (draft-and-verify over the block-paged cache: bit-identical transcripts across plain/ngram/adversarial arms, >=1.5x tokens/s on the screened repetitive-suffix workload, zero-acceptance arm <=1.15x via acceptance-aware backoff) =="
JAX_PLATFORMS=cpu python scripts/spec_decode_smoke.py

echo "== quantized serving smoke (int8 tier: calibrate -> export both tiers, top-1 parity, 0-compile warm int8 replica, >=1.3x fixed-cache-HBM decode throughput via 2x max_slots) =="
JAX_PLATFORMS=cpu python scripts/quant_smoke.py

echo "== serving fleet smoke (3-replica warm fleet 0 compiles at spin-up; SIGKILL chaos loses only the victim's in-flight work with bit-identical survivors; autoscaler holds p99 TTFT across a 5x Poisson swing with zero dropped streams; rolling int8 rollout promotes on parity and rolls back loudly on an injected failure; fleet_ctl 0/1/2 exit codes) =="
JAX_PLATFORMS=cpu python scripts/fleet_smoke.py

echo "== serving gateway smoke (serve.py gateway over a 2-replica fleet: SSE byte-identical to the direct predictor; 401/429 admission with Retry-After; SIGKILL chaos 502s only the victim's in-flight streams; SIGTERM drain finishes every stream and exits 0) =="
JAX_PLATFORMS=cpu python scripts/gateway_smoke.py

echo "CI OK"
