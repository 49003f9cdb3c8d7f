# Test entry points.  `make smoke` is the fast inner-loop subset (no
# multi-device subprocesses, no end-to-end transformer training); `make
# tier1` is the full suite ROADMAP.md names as the verify gate.  The
# subprocess-heavy tests spawn children with
# --xla_force_host_platform_device_count and are bounded by `timeout`.
PYTEST := env PYTHONPATH=src timeout

SMOKE_TIMEOUT ?= 300
TIER1_TIMEOUT ?= 900

.PHONY: smoke tier1 strategies elastic hybrid comm kernels serve obs

# Fast subset: pure-host unit tests (collectives shim units, compression,
# schedulers, configs, models). ~1 min.
smoke:
	$(PYTEST) $(SMOKE_TIMEOUT) python -m pytest -q -x \
	    tests/test_compression.py tests/test_comm_scheduler.py \
	    tests/test_configs.py tests/test_specs.py tests/test_sched.py \
	    tests/test_data_parallel.py -k "not 8dev"

# Strategy-matrix gate: every registered (sync x arch x compression) cell
# runs 2 steps on 2 virtual devices (see docs/strategies.md); fails if a
# registered cell is untested or broken.
strategies:
	$(PYTEST) $(SMOKE_TIMEOUT) python tools/strategy_smoke.py

# Elasticity gate: one crash, one resize, one straggler, and one
# scheduler-trace-driven scenario on 2 virtual devices
# (see docs/elasticity.md); fails if any scenario can't recover.
elastic:
	$(PYTEST) $(SMOKE_TIMEOUT) python tools/elastic_smoke.py

# Hybrid-parallel gate: representative mesh x ZeRO cells (data x tensor
# x stage, ZeRO-1/2/3, sgd + adamw, compressed data axis) on 8 virtual
# devices (see docs/hybrid.md); uncompressed sgd cells are cross-checked
# against the single-device stacked reference.
hybrid:
	$(PYTEST) $(SMOKE_TIMEOUT) python tools/hybrid_smoke.py

# Communication-plane gate: every topology x codec cell with encoded
# payloads inside the schedule (wire=measured) on 4 virtual devices,
# with the measured-vs-modeled byte assertion (see docs/comm.md).
comm:
	$(PYTEST) $(SMOKE_TIMEOUT) python tools/comm_smoke.py

# Kernel-backend gate: every codec x backend cell on 4 virtual devices
# (ref vs kernel: losses in band, wire bytes bitwise) plus one
# flash-attention fwd/grad/decode cell, all in interpret mode
# (see docs/kernels.md).
kernels:
	$(PYTEST) $(SMOKE_TIMEOUT) python tools/kernel_smoke.py

# Serving gate: paged/contiguous/seed-loop token equivalence,
# continuous-vs-oneshot latency win, pool-exhaustion stalls, the
# autoscale->sched->elastic plan loop, and a 2-virtual-device
# tensor-parallel decode cell (see docs/serving.md).
serve:
	$(PYTEST) $(SMOKE_TIMEOUT) python tools/serve_smoke.py

# Observability gate: a traced bsp/ring/onebit@8 run on 8 virtual
# devices (well-formed Chrome trace, step->exchange->bucket nesting,
# same-seed byte identity, analyzer attribution + overlap bounds), a
# traced d2.t2.s2 pipeline run (measured vs analytic bubble fraction),
# and a traced serve episode (request lifecycles, KV occupancy, stall
# instants, SLO burn alert); see docs/observability.md.
obs:
	$(PYTEST) $(SMOKE_TIMEOUT) python tools/obs_smoke.py

# Full tier-1 verify (ROADMAP.md): the strategy-matrix, elasticity,
# hybrid-mesh, comm-plane, kernel-backend, serving and observability
# gates plus everything in tests/, including the
# 8-virtual-device subprocess tests and end-to-end training
# compositions.
tier1: strategies elastic hybrid comm kernels serve obs
	$(PYTEST) $(TIER1_TIMEOUT) python -m pytest -q
