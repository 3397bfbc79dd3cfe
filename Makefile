# dragnet-tpu build/test entry points (the reference's Makefile wired
# `make` = deps, `make test` = catest -a, `make check` = lint;
# Makefile:13-34).

PYTHON ?= python3

.PHONY: all native test check chip-smoke \
    soak-faults soak-cluster soak-follow soak-compact \
    soak-overload soak-rebalance soak-scrub soak-resources \
    soak-subscribe \
    clean parity-matrix

all: native

native:
	$(MAKE) -C native

test: native
	$(PYTHON) -m pytest tests/ -q

check:
	$(PYTHON) -m compileall -q dragnet_tpu bin/dn.py bench.py \
	    chip_smoke.py __graft_entry__.py tests
	$(PYTHON) tools/checkstyle dragnet_tpu bin tests \
	    tools/checkstyle tools/json_streamer tools/pathenum \
	    tools/validate-schema tools/mktestdata \
	    tools/soak_faults.py tools/hostmem_count.py bench.py chip_smoke.py \
	    __graft_entry__.py

# the quickest proof that scan, build and query still run on the chip:
# forced device lanes through bin/dn at 2M records, byte-compared with
# DN_ENGINE=vector; the last stdout line is the JSON verdict (exit 0
# only on a TPU).  It builds native/ itself.
chip-smoke:
	$(PYTHON) chip_smoke.py

# the chaos soak: mixed scan/query/build under deterministic fault
# injection (>= 500 faults across every DN_FAULTS site) plus
# mid-flush SIGKILL crash drills — asserts zero torn shards and
# byte-identical output vs a fault-free run (docs/robustness.md)
soak-faults: native
	JAX_PLATFORMS=cpu $(PYTHON) tools/soak_faults.py

# the scatter-gather cluster drill: 3 members x 2-replica partitions
# under armed router/member/transport faults, a SIGKILL'd partition
# owner mid-query, and a no-surviving-replica degraded check —
# asserts byte-identity whenever a replica survives and the clean
# degraded-or-error contract when none does (docs/serving.md)
soak-cluster: native
	JAX_PLATFORMS=cpu $(PYTHON) tools/soak_faults.py --cluster

# the continuous-ingest drill: an appender races a `dn follow` daemon
# under armed follow.read/checkpoint/publish faults with mid-publish
# SIGKILL drills — after every kill the resumed tree must byte-equal
# a from-scratch build over the checkpointed prefix (docs/ingest.md)
soak-follow: native
	JAX_PLATFORMS=cpu $(PYTHON) tools/soak_faults.py --follow

# the background-compaction drill: follow --append mini-generations
# under remote query flood while a serve-resident compactor and
# rollup builder rewrite the tree with compact.publish/rollup.publish
# faults armed; subprocess dn compact/rollup SIGKILLed on both sides
# of the commit record — every accepted response byte-equals a
# from-scratch build and the converged tree byte-equals it shard for
# shard (docs/robustness.md)
soak-compact: native
	JAX_PLATFORMS=cpu $(PYTHON) tools/soak_faults.py --compact

# the overload drill: multi-tenant flood at ~5x capacity against the
# 3-member cluster with torn-frame/stall/flood faults armed, tenant
# weights 3:1, and a mid-flood SIGKILL of one member — asserts zero
# hangs, zero byte-diffs on accepted requests, retry_after_ms on
# busy/overloaded rejections, fairness within 2x of weights
soak-overload: native
	JAX_PLATFORMS=cpu $(PYTHON) tools/soak_faults.py --overload

# the live-resize drill: a serving cluster grows 3->5 and shrinks
# 5->2 members under routed-query flood with armed handoff/topology
# faults, joiners streaming their shards into EMPTY private trees,
# a mid-handoff SIGKILL of a joiner (restart + idempotent re-pull)
# and a donor SIGKILL mid-flood — asserts zero byte-diffs vs the
# single-process goldens, zero dropped partitions, zero hangs
soak-rebalance: native
	JAX_PLATFORMS=cpu $(PYTHON) tools/soak_faults.py --rebalance

# shard-integrity: flip random bytes in committed shards across a
# 3-member cluster (private byte-identical trees) under routed flood
# with DN_VERIFY=open + a 1s background scrub — asserts zero silently
# wrong result bytes (every corruption detected as a clean retryable/
# degraded error or transparently failed over) and every damaged
# shard repaired from a co-replica, byte-identical to its catalog
soak-scrub: native
	JAX_PLATFORMS=cpu $(PYTHON) tools/soak_faults.py --scrub

# resource-exhaustion survival: a 3-member routed cluster under query
# flood while the simulated disk (DN_DISK_SIM_FILE) is forced through
# a full low -> critical -> recovered cycle, with enospc/emfile
# faults armed at every write seam — asserts queries byte-identical
# throughout (including the read-only window), builds rejected with
# the clean retryable disk-full error while critical, automatic write
# resumption on recovery, zero torn shards, zero stranded tmps
soak-resources: native
	JAX_PLATFORMS=cpu $(PYTHON) tools/soak_faults.py --resources

# the standing-query drill: a `dn subscribe` flood over the 3-member
# cluster while publishes land under armed push/transport faults
# (torn push frames force token resume), with a publisher subprocess
# and a CLI subscriber SIGKILLed mid-stream — asserts pushed-vs-polled
# byte identity at every quiescent epoch, zero torn shards after the
# publisher kill, dead-subscriber shedding, and zero wedges
soak-subscribe: native
	JAX_PLATFORMS=cpu $(PYTHON) tools/soak_faults.py --subscribe

# golden byte-parity under every engine (the strongest single seal:
# host per-record, vectorized, forced device, auto router), then the
# forced raw-byte ingest lane (DN_PARSE=vector) over the vector engine
parity-matrix: native
	@for e in host vector jax auto; do \
	    echo "== DN_ENGINE=$$e =="; \
	    DN_ENGINE=$$e $(PYTHON) -m pytest tests/parity/ -q || exit 1; \
	done
	@echo "== DN_PARSE=vector =="
	@DN_PARSE=vector $(PYTHON) -m pytest tests/parity/ -q

clean:
	rm -rf native/build
	find . -name __pycache__ -type d -prune -exec rm -rf {} +
