#!/usr/bin/env bash
# Blessed tier-1 verify entry point: the ROADMAP.md "Tier-1 verify"
# command, verbatim, runnable from anywhere (builders and CI invoke
# this one script so the command can never drift between callers —
# update ROADMAP.md and this file together).
#
# Budget policy (ISSUE 14 satellite): every step prints its own wall
# seconds and pytest runs with --durations=20, so when the 870 s pytest
# budget is tight on a slow box (the PR 13 caveat: the FULL suite no
# longer fits there) the overrun is ATTRIBUTABLE to named steps/tests
# instead of anecdotal. The per-step sanitizer timeouts below are part
# of the same policy: a hung analyzer exits 124 in its own window and
# can never eat the pytest budget.
cd "$(dirname "$0")/.." || exit 1
# Cumulative wall clock vs the 870 s budget (ISSUE 20 satellite): the
# PR 13/14 caveat — the full stack of steps no longer fits the pytest
# budget on a slow box — made visible. Every step prints the running
# total and the script warns (without failing) once 80% is spent, so a
# creeping sanitizer step is caught the run it creeps, not when the
# budget finally bursts.
tstart=$(date +%s)
BUDGET=870
cum() {
  local c=$(( $(date +%s) - tstart ))
  echo "tier1: cumulative wall ${c}s / ${BUDGET}s budget"
  if (( c * 5 >= BUDGET * 4 )); then
    echo "tier1: WARNING: cumulative wall ${c}s past 80% of the ${BUDGET}s budget" >&2
  fi
}
t0=$(date +%s)
# Static analysis first (ISSUE 5): an un-baselined jaxlint finding fails
# tier-1 before any test runs (exit 1 = findings, 2 = analyzer crash —
# distinct so CI logs tell them apart).
env JAX_PLATFORMS=cpu python scripts/jaxlint.py actor_critic_tpu train.py --error-on-new || exit $?
echo "tier1: jaxlint wall $(( $(date +%s) - t0 ))s"; cum
t0=$(date +%s)
# Race sanitizer quick profile (ISSUE 7): 100 fixed-seed cooperative
# schedules over the queue/publisher/mailbox units, under its OWN
# timeout so a schedule hang (exit 124) cannot eat the pytest budget
# below (exit 1 = race detected, 2 = exerciser crash).
timeout -k 5 120 env JAX_PLATFORMS=cpu python scripts/racesan.py --schedules 100 || exit $?
echo "tier1: racesan wall $(( $(date +%s) - t0 ))s"; cum
t0=$(date +%s)
# Fleet chaos sanitizer quick profile (ISSUE 12): 30 fixed-seed chaos
# schedules over the gossip-fleet + gateway-swap units (real mailbox
# objects, injected kills/torn files/reordered delivery), under its
# OWN timeout like the racesan step (exit 1 = protocol violation
# detected, 2 = exerciser crash). --flight-dump (ISSUE 16) adds one
# REAL SIGKILL schedule with per-host telemetry and asserts the
# victim's crash flight ring was harvested into a rendered dump — the
# post-mortem path must produce evidence, not just not crash.
fleetdir=$(mktemp -d /tmp/tier1_flight.XXXXXX)
timeout -k 5 180 env JAX_PLATFORMS=cpu python scripts/fleetsan.py --schedules 30 --flight-dump "$fleetdir" || { rc=$?; rm -rf "$fleetdir"; exit $rc; }
ls "$fleetdir"/host*/flight_dump_*.json >/dev/null 2>&1 || { echo "tier1: fleetsan left no flight dump in $fleetdir" >&2; rm -rf "$fleetdir"; exit 1; }
rm -rf "$fleetdir"
echo "tier1: fleetsan wall $(( $(date +%s) - t0 ))s"; cum
t0=$(date +%s)
# Replica-kill-mid-swap schedule (ISSUE 17 leg b): 30 fixed-seed
# schedules over the horizontal scale-out propagation path — N
# MailboxPolicySyncer replicas consuming a publisher's mailbox under
# replica SIGKILL/restart + torn/replayed snapshots; proves a torn
# policy is never served and every replica (incl. the rejoiner)
# converges. Own timeout like the other sanitizer steps.
timeout -k 5 120 env JAX_PLATFORMS=cpu python scripts/fleetsan.py --scenario replica --schedules 30 || exit $?
echo "tier1: fleetsan-replica wall $(( $(date +%s) - t0 ))s"; cum
t0=$(date +%s)
# Numerics fault sanitizer quick profile (ISSUE 14): 16 fixed-seed
# poison schedules (nan/±inf/denormal/int8-saturating) through the REAL
# update/codec/publish/checkpoint objects — every poison must be
# blocked by its named guard (divergence event, checkpoint refusal,
# publish/mailbox/swap rejection, codec saturation) and the tolerated
# poisons must not over-fire. Own timeout like the other sanitizers
# (exit 1 = a guard failed/over-fired, 2 = exerciser crash).
timeout -k 5 240 env JAX_PLATFORMS=cpu python scripts/numsan.py --schedules 16 || exit $?
echo "tier1: numsan wall $(( $(date +%s) - t0 ))s"; cum
t0=$(date +%s)
# Performance budget sanitizer quick profile (ISSUE 15): the five
# steady-state programs (async PPO update host+device plane, off-policy
# ingest, serving dispatch, mixture fleet step) measured for
# dispatches/transfers/transferred-bytes/recompiles per block against
# the committed perf_budgets.json — a stray host round-trip, an extra
# dispatch, or a recompiling swap fails here before any test runs. Own
# timeout like the other sanitizers (exit 1 = budget violation
# detected, 2 = exerciser/manifest crash).
timeout -k 5 300 env JAX_PLATFORMS=cpu python scripts/perfsan.py --quick || exit $?
echo "tier1: perfsan wall $(( $(date +%s) - t0 ))s"; cum
t0=$(date +%s)
# Padding-lane poison sanitizer quick profile (ISSUE 20): 16 fixed-seed
# poison schedules through the REAL shape-stabilization seams (masked
# chunk tail, Pallas ragged-lane pad, parked mixture members, serving
# bucket backfill, non-leased ring slots) — each program runs twice,
# pad lanes zeroed vs poisoned (nan/±3e38/int8-saturating), and the
# valid-lane outputs must be BITWISE identical. Own timeout like the
# other sanitizers (exit 1 = a junk lane is observable, 2 = exerciser
# crash).
timeout -k 5 180 env JAX_PLATFORMS=cpu python scripts/padsan.py --quick || exit $?
echo "tier1: padsan wall $(( $(date +%s) - t0 ))s"; cum
t0=$(date +%s)
# Multi-process CPU smoke (ISSUE 9): a 2-process jax.distributed local
# cluster must come up against a localhost coordinator, train a few
# blocks through the global-mesh learner, and agree bit-exactly on the
# broadcast version counter + replicated-params fingerprint. Its OWN
# timeout, like the racesan step: a hung coordinator (wedged port,
# dead worker) must exit 124 here, not eat the pytest budget.
timeout -k 10 300 env JAX_PLATFORMS=cpu python scripts/launch_multihost.py --smoke || exit $?
echo "tier1: multihost-smoke wall $(( $(date +%s) - t0 ))s"; cum
t0=$(date +%s)
set -o pipefail; rm -f /tmp/_t1.log; timeout -k 10 870 env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'not slow' --durations=20 --continue-on-collection-errors -p no:cacheprovider -p no:xdist -p no:randomly 2>&1 | tee /tmp/_t1.log; rc=${PIPESTATUS[0]}; echo DOTS_PASSED=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' /tmp/_t1.log | tr -cd . | wc -c); echo "tier1: pytest wall $(( $(date +%s) - t0 ))s"; cum; exit $rc
