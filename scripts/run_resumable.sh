#!/usr/bin/env bash
# Self-healing wrapper for long host-training runs (SURVEY.md §5.3).
#
# Pair with train.py's stall watchdog: when a device call wedges
# mid-run, the watchdog exits 42, and this wrapper restarts the run with
# --resume from the last orbax checkpoint. Any other exit code passes
# through. The retry budget counts CONSECUTIVE no-progress attempts: a
# resume that advanced the checkpoint resets it, so a multi-day run that
# wedges many times — but always past a fresh checkpoint — keeps going,
# while a wedge that recurs before ANY checkpoint lands gives up after
# MAX_RETRIES instead of replaying the same prefix forever.
#
#   scripts/run_resumable.sh --preset sac_humanoid --ckpt-dir runs/hum \
#       --save-every 1000 --stall-timeout 300 --eval-every 1000
#
# --fresh (consumed here, not passed to train.py): refuse to start if the
# ckpt-dir already holds a checkpoint. Evidence runs want this — reusing a
# dir from an earlier leg would silently resume foreign state (worst case
# a --no-save-replay checkpoint, whose replay-free resume measurably
# degrades the actor; ADVICE.md round 4 #1).
set -u
MAX_RETRIES=${MAX_RETRIES:-10}

ckpt_dir=""
cache_dir=""
cache_passed=0  # --compile-cache-dir given on the command line
fresh=0
prev=""
args=()
# train.py options that take a VALUE: a literal "--fresh" right after one
# of these is that option's argument, not our flag (e.g. a metrics file
# named --fresh), and must pass through untouched. Mirrors train.py's
# argparse spec; boolean flags (--quiet, --resume, --warmup ...) are
# absent on purpose.
takes_value() {
  case "$1" in
    --preset|--algo|--env|--iterations|--seed|--set|--env-set|--metrics|\
    --telemetry-dir|--telemetry-port|--telemetry-sample-s|--log-every|\
    --chunk|--eval-every|--eval-envs|--eval-steps|--workers|--ckpt-dir|\
    --compile-cache-dir|--save-every|--stall-timeout|--async-actors|\
    --updates-per-block|--max-staleness|--queue-depth|--async-correction|\
    --replay-dtype|--curriculum|--data-plane|--data-plane-codec|\
    --serve-port|--serve-buckets)
      return 0 ;;
  esac
  return 1
}
for a in "$@"; do
  if [ "$a" = "--fresh" ] && ! takes_value "$prev"; then
    fresh=1; prev="$a"; continue
  fi
  if [ "$prev" = "--ckpt-dir" ]; then ckpt_dir="$a"; fi
  if [ "$prev" = "--compile-cache-dir" ]; then cache_dir="$a"; cache_passed=1; fi
  args+=("$a")
  prev="$a"
done
# Every leg shares the persistent compilation cache, so leg N>0 skips
# XLA compile. Mirror compile_cache.resolve_cache_dir exactly so --fresh
# knows which directory to wipe:
#   'none'/'off' (ANY case — python lowercases) or an explicit empty
#     value → DISABLED, never a literal path (wiping a "None" directory
#     would delete unrelated cwd state);
#   JAX_COMPILATION_CACHE_DIR set → that directory is the cache, placed
#     from OUTSIDE this run: it is never this script's to delete;
#   else an explicit --compile-cache-dir, else <checkout>/.jax_cache.
cache_lc=$(printf '%s' "$cache_dir" | tr '[:upper:]' '[:lower:]')
case "$cache_passed:$cache_lc" in
  1:|1:none|1:off) cache_dir="" ;;
  *)
    if [ -n "${JAX_COMPILATION_CACHE_DIR:-}" ]; then cache_dir=""
    elif [ "$cache_passed" -eq 0 ]; then
      cache_dir="$(cd "$(dirname "$0")/.." && pwd)/.jax_cache"
    fi ;;
esac
# ${args[@]+...}: bash < 4.4 treats expanding an EMPTY array as an unset-
# variable error under `set -u`; the parameter-expansion guard is the
# portable spelling (a bare "${args[@]}" aborts the wrapper when train.py
# is invoked with --fresh as its only argument).
set -- ${args[@]+"${args[@]}"}

if [ "$fresh" -eq 1 ] && [ -n "$ckpt_dir" ] && [ -d "$ckpt_dir" ] \
    && ls "$ckpt_dir" 2>/dev/null | grep -qE '^[0-9]+$'; then
  echo "[run_resumable] --fresh: $ckpt_dir already contains a checkpoint;" \
       "refusing to start an evidence run over foreign state" >&2
  exit 3
fi
if [ "$fresh" -eq 1 ] && [ -n "$cache_dir" ] && [ -d "$cache_dir" ]; then
  # A fresh evidence run must also start compile-fresh: stale cache
  # entries (old jax/XLA flags, a since-edited model) would make leg 0's
  # "cold" startup measurement quietly warm.
  echo "[run_resumable] --fresh: wiping compile cache $cache_dir" >&2
  rm -rf "$cache_dir"
fi

latest_step() {
  [ -n "$ckpt_dir" ] && [ -d "$ckpt_dir" ] || { echo -1; return; }
  ls "$ckpt_dir" 2>/dev/null | grep -E '^[0-9]+$' | sort -n | tail -1 || echo -1
}

python train.py "$@"
rc=$?
tries=0
last_seen=$(latest_step)
while [ "$rc" -eq 42 ] && [ "$tries" -lt "$MAX_RETRIES" ]; do
  tries=$((tries + 1))
  echo "[run_resumable] stall exit 42 — resuming (no-progress attempt $tries/$MAX_RETRIES)" >&2
  python train.py "$@" --resume
  rc=$?
  now_seen=$(latest_step)
  if [ "${now_seen:-"-1"}" != "${last_seen:-"-1"}" ]; then
    tries=0  # the checkpoint advanced: this was not a futile retry
    last_seen="$now_seen"
  fi
done
exit "$rc"
