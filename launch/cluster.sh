#!/usr/bin/env bash
# Multi-process cluster launcher (DESIGN.md §17): spawn NPROCS run_pdf
# workers on this host, each pinned to one seat of the placement
# (--num-processes/--process-id), sharing one jax.distributed coordinator
# and one --out-dir. It is the CPU rehearsal of a multi-node topology, and
# it runs every worker on the CPU (JAX_PLATFORMS=cpu): a chip belongs to
# one process, so on a host with chips a second worker would fail on the
# chip's lock. There, one process drives every local chip through
# execution.placement.shard_devices. Usage:
#
#   launch/cluster.sh NPROCS [run_pdf flags...]
#
# Every flag after NPROCS is passed through to every worker — give them a
# shared --out-dir (required in cluster mode). They share one compile cache
# (JAX_COMPILATION_CACHE_DIR, else --compile-cache-dir, else .jax_cache/ in
# the checkout), so only the first launch ever compiles. Environment:
#
#   COORD_PORT          coordinator port (default 12723)
#   CLUSTER_REF         a reference out_dir: after the run, verify this
#                       run's --out-dir is bitwise-identical to it and
#                       print the invariant line CI greps for
#   CPU_DEVICES_PER_PROC  host-platform device count per worker (default 1)
#
# Env hardening per the SNIPPETS run.sh recipes: tcmalloc preload (when
# present), silenced TF/absl logging, a pinned host device count, and
# explicit x64 settings (the pipeline's f64 work goes through its own
# "x64 lanes" emulation — JAX_ENABLE_X64 stays off so traces match the
# single-process/test configuration bit for bit).
set -euo pipefail

if [ "$#" -lt 1 ]; then
    echo "usage: launch/cluster.sh NPROCS [run_pdf flags...]" >&2
    exit 2
fi
NPROCS="$1"; shift

# -- env hardening (SNIPPETS: HomebrewNLP-Jax/olmax run.sh) -------------------
TCMALLOC=/usr/lib/x86_64-linux-gnu/libtcmalloc.so.4
if [ -f "$TCMALLOC" ]; then
    export LD_PRELOAD="$TCMALLOC"                          # faster malloc
    export TCMALLOC_LARGE_ALLOC_REPORT_THRESHOLD=60000000000  # no numpy spam
fi
export TF_CPP_MIN_LOG_LEVEL=4                              # no XLA chatter
export JAX_PLATFORMS=cpu          # one process per chip: workers stay on CPU
export JAX_ENABLE_X64=0           # f64 runs through the x64-lanes emulation
export JAX_DEFAULT_DTYPE_BITS=32
export JAX_NUM_CPU_DEVICES="${CPU_DEVICES_PER_PROC:-1}"
export XLA_FLAGS="--xla_force_host_platform_device_count=${CPU_DEVICES_PER_PROC:-1} ${XLA_FLAGS:-}"
REPO_ROOT="$(cd "$(dirname "$0")/.." && pwd)"
export PYTHONPATH="$REPO_ROOT/src${PYTHONPATH:+:$PYTHONPATH}"

COORD="127.0.0.1:${COORD_PORT:-12723}"

# The shared out_dir is also where the marker protocol lives — find it in
# the pass-through flags so the optional CLUSTER_REF verification knows
# what to compare.
OUT_DIR=""
prev=""
for arg in "$@"; do
    if [ "$prev" = "--out-dir" ]; then OUT_DIR="$arg"; fi
    prev="$arg"
done

echo "[cluster.sh] launching $NPROCS worker(s), coordinator $COORD"
pids=()
for i in $(seq 0 $((NPROCS - 1))); do
    python -m repro.launch.run_pdf \
        --num-processes "$NPROCS" --process-id "$i" --coordinator "$COORD" \
        "$@" 2>&1 | sed "s/^/[proc $i] /" &
    pids+=($!)
done
status=0
for pid in "${pids[@]}"; do
    wait "$pid" || status=$?
done
if [ "$status" -ne 0 ]; then
    echo "[cluster.sh] a worker failed (exit $status)" >&2
    exit "$status"
fi

if [ -n "${CLUSTER_REF:-}" ]; then
    if [ -z "$OUT_DIR" ]; then
        echo "[cluster.sh] CLUSTER_REF set but no --out-dir flag found" >&2
        exit 2
    fi
    python -m repro.runtime.cluster --compare "$CLUSTER_REF" "$OUT_DIR"
fi
echo "[cluster.sh] done"
