#!/usr/bin/env sh
# One-command multi-process SPMD mesh bring-up (ISSUE 12).
# Spawns N OS processes as ONE logical jax.distributed mesh, serves a
# smoke query over the HTTP wire, and keeps serving until Ctrl-C.
# CPU harness for the multi-process protocol: every child runs on
# virtual CPU devices and holds no chip. On a TPU host, bin/startYACY.sh
# serves from all chips in ONE process (index.device.mesh=auto).
#
#   bin/startMESH.sh [procs] [local_devices] [extra launcher args...]
#
# Examples:
#   bin/startMESH.sh            # 2 processes x 2 CPU devices
#   bin/startMESH.sh 3 2 --ndocs 2000
cd "$(dirname "$0")/.." || exit 1
PROCS="${1:-2}"; shift 2>/dev/null
LOCAL="${1:-2}"; shift 2>/dev/null
exec python -m yacy_search_server_tpu.parallel.launcher \
    --procs "$PROCS" --local-devices "$LOCAL" --serve "$@"
