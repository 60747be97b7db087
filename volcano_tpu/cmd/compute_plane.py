"""Compute-plane sidecar entry point.

The scheduler daemon runs the control plane; this process owns the
device and serves the packed kernels over the versioned socket protocol
(serving/compute_plane.py).  On a chip host exactly one process holds
the chip, and with a sidecar that process is this one: start the
scheduler with ``VTPU_COMPUTE_PLANE=<socket>``, which pins the
scheduler's JAX to the CPU (cmd/scheduler.py).  If the sidecar dies the
scheduler's executors fall back in-process on the host, count the
fallback, and re-probe.

Usage: python -m volcano_tpu.cmd.compute_plane --socket /run/vtpu.sock
"""

from __future__ import annotations

import argparse
import time

from volcano_tpu.serving.compute_plane import ComputePlaneServer
from volcano_tpu.utils.compile_cache import configure_compile_cache
from volcano_tpu.utils.logging import get_logger

log = get_logger(__name__)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(prog="vtpu-compute-plane")
    parser.add_argument("--socket", default="/tmp/vtpu-compute-plane.sock")
    parser.add_argument(
        "--warmup", action="store_true",
        help="compile the headline-bucket kernels before serving",
    )
    parser.add_argument(
        "--faults", default="",
        help="deterministic fault-injection schedule (compute.* and "
        "device.* points fire in this process; same grammar as "
        "VTPU_FAULTS)",
    )
    args = parser.parse_args(argv)
    from volcano_tpu.cmd.daemon import apply_faults

    apply_faults(args.faults)
    configure_compile_cache()

    if args.warmup:
        # populate the jit cache so the first real session doesn't pay
        # compile latency (~20-40s on TPU)
        from volcano_tpu.ops.dispatch import warmup_kernels

        warmup_kernels()  # times and logs itself

    server = ComputePlaneServer(args.socket).start()
    try:
        while True:
            time.sleep(3600)
    except KeyboardInterrupt:
        server.stop()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
