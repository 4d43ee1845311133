"""Worker bootstrap for the launch controller.

Two jobs before the user script becomes __main__:
- CPU pinning (when PADDLE_LAUNCH_CPU_DEVICES is set): the pin goes
  through the jax config API inside the worker process, before anything
  initializes a backend (see device.pin_cpu).
- Liveness heartbeat (when PADDLE_HEARTBEAT_FILE is set): start the beat
  thread the controller's hang watchdog relies on (reference
  fleet/elastic/manager.py keepalive).
"""
import os
import runpy
import sys

if os.environ.get("PADDLE_LAUNCH_CPU_DEVICES"):
    from paddle_tpu.device import pin_cpu
    n = int(os.environ["PADDLE_LAUNCH_CPU_DEVICES"])
    # verify=False: verification would initialize the backend, which must
    # not happen before the worker's jax.distributed.initialize
    if not pin_cpu(n, verify=False):
        print("[launch] could not pin the CPU platform", file=sys.stderr)
        sys.exit(17)

from paddle_tpu.distributed.launch import heartbeat  # noqa: E402

heartbeat.start_from_env()

sys.argv = sys.argv[1:]
runpy.run_path(sys.argv[0], run_name="__main__")
