"""``deepspeed_tpu`` CLI — multi-host job runner (reference:
deepspeed/launcher/runner.py:419 main, :213 hostfile parsing, :293
resource filters).

The reference launches one process per GPU per node over ssh/pdsh/mpirun.
On TPU the unit is the *host*: each host of a pod slice runs ONE process
that owns that host's chips, and `jax.distributed.initialize` does the
rendezvous against a coordinator. So the runner's job is:

  1. parse hostfile / --include / --exclude filters (same syntax as the
     reference: ``worker-0 slots=4``, ``--include worker-0@worker-1:0,2``)
  2. pick a multinode backend (pdsh/ssh/openmpi/slurm/...)
  3. start the user script on every host with coordinator env exported

Single-host jobs skip ssh entirely and exec the script in-process
(reference: runner.py launches launch.py locally).
"""

from __future__ import annotations

import argparse
import os
import re
import shlex
import subprocess
import sys
from collections import OrderedDict

from ..utils.logging import logger
from . import constants
from .multinode_runner import (IMPIRunner, MPICHRunner, MVAPICHRunner,
                               OpenMPIRunner, PDSHRunner, SlurmRunner,
                               SSHRunner)

DLTS_HOSTFILE = "/job/hostfile"
EXPORT_ENVS = ["PYTHONPATH", "PATH", "LD_LIBRARY_PATH", "TPU_", "JAX_",
               "XLA_", "LIBTPU_", "DS_"]


def parse_args(args=None):
    parser = argparse.ArgumentParser(
        prog="deepspeed_tpu",
        description="deepspeed_tpu multi-host launcher "
                    "(reference CLI: deepspeed/launcher/runner.py)")
    parser.add_argument("-H", "--hostfile", type=str, default=DLTS_HOSTFILE,
                        help="Hostfile: lines of '<host> slots=<n>'")
    parser.add_argument("-i", "--include", type=str, default="",
                        help="Host filter, e.g. 'worker-0@worker-1:0,2'")
    parser.add_argument("-e", "--exclude", type=str, default="",
                        help="Host exclusion filter")
    parser.add_argument("--num_nodes", type=int, default=-1)
    parser.add_argument("--num_gpus", "--num_chips", type=int, default=-1,
                        dest="num_gpus", help="chips per host to use")
    parser.add_argument("--master_port", type=int, default=29500)
    parser.add_argument("--master_addr", type=str, default="")
    parser.add_argument("--launcher", type=str,
                        default=constants.PDSH_LAUNCHER,
                        choices=[constants.PDSH_LAUNCHER,
                                 constants.SSH_LAUNCHER,
                                 constants.OPENMPI_LAUNCHER,
                                 constants.MPICH_LAUNCHER,
                                 constants.IMPI_LAUNCHER,
                                 constants.SLURM_LAUNCHER,
                                 constants.MVAPICH_LAUNCHER])
    parser.add_argument("--launcher_args", type=str, default="")
    parser.add_argument("--force_multi", action="store_true")
    parser.add_argument("--autotuning", type=str, default="",
                        choices=["", "tune", "run"])
    parser.add_argument("--elastic_training", action="store_true")
    parser.add_argument("--save_pid", action="store_true")
    parser.add_argument("--enable_each_rank_log", type=str, default=None)
    parser.add_argument("--venv_script", type=str, default=None)
    parser.add_argument("user_script", type=str,
                        help="user training script")
    parser.add_argument("user_args", nargs=argparse.REMAINDER)
    return parser.parse_args(args=args)


def fetch_hostfile(hostfile_path: str):
    """Parse '<hostname> slots=<n>' lines (reference: runner.py:213).
    Returns OrderedDict host -> slot count, or None when absent."""
    if not os.path.isfile(hostfile_path):
        return None
    resource_pool = OrderedDict()
    with open(hostfile_path) as f:
        for line in f:
            line = line.strip()
            if not line or line.startswith("#"):
                continue
            m = re.match(r"^(\S+)\s+slots=(\d+)\s*$", line)
            if m is None:
                raise ValueError(
                    f"Hostfile line not of form '<host> slots=<n>': {line!r}")
            host, slots = m.group(1), int(m.group(2))
            if host in resource_pool:
                raise ValueError(f"Duplicate host {host} in hostfile")
            resource_pool[host] = slots
    if not resource_pool:
        raise ValueError(f"Hostfile {hostfile_path} is empty")
    return resource_pool


def _parse_filter_spec(spec: str):
    """'h0@h1:0,2' -> {h0: None, h1: [0, 2]} (None = all slots)."""
    mapping = OrderedDict()
    if not spec:
        return mapping
    for part in spec.split("@"):
        if ":" in part:
            host, slots = part.split(":")
            mapping[host] = sorted(int(s) for s in slots.split(","))
        else:
            mapping[part] = None
    return mapping


def parse_resource_filter(host_info, include_str="", exclude_str=""):
    """Apply --include/--exclude (reference: runner.py:293). Only one of
    the two may be given. Returns OrderedDict host -> list of chip
    indices; the indices reach each host as TPU_VISIBLE_CHIPS (the
    reference's per-rank CUDA_VISIBLE_DEVICES), so excluding a single bad
    chip really removes it."""
    if include_str and exclude_str:
        raise ValueError("--include and --exclude are mutually exclusive")

    if include_str:
        included = _parse_filter_spec(include_str)
        pool = OrderedDict()
        for host, slots in included.items():
            if host not in host_info:
                raise ValueError(f"included host {host} not in hostfile")
            n = host_info[host]
            if slots is None:
                pool[host] = list(range(n))
            else:
                bad = [s for s in slots if s >= n]
                if bad:
                    raise ValueError(f"host {host} has {n} slots; "
                                     f"cannot include {bad}")
                pool[host] = slots
        return pool

    excluded = _parse_filter_spec(exclude_str)
    for host, slots in excluded.items():
        if host not in host_info:
            raise ValueError(f"excluded host {host} not in hostfile")
        if slots is not None:
            bad = [s for s in slots if s >= host_info[host]]
            if bad:
                raise ValueError(f"host {host} has {host_info[host]} "
                                 f"slots; cannot exclude {bad}")
    pool = OrderedDict()
    for host, n in host_info.items():
        if host in excluded:
            slots = excluded[host]
            if slots is None:
                continue  # whole host excluded
            keep = [s for s in range(n) if s not in slots]
            if keep:
                pool[host] = keep
        else:
            pool[host] = list(range(n))
    if not pool:
        raise ValueError("resource filter excluded every host")
    return pool


def _local_run(args) -> int:
    """Single-host path: exec the user script directly; one process owns
    all local chips (no per-chip fork — that is the TPU model)."""
    env = os.environ.copy()
    env[constants.COORDINATOR_ADDR_ENV] = \
        f"{args.master_addr or 'localhost'}:{args.master_port}"
    env[constants.PROCESS_ID_ENV] = "0"
    env[constants.NUM_PROCESSES_ENV] = "1"
    if args.num_gpus > 0:
        # libtpu honors TPU_VISIBLE_CHIPS; restrict the process to the
        # first N local chips (reference: per-GPU CUDA_VISIBLE_DEVICES)
        env["TPU_VISIBLE_CHIPS"] = ",".join(
            str(i) for i in range(args.num_gpus))
    cmd = [sys.executable, args.user_script] + list(args.user_args)
    logger.info(f"launch (single host): {' '.join(map(shlex.quote, cmd))}")
    return subprocess.call(cmd, env=env)


def _local_chip_count() -> int:
    """Local device count, asked of a short-lived child. A chip belongs
    to one process at a time: if the launcher initialised a JAX backend
    itself it would hold the chips, and the workers it spawns next would
    fail or hang waiting for them. The child exits (releasing the
    chips) before any worker starts."""
    out = subprocess.run(
        [sys.executable, "-c",
         "import jax; print(jax.local_device_count())"],
        check=True, capture_output=True, text=True)
    return max(1, int(out.stdout.split()[-1]))


RUNNERS = {
    constants.PDSH_LAUNCHER: PDSHRunner,
    constants.SSH_LAUNCHER: SSHRunner,
    constants.OPENMPI_LAUNCHER: OpenMPIRunner,
    constants.MPICH_LAUNCHER: MPICHRunner,
    constants.IMPI_LAUNCHER: IMPIRunner,
    constants.SLURM_LAUNCHER: SlurmRunner,
    constants.MVAPICH_LAUNCHER: MVAPICHRunner,
}


def main(args=None) -> int:
    args = parse_args(args)
    resource_pool = fetch_hostfile(args.hostfile)

    if resource_pool is None and not args.force_multi:
        return _local_run(args)
    if resource_pool is None:
        # no hostfile + --force_multi: localhost with ALL its chips (a
        # slots=1 default would shrink TPU_VISIBLE_CHIPS to one chip)
        resource_pool = OrderedDict(localhost=_local_chip_count())

    resource_pool = OrderedDict(resource_pool)
    active = parse_resource_filter(resource_pool, args.include, args.exclude)
    if args.num_nodes > 0:
        active = OrderedDict(list(active.items())[:args.num_nodes])

    if not args.master_addr:
        args.master_addr = next(iter(active))

    runner_cls = RUNNERS[args.launcher]
    runner = runner_cls(args, active)
    if not runner.backend_exists():
        raise RuntimeError(
            f"launcher backend {args.launcher!r} not available on PATH")

    env = {}
    for key, val in os.environ.items():
        if any(key.startswith(p) or key == p for p in EXPORT_ENVS):
            env[key] = val
    env[constants.COORDINATOR_ADDR_ENV] = \
        f"{args.master_addr}:{args.master_port}"
    if args.num_gpus > 0:
        # cap every host's chip list at the first N requested
        active = OrderedDict(
            (h, slots[:args.num_gpus]) for h, slots in active.items())

    cmd = runner.get_cmd(env, active)
    logger.info(f"launch ({args.launcher}): "
                f"{' '.join(map(shlex.quote, cmd))}")
    result = subprocess.Popen(cmd, env={**os.environ, **env})
    result.wait()
    return result.returncode


if __name__ == "__main__":
    sys.exit(main())
