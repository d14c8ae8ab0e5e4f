"""Multi-host launch helpers (reference: apex/parallel/multiproc.py).

The reference spawns one process per GPU with ``--rank i`` args
(multiproc.py:12-35) because NCCL is process-per-device. The JAX runtime is
process-per-HOST: on one host the supported shape is ONE process driving
all local chips through a mesh (``make_mesh`` + ``Plan``). A chip belongs
to one process at a time, so N local children that each initialize the
TPU would each try to claim every chip, and all but one fail or hang.
Multi-host jobs call ``jax.distributed.initialize`` once per host — so the
launcher's job here is (a) a thin initialize wrapper, and (b) a local
CPU-simulation spawner for testing multi-process code paths without
hardware (something the reference never had; its distributed tests
require real GPUs, SURVEY §4). (b) never starts a child on the chip.
"""

from __future__ import annotations

import os
import subprocess
import sys
from typing import Optional


def initialize(coordinator_address: Optional[str] = None,
               num_processes: Optional[int] = None,
               process_id: Optional[int] = None,
               local_device_ids=None) -> None:
    """Initialize the multi-host JAX runtime (DCN-connected hosts).

    All arguments default to cluster-environment autodetection (TPU pods
    populate them from the metadata server). Single-host callers can skip
    this entirely — the reference requires a launcher even on one node;
    here one process already owns all local chips.
    """
    import jax

    kwargs = {}
    if coordinator_address is not None:
        kwargs["coordinator_address"] = coordinator_address
    if num_processes is not None:
        kwargs["num_processes"] = num_processes
    if process_id is not None:
        kwargs["process_id"] = process_id
    if local_device_ids is not None:
        kwargs["local_device_ids"] = local_device_ids
    jax.distributed.initialize(**kwargs)


def multiproc(script: str, world_size: int, *script_args: str,
              log_dir: str = ".") -> int:
    """Spawn ``world_size`` local CPU processes running ``script`` — the
    reference launcher's shape (multiproc.py:12-35: one process per device,
    non-rank-0 stdout to files), retargeted at CPU-simulated multi-process
    testing. Each child gets WORLD_SIZE/RANK env vars and is PINNED to the
    CPU platform (``JAX_PLATFORMS=cpu``, whatever the caller's
    environment says): N children sharing this host's device visibility
    would each claim the same local chips. Returns the first non-zero
    child exit status (signal deaths included via their negative
    returncode), 0 if all succeeded."""
    procs, logs = [], []
    try:
        for rank in range(world_size):
            env = dict(os.environ,
                       WORLD_SIZE=str(world_size), RANK=str(rank),
                       JAX_PLATFORMS="cpu")
            argv = [sys.executable, script, *script_args]
            if rank == 0:
                p = subprocess.Popen(argv, env=env)
            else:
                out = open(os.path.join(log_dir, f"rank{rank}.log"), "w")
                logs.append(out)
                p = subprocess.Popen(argv, env=env, stdout=out,
                                     stderr=out)
            procs.append(p)
        codes = [p.wait() for p in procs]
    finally:
        for out in logs:
            out.close()
    return next((rc for rc in codes if rc != 0), 0)


def _main(argv=None):
    """CLI: ``python -m apex_tpu.parallel.launch <world_size> script.py
    [args...]`` (the reference's ``python -m apex.parallel.multiproc``
    surface, multiproc.py:12-35)."""
    import argparse
    p = argparse.ArgumentParser(prog="apex_tpu.parallel.launch")
    p.add_argument("world_size", type=int)
    p.add_argument("script")
    p.add_argument("script_args", nargs="*")
    p.add_argument("--log-dir", default=".")
    a = p.parse_args(argv)
    return multiproc(a.script, a.world_size, *a.script_args,
                     log_dir=a.log_dir)


if __name__ == "__main__":  # pragma: no cover - thin CLI
    raise SystemExit(_main())
