"""Test harness configuration.

Forces an 8-device CPU mesh so every distributed test runs multi-device
without hardware — the capability the reference never had (its distributed
tests require >=2 physical GPUs, reference:
tests/distributed/DDP/run_race_test.sh). The suite is a CPU suite
(``JAX_PLATFORMS=cpu``); the chip is driven by ``chip_smoke.py``, one
process per chip.
"""

import jax

from apex_tpu.parallel import pin_cpu_devices

pin_cpu_devices(8)


def pytest_report_header(config):
    return (f"apex_tpu backend: {jax.default_backend()} "
            f"({len(jax.devices())} devices)")
