"""Multi-device half of tests/test_paged_pool_inplace.py.

Runs every case under a ``(1, 2)`` mesh with four virtual CPU devices
(device count is fixed when jax starts, so the single-device test session
cannot).  Prints one ``<kind> <case>@1x2: <verdict>`` line per case, the
verdict ``ok`` or what went wrong; the parent test asserts on each line.
"""
import os
import pathlib
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = \
        (flags + " --xla_force_host_platform_device_count=4").strip()
sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

import jax  # noqa: E402

from test_paged_pool_inplace import (HLO_CASES, MESH, WRITE_CASES,  # noqa: E402
                                     hlo_case, write_case)


def _verdict(fn):
    try:
        return fn()
    except Exception as e:  # noqa: BLE001 - reported to the parent test
        return f"raised {type(e).__name__}: {e}"


def _hlo(case):
    bad, seen = hlo_case(case, shards=2)
    if bad:
        return "pool-sized copies: " + "; ".join(bad)
    return "ok" if seen else "no op of the stacked pool's shape: wrong shape?"


if __name__ == "__main__":
    assert jax.device_count() >= 2, jax.device_count()
    for case in HLO_CASES:
        print(f"hlo {case}{MESH}: {_verdict(lambda: _hlo(case))}",
              flush=True)
    for case in WRITE_CASES:
        print(f"writes {case}{MESH}: "
              f"{_verdict(lambda: write_case(case, shards=2) or 'ok')}",
              flush=True)
