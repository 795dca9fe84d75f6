"""The four-chip path of the harness on four virtual CPU devices: a sound
tensor-sharded run is correct, and one whose decode step leaves out the
exchange between chips is not."""
import json
import pathlib
import subprocess
import sys

import pytest

MAIN = pathlib.Path(__file__).with_name("_sharded_check_main.py")


@pytest.mark.parametrize("mode,ok", [("sound", True), ("fault", False)])
def test_sharded_exchange_is_checked(mode, ok):
    p = subprocess.run([sys.executable, str(MAIN), mode],
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    got = json.loads(p.stdout.strip().splitlines()[-1])
    assert got["ok"] is ok and got["tokens"] > 50, got
