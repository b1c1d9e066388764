"""Import health: every module under src/repro must import cleanly.

One bad import used to poison collection of all 11 tier-1 test modules
(jax API drift in grblas/dist.py plus a missing repro.dist
package); this walk makes any regression show up as exactly one
parametrized failure naming the broken module.
"""
import importlib
import pkgutil

import os
import subprocess
import sys
from pathlib import Path

import pytest

import repro

SRC = Path(__file__).resolve().parent.parent / "src"

ALL_MODULES = sorted(
    m.name for m in pkgutil.walk_packages(repro.__path__, prefix="repro."))


def test_walk_found_the_tree():
    assert len(ALL_MODULES) > 50, ALL_MODULES
    for expected in ("repro.dist.sharding", "repro.dist.compression",
                     "repro.grblas.dist", "repro.models.layers",
                     "repro.launch.dryrun"):
        assert expected in ALL_MODULES


@pytest.mark.parametrize("name", ALL_MODULES)
def test_import(name):
    importlib.import_module(name)


def test_dryrun_import_leaves_environment_alone():
    """Importing the dry-run module must not touch XLA_FLAGS: a process
    that imports it and then starts jax keeps its own device set."""
    code = ("import os; import repro.launch.dryrun; "
            "print(repr(os.environ.get('XLA_FLAGS')))")
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["PYTHONPATH"] = str(SRC)
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    assert r.stdout.strip().splitlines()[-1] == "None"
