"""Helpers for tests that run the harness in a child process."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


def child_env(cache_dir) -> dict:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_ENABLE_X64"] = "0"
    env["JAX_COMPILATION_CACHE_DIR"] = str(cache_dir)
    return env


def checkout(tmp_path, with_src: bool = True) -> Path:
    """A copy of ``BENCHMARK.json`` and ``bench/``, with the program
    linked beside it when ``with_src``."""
    root = tmp_path / "checkout"
    root.mkdir()
    shutil.copy(ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    if with_src:
        (root / "src").symlink_to(ROOT / "src")
    return root


def add_entries(root: Path, **entries) -> None:
    """Append entries to the copy's ``BENCHMARK.json`` lists."""
    spec = json.loads((root / "BENCHMARK.json").read_text())
    for key, items in entries.items():
        spec[key].extend(items)
    (root / "BENCHMARK.json").write_text(json.dumps(spec))


def run(args, cache_dir, script=None, timeout=600):
    script = script or BENCH / "run.py"
    p = subprocess.run([sys.executable, str(script), *args],
                       capture_output=True, text=True, timeout=timeout,
                       env=child_env(cache_dir))
    lines = p.stdout.strip().splitlines()
    try:
        result = json.loads(lines[-1]) if lines else None
    except ValueError:
        result = None
    if result is not None and "correct" not in result:
        result = None
    return p.returncode, result, p.stderr


def over_limit(result) -> list:
    """The names of the checks that read over their limit."""
    return sorted(name for name, c in result["checks"].items()
                  if not (c["value"] == c["value"]
                          and c["value"] <= c["limit"]))
