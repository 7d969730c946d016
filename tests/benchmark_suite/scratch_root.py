"""A scratch root that stands for a later PR's tree: the benchmark's data
directories copied, so that a test adds files and entries to them and then
holds every file that was there to its digest."""
import hashlib
import os
import shutil

from benchmark import manifest

DATA_DIRS = ("configs", "traffic", "metrics", "readers", "families")


def digest(top) -> dict:
    out = {}
    for d, _dirs, files in os.walk(top):
        for f in files:
            path = os.path.join(d, f)
            with open(path, "rb") as fh:
                out[os.path.relpath(path, top)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


def make(root):
    """Copy the data directories under ``root``/benchmark; returns that
    directory and the digests of what it holds."""
    bench = root / "benchmark"
    for sub in DATA_DIRS:
        shutil.copytree(os.path.join(manifest.ROOT, "benchmark", sub),
                        bench / sub,
                        ignore=shutil.ignore_patterns("__pycache__"))
    return bench, digest(bench)
