"""A benchmark spec over the tiny test configurations, for CPU tests that
drive the harness end to end without a chip: one cell ``<name>.steady`` for
each file ``bench/tests/configs/<name>.json``, so that a configuration's
tiny file is a cell of the CPU tests with no change here."""
import atexit
import copy
import os
import shutil
import sys
import tempfile
from pathlib import Path
from typing import List, Optional

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import harness  # noqa: E402

SEED = 2 ** 31 + 11           # larger than 32 signed bits hold
CONFIGS = Path(__file__).resolve().parent / "configs"
_CACHE = tempfile.mkdtemp(prefix="bench_tiny_cache_")
atexit.register(shutil.rmtree, _CACHE, ignore_errors=True)


def config_names(configs: Path = CONFIGS) -> List[str]:
    """The tiny configurations: the files in ``configs``, by name."""
    return sorted(p.stem for p in configs.glob("*.json"))


def tiny_spec(spec: Optional[dict] = None, configs: Path = CONFIGS) -> dict:
    """``spec`` (BENCHMARK.json by default) with its configurations and cells
    replaced by the tiny ones, and every metric that lists its cells listing
    all of them."""
    spec = copy.deepcopy(spec or harness.load_spec())
    names = config_names(configs)
    spec["configs"] = [{"name": n, "file": str(configs / f"{n}.json")}
                       for n in names]
    spec["workloads"] = [{"name": f"{n}.steady", "config": n,
                          "traffic": "steady", "chips": 1} for n in names]
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [w["name"] for w in spec["workloads"]]
    return spec


def run(workload: str, seed: int = SEED, trace: bool = False,
        spec: Optional[dict] = None) -> dict:
    """One run of a tiny cell of ``spec`` (``tiny_spec()`` by default). JAX
    takes its cache directory from the environment at its first compile and
    keeps it, so every run in this process names the same directory, which
    goes when the process ends; naming one also keeps the program from
    choosing its own."""
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", _CACHE)
    return harness.run_cell(workload, seed, 1, trace,
                            require_accelerator=False,
                            spec=spec or tiny_spec())[0]
