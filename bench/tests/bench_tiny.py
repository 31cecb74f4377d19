"""A benchmark spec over the tiny test configurations, for CPU tests that
drive the harness end to end without a chip."""
import atexit
import copy
import os
import shutil
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import harness  # noqa: E402

SEED = 2 ** 31 + 11           # larger than 32 signed bits hold
_CACHE = tempfile.mkdtemp(prefix="bench_tiny_cache_")
atexit.register(shutil.rmtree, _CACHE, ignore_errors=True)


def tiny_spec() -> dict:
    spec = copy.deepcopy(harness.load_spec())
    spec["configs"] = [
        {"name": n, "file": f"bench/tests/configs/{n}.json"}
        for n in ("tiny-lm", "tiny-mamba", "tiny-hybrid")]
    spec["workloads"] = [
        {"name": f"{n}.steady", "config": f"tiny-{n}", "traffic": "steady",
         "chips": 1} for n in ("lm", "mamba", "hybrid")]
    rename = {"internlm2-1.8b": "lm", "falcon-mamba-7b.l8": "mamba"}
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [rename[w.rsplit(".", 1)[0]] + "." +
                              w.rsplit(".", 1)[1] for w in m["workloads"]]
            m["workloads"].append("hybrid.steady")
    return spec


def run(workload: str, seed: int = SEED, trace: bool = False) -> dict:
    """One run of a tiny cell. JAX takes its cache directory from the
    environment at its first compile and keeps it, so every run in this
    process names the same directory, which goes when the process ends;
    naming one also keeps the program from choosing its own."""
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", _CACHE)
    return harness.run_cell(workload, seed, 1, trace,
                            require_accelerator=False, spec=tiny_spec())
