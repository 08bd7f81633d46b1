"""Shared set-up of the benchmark's tests: the harness on ``sys.path`` and a
throwaway benchmark root at tiny sizes.

``tiny_root`` copies ``BENCHMARK.json`` and the benchmark's files into a
temporary directory and cuts every configuration and mix to sizes a CPU test
run holds (rows, query pool, clients, rate, buckets, build rounds); names,
metrics and checks stay as committed.
"""
import json
import os
import shutil
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "bench")
for p in (BENCH, os.path.join(REPO, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY_ROWS = 2000
TINY_POOL = 64


def make_tiny_root(dst: str) -> str:
    shutil.copytree(BENCH, os.path.join(dst, "bench"),
                    ignore=shutil.ignore_patterns("tests", "testdata",
                                                  "__pycache__"))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        doc = json.load(f)
    for c in doc["configs"]:
        path = os.path.join(dst, c["file"])
        with open(path) as f:
            cfg = json.load(f)
        cfg["corpus"]["rows"] = TINY_ROWS
        if "max_rounds" in cfg["index"]:
            cfg["index"]["max_rounds"] = 3
        cfg["serve"]["buckets"] = [1, 8]
        with open(path, "w") as f:
            json.dump(cfg, f)
    for w in doc["workloads"]:
        path = os.path.join(dst, "bench", "traffic", w["traffic"] + ".json")
        with open(path) as f:
            mix = json.load(f)
        mix["query_pool"] = TINY_POOL
        if mix["loop"] == "closed":
            mix["clients"] = 8
        else:
            mix["rate_qps"] = 400
        with open(path, "w") as f:
            json.dump(mix, f)
    with open(os.path.join(dst, "BENCHMARK.json"), "w") as f:
        json.dump(doc, f)
    return dst


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    return make_tiny_root(str(tmp_path_factory.mktemp("bench_root")))


@pytest.fixture
def jax_config_restored():
    """A run turns on JAX's persistent compile cache settings; put them
    back so later tests in this worker see the defaults."""
    import jax

    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    saved = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in saved.items():
        jax.config.update(k, v)
