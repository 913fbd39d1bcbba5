"""The benchmark's own tests: tiny sizes on the CPU (interpret mode)."""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import json  # noqa: E402
import shutil  # noqa: E402

import pytest  # noqa: E402

TINY = {"rows": 1500, "dims": 40, "queries": 300, "clusters": 16,
        "rows_per_sub": 16, "cols": 16, "batch": 8}


def tiny_config(name: str) -> dict:
    """A repo configuration cut to a size the CPU interprets quickly."""
    with open(os.path.join(ROOT, "bench", "configs", name + ".json")) as f:
        cfg = json.load(f)
    cfg.update(rows=TINY["rows"], dims=TINY["dims"], queries=TINY["queries"])
    cfg["data"]["clusters"] = TINY["clusters"]
    cfg["cam"]["circuit"].update(rows=TINY["rows_per_sub"],
                                 cols=TINY["cols"])
    cfg["cam"]["sim"]["serve_batch"] = TINY["batch"]
    return cfg


def make_checkout(root, cells):
    """A checkout at ``root`` holding the repo's ``bench/`` plus, as new
    files only, one tiny configuration and mix per cell in ``cells``
    ({name: (config name, mix dict)}), and a BENCHMARK.json naming them."""
    shutil.copytree(os.path.join(ROOT, "bench"), os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns("tests", ".cache",
                                                  "__pycache__"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"], bench["workloads"] = [], []
    for cell, (base, mix) in cells.items():
        cname, tname = f"tiny-{cell}", f"tiny-{cell}-mix"
        cfg = tiny_config(base)
        path = f"bench/configs/{cname}.json"
        with open(os.path.join(root, path), "w") as f:
            json.dump(cfg, f)
        with open(os.path.join(root, "bench", "traffic", tname + ".json"),
                  "w") as f:
            json.dump(mix, f)
        bench["configs"].append({"name": cname, "source": "test",
                                 "file": path, "reduced": ["rows"],
                                 "why": "test"})
        bench["workloads"].append({"name": cell, "config": cname,
                                   "traffic": tname, "chips": 1,
                                   "why": "test"})
    for m in bench["per_layer"]:
        m["workloads"] = list(cells)
    for m in bench["end_to_end"]:         # the tail: open-loop cells only
        if "workloads" in m:
            m["workloads"] = [c for c, (_, mix) in cells.items()
                              if mix["kind"] == "poisson"]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root


@pytest.fixture(scope="session")
def jax_cache(tmp_path_factory):
    from bench import harness
    return harness.enable_cache(str(tmp_path_factory.mktemp("jaxcache")))
