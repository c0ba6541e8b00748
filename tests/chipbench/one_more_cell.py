"""One more configuration and cell in a root's manifest, as a `model_config`
PR leaves it (ISSUE 42): the Granite configuration and cell files copied
under other names, a `configs` and a `workloads` entry appended, the
cell's name appended to every metric's `workloads` that lists Granite's.
`test_sixth_cell.py` builds its roots with it; run on a scratch checkout,
never on the repo, it gives the root PR 41 met:

    python3 tests/chipbench/one_more_cell.py <checkout>
    python3 -m pytest <checkout>/tests/chipbench
"""
import json
import os
import shutil
import sys

LIKE = "granite-4.0-h-micro-pp4.pretrain-32k"


def copy_of(root, to):
    """The benchmark of `root` under `to`: its manifest and `chipbench/`,
    code and data, which the tests read through their `ROOT`."""
    shutil.copytree(os.path.join(root, "chipbench"),
                    os.path.join(to, "chipbench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(root, "BENCHMARK.json"), to)
    return str(to)


def append_cell(root):
    """(configuration's name, cell's name) appended to the manifest at
    `root`, in place; the names count the configurations, so a root that
    has one such cell takes another."""
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        m = json.load(f)
    cell = dict(next(w for w in m["workloads"] if w["name"] == LIKE))
    config = dict(next(c for c in m["configs"]
                       if c["name"] == cell["config"]))
    name = f"one-more-config-{len(m['configs']) + 1}"
    new = f"{name}.{cell['traffic']}"
    file = f"chipbench/configs/{name}.json"
    shutil.copy(os.path.join(root, config["file"]), os.path.join(root, file))
    shutil.copy(os.path.join(root, "chipbench", "cells", LIKE + ".json"),
                os.path.join(root, "chipbench", "cells", new + ".json"))
    m["configs"].append(dict(config, name=name, file=file))
    m["workloads"].append(dict(cell, name=new, config=name))
    for x in m["end_to_end"] + m["per_layer"]:
        if LIKE in x.get("workloads", []):
            x["workloads"].append(new)
    with open(path, "w") as f:
        json.dump(m, f, indent=1)
    return name, new


if __name__ == "__main__":
    print("appended", *append_cell(sys.argv[1]))
