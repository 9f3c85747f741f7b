"""A benchmark tree at a size the CPU can run: the real configurations,
traffic, cells and readers, with three tiny cells beside them.

``make_root(dest)`` writes it: ``dest/BENCHMARK.json`` names the tiny
cells, ``dest/chipbench`` is a copy of the benchmark's files, and
``dest/src`` points at the program.  The tiny models keep every key of
the real files and shrink only their sizes.
"""

from __future__ import annotations

import json
import os
import shutil

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)

TINY = {"hidden_size": 64, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 16, "num_hidden_layers": 2,
        "vocab_size": 256}
#: the values the program runs at the tiny widths
TINY_RUN = {"embedding_multiplier": 8.0, "attention_multiplier": 0.25}
TINY_ARCH = {"num_layers": 2, "d_model": 64, "num_heads": 4,
             "num_kv_heads": 2, "head_dim": 16, "vocab_size": 256}
CELLS = {"tiny-dense.1chip": ("tiny-dense", "tiny-2x16-per-chip", 1),
         "tiny-dense.4chip": ("tiny-dense", "tiny-8x16-dp4", 4),
         "tiny-moe.4chip": ("tiny-moe", "tiny-8x16-dp4", 4)}
#: each tiny mix shrinks the real mix it stands for
TRAFFIC_OF = {"tiny-2x16-per-chip": "zipf-8x512-per-chip",
              "tiny-8x16-dp4": "zipf-32x512-dp4"}
#: each tiny cell checks against the limits of the real cell it stands for
LIMITS_OF = {"tiny-dense.1chip": "granite-3-2b.l4.zero.1chip",
             "tiny-dense.4chip": "granite-3-2b.l4.zero.4chip",
             "tiny-moe.4chip": "granite-moe-1b-a400m.l4.zero.1chip"}


def _load(path):
    with open(path) as f:
        return json.load(f)


def _dump(obj, path):
    with open(path, "w") as f:
        json.dump(obj, f, indent=1)


def make_root(dest: str) -> str:
    here = os.path.join(dest, "chipbench")
    shutil.copytree(BENCH, here, ignore=shutil.ignore_patterns(
        "tests", "__pycache__"))
    os.symlink(os.path.join(REPO, "src"), os.path.join(dest, "src"))
    bench = _load(os.path.join(REPO, "BENCHMARK.json"))

    dense = _load(os.path.join(here, "configs", "granite-3-2b.l4.json"))
    dense.update(TINY, intermediate_size=128)
    dense["arch"].update(TINY_ARCH, d_ff=128)
    moe = _load(os.path.join(here, "configs",
                             "granite-moe-1b-a400m.l4.json"))
    moe.update(TINY, intermediate_size=32, num_local_experts=4,
               num_experts_per_tok=2)
    moe["arch"].update(TINY_ARCH, d_ff=32, num_experts=4, top_k=2)
    for name, cfg in (("tiny-dense", dense), ("tiny-moe", moe)):
        cfg["name"] = name
        for key, value in TINY_RUN.items():
            cfg["departures"][key]["run"] = value
        _dump(cfg, os.path.join(here, "configs", name + ".json"))

    for tiny, real in TRAFFIC_OF.items():
        traffic = _load(os.path.join(here, "traffic", real + ".json"))
        traffic.update(name=tiny, batch_per_chip=2, seq=16, ring=4)
        _dump(traffic, os.path.join(here, "traffic", tiny + ".json"))

    bench["configs"] = [
        dict(bench["configs"][0], name="tiny-dense",
             file="chipbench/configs/tiny-dense.json"),
        dict(bench["configs"][1], name="tiny-moe",
             file="chipbench/configs/tiny-moe.json")]
    bench["workloads"] = [
        {"name": cell, "config": cfg, "traffic": traffic, "chips": chips,
         "why": "a tiny stand-in for the CPU"}
        for cell, (cfg, traffic, chips) in CELLS.items()]
    for metric in bench["per_layer"]:
        if "workloads" in metric:
            metric["workloads"] = ["tiny-dense.4chip", "tiny-moe.4chip"]
    _dump(bench, os.path.join(dest, "BENCHMARK.json"))
    for cell, real in LIMITS_OF.items():
        shutil.copy(os.path.join(here, "cells", real + ".json"),
                    os.path.join(here, "cells", cell + ".json"))
    return dest


if __name__ == "__main__":
    import sys
    make_root(sys.argv[1])
