#!/usr/bin/env python3
"""Draw a traffic mix's pool from its distributions and store it.

    python bench/tools/make_pool.py bench/traffic/<mix>.json

The pool is drawn once with the mix's own ``pool_seed``
(``bench.harness.traffic.draw_pool``) and written into the file, where
the generator reads it; ``--seed`` never changes it.
"""
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from bench.harness.traffic import draw_pool  # noqa: E402


def main(path: str) -> None:
    with open(path) as f:
        mix = json.load(f)
    mix["pool"] = draw_pool(mix)
    with open(path, "w") as f:
        json.dump(mix, f, indent=1)
        f.write("\n")


if __name__ == "__main__":
    for p in sys.argv[1:]:
        main(p)
