"""Draw a traffic file's deck from the distributions it names.

    python3 benchmark/traffic/make_deck.py benchmark/traffic/serve-code.json

Run by hand, once; the lists it writes into the file are committed and
are the traffic. ``drawn_from`` in the file gives, for the prompt and
for the output length, ``{"dist": "lognormal", "median", "sigma",
"clip": [lo, hi]}`` or ``{"dist": "uniform", "clip": [lo, hi]}``, the
number of clients, the requests in each client's list, and the
generator's seed. Every run of the cell then deals the same lengths in
the same order, whatever its ``--seed``.
"""

from __future__ import annotations

import json
import sys

import numpy as np


def draw(rng: np.random.Generator, spec: dict, n: int) -> np.ndarray:
    lo, hi = spec["clip"]
    if spec["dist"] == "lognormal":
        x = rng.lognormal(np.log(spec["median"]), spec["sigma"], n)
    elif spec["dist"] == "uniform":
        x = rng.uniform(lo, hi + 1, n)
    else:
        raise ValueError(f"unknown distribution {spec['dist']!r}")
    return np.clip(np.floor(x), lo, hi).astype(int)


def make(spec: dict) -> list:
    rng = np.random.default_rng(spec["generator_seed"])
    n, per = spec["clients"], spec["requests_per_client"]
    prompts = draw(rng, spec["prompt"], n * per).reshape(n, per)
    outputs = draw(rng, spec["output"], n * per).reshape(n, per)
    return [[[int(p), int(o)] for p, o in zip(prompts[c], outputs[c])]
            for c in range(n)]


def main(path: str) -> None:
    with open(path) as f:
        doc = json.load(f)
    doc["clients"] = make(doc["drawn_from"])
    head = {k: v for k, v in doc.items() if k != "clients"}
    text = json.dumps(head, indent=2)[:-2] + ',\n  "clients": [\n' + ",\n".join(
        "    " + json.dumps(c) for c in doc["clients"]) + "\n  ]\n}\n"
    with open(path, "w") as f:
        f.write(text)


if __name__ == "__main__":
    main(sys.argv[1])
