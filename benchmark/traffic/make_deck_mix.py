"""Draw a deck whose prompts are a mixture of distributions.

    python3 benchmark/traffic/make_deck_mix.py benchmark/traffic/serve-longmix.json

``make_deck.py`` draws every prompt from one distribution. Here
``drawn_from.prompt`` is ``{"dist": "mixture", "parts": [{"p": ...,
"name": ..., "dist": "lognormal", ...}, ...]}``: each prompt comes from
part ``i`` with probability ``p_i``, and every client's list holds at
least one prompt of every part (a list that does not is drawn again).
The parts and the output lengths are drawn by ``make_deck.draw``. Run
by hand, once; the lists it writes into the file are committed and are
the traffic.
"""

from __future__ import annotations

import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from make_deck import draw  # noqa: E402


def client(rng: np.random.Generator, spec: dict, per: int) -> list:
    parts = spec["prompt"]["parts"]
    p = np.array([part["p"] for part in parts], float)
    while True:
        which = rng.choice(len(parts), size=per, p=p / p.sum())
        if len(set(which.tolist())) == len(parts):
            break
    prompts = [int(draw(rng, parts[w], 1)[0]) for w in which]
    outputs = draw(rng, spec["output"], per)
    return [[p_, int(o)] for p_, o in zip(prompts, outputs)]


def main(path: str) -> None:
    with open(path) as f:
        doc = json.load(f)
    spec = doc["drawn_from"]
    rng = np.random.default_rng(spec["generator_seed"])
    doc["clients"] = [client(rng, spec, spec["requests_per_client"])
                      for _ in range(spec["clients"])]
    head = {k: v for k, v in doc.items() if k != "clients"}
    text = json.dumps(head, indent=2)[:-2] + ',\n  "clients": [\n' + ",\n".join(
        "    " + json.dumps(c) for c in doc["clients"]) + "\n  ]\n}\n"
    with open(path, "w") as f:
        f.write(text)


if __name__ == "__main__":
    main(sys.argv[1])
