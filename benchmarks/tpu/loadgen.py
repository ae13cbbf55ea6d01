"""The general traffic generator: a mix's data file in, requests out.

A mix is a JSON file under ``traffic/``.  It lists the query texts it
sends; each entry names its reference (``refs/<ref>.py``), carries the
SQL and fixes its parameters, which are passed, as strings, to the
reference:

``{"name": "q6", "ref": "q6", "sql": "...", "params": {"date_lo": "1994-01-01", ...}}``

One loop kind, ``"closed"``: one client sends every text once per round,
in an order drawn from the seed, waiting for each answer before the
next.

Imports numpy only.
"""
from __future__ import annotations

import json
from dataclasses import dataclass
from typing import Dict, Iterator, List, Tuple

import numpy as np


@dataclass(frozen=True)
class Text:
    name: str  # the template's name, e.g. "q6"
    ref: str  # module under refs/
    sql: str  # the text as sent
    params: Dict[str, str]  # passed to the reference


@dataclass
class Traffic:
    loop: str  # "closed"
    texts: List[Text]  # every distinct text the window sends

    def rounds(self, rng: np.random.Generator) -> Iterator[List[int]]:
        """Each round, every text once, in a seeded order."""
        while True:
            yield [int(i) for i in rng.permutation(len(self.texts))]


def load(path: str, seed: int) -> Tuple[Traffic, np.random.Generator]:
    """The mix in ``path``; returns the generator to draw the order of
    each round from as well."""
    with open(path) as f:
        spec = json.load(f)
    if spec["loop"] != "closed":
        raise ValueError(f"{path}: unknown loop {spec['loop']!r}")
    texts = [Text(e["name"], e["ref"], e["sql"], {k: str(v) for k, v in e["params"].items()})
             for e in spec["queries"]]
    if len({t.sql for t in texts}) != len(texts):
        raise ValueError(f"{path}: two entries give the same text")
    return Traffic(spec["loop"], texts), np.random.default_rng(seed)
