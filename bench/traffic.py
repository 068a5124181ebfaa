"""One generator for every traffic mix.

A mix is a JSON file under ``traffic/``.  Its keys:

``loop``        ``"closed"``: ``clients`` clients, each sending its next
                query when the previous one returns.
``clients``     the number of clients.
``family``      the query family: the name of a file under ``families/``
                that makes the family's queries and answers them plainly.
``k``           the size of each query's top-k.

Every other key belongs to the family, whose file documents it.

A family file ``families/<family>.py`` defines

``Queries(spec, n, labels, seed)``  an object whose ``query(i)`` gives
        the request fields of the window's ``i``-th query and whose
        ``warmup()`` gives queries that use every program the window's
        queries use;
``reference(graph, query)``  the plain answer, ``(keys, results)``, of a
        query on a :class:`bench.reference.Graph`.

Everything is drawn from the run's seed, query by query (:func:`stream`),
so the same seed gives the same queries in the same order, and warm-up
queries come from a stream of their own.
"""
from __future__ import annotations

import importlib.util
import json
import os

import numpy as np

FAMILIES_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "families")

# independent random streams under one seed
WINDOW, WARMUP, POOL = 10, 11, 12


def stream(seed: int, *key: int) -> np.random.Generator:
    return np.random.default_rng([seed, *key])


def family(name: str):
    """The module ``families/<name>.py``."""
    path = os.path.join(FAMILIES_DIR, name + ".py")
    if not os.path.exists(path):
        raise ValueError(f"unknown query family {name!r}: no {path}")
    spec = importlib.util.spec_from_file_location(
        "bench_family_" + name.replace(".", "_").replace("-", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load(path: str) -> dict:
    with open(path) as f:
        spec = json.load(f)
    if spec["loop"] != "closed":
        raise ValueError(f"{path}: loop must be closed")
    if int(spec["clients"]) < 1 or int(spec["k"]) < 1:
        raise ValueError(f"{path}: clients and k must be at least 1")
    family(spec["family"])
    return spec


class Traffic:
    """The queries of one mix on one graph for one seed."""

    def __init__(self, spec: dict, n: int, labels, seed: int):
        self.spec = spec
        self.batch_size = int(spec["clients"])
        self._family = family(spec["family"])
        self._queries = self._family.Queries(spec, n, labels, seed)

    def query(self, i: int) -> dict:
        """The request fields of the window's ``i``-th query."""
        return self._queries.query(i)

    def warmup(self) -> list:
        return self._queries.warmup()

    def reference(self, graph, query: dict):
        return self._family.reference(graph, query)
