"""Alexandria-shaped columns from a seed (``alexandria.json``).

The ``id`` column is not made here: the store assigns ``0 .. rows-1`` in
file order at ``create``, and the reference takes it as that range.
"""
from typing import Dict

import numpy as np


def _normal(c: dict, rows: int, rng: np.random.Generator) -> np.ndarray:
    return np.round(rng.normal(c["mean"], c["std"], rows),
                    c["decimals"]).astype(np.float32)


def generate(cfg: dict, rows: int, seed: int) -> Dict[str, np.ndarray]:
    c = cfg["columns"]
    rng = np.random.default_rng(seed)
    groups = np.array(c["spg"]["values"], np.int64)
    w = 1.0 / np.arange(1, len(groups) + 1)
    return {
        "spg": groups[rng.choice(len(groups), rows, p=w / w.sum())],
        "n_sites": rng.integers(c["n_sites"]["low"],
                                c["n_sites"]["high"] + 1, rows),
        "energy": _normal(c["energy"], rows, rng),
        "e_form": _normal(c["e_form"], rows, rng),
    }
