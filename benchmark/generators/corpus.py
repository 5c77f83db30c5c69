"""Seeded documents: the text of document ``i`` is a pure function of the
seed, so the load process, the system and ``correct`` all regenerate it.
Document length and vocabulary are those of ``chip_smoke.Corpus``."""

from __future__ import annotations

import os
import random

VOCAB = [f"w{i:05d}" for i in range(20000)]


def doc_text(seed: int, i: int, words: int) -> str:
    rng = random.Random(f"{seed}:{i}")
    n = words + rng.randrange(-8, 9)
    return f"doc{i:06d} " + " ".join(rng.choice(VOCAB) for _ in range(n))


def doc_name(i: int) -> str:
    return f"doc_{i:06d}.txt"


def doc_id(path: str) -> int:
    return int(os.path.basename(path)[4:10])


def excerpt(seed: int, i: int, words: int, n: int) -> str:
    ws = doc_text(seed, i, words).split()[1:]
    s = random.Random(f"{seed}:q:{i}").randrange(0, max(len(ws) - n, 1))
    return " ".join(ws[s: s + n])


def write_docs(directory: str, stage: str, seed: int, ids, words: int) -> None:
    """Each file is written under ``stage`` and renamed into ``directory``,
    so the connector never lists one half-written."""
    for i in ids:
        tmp = os.path.join(stage, doc_name(i))
        with open(tmp, "w") as f:
            f.write(doc_text(seed, i, words))
        os.replace(tmp, os.path.join(directory, doc_name(i)))
