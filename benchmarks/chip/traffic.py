"""The one traffic generator: a mix is a JSON file of parameters under
``traffic/<mix>.json``; this module turns it and a seed into requests.

Keys of a mix:

* ``context_tokens`` — one entry a session: the context it holds when
  the window opens.  Set-up builds it, as the session's earlier turns
  would have built it.
* ``new_tokens`` — what each session's current turn may generate
  (greedy).

Every seed serves the same sizes.  The seed sets which session gets
which length and draws every token id uniformly from the vocabulary, so
no two contexts share a prefix.
"""

from __future__ import annotations

import dataclasses
import json
from pathlib import Path
from typing import List

import numpy as np

HERE = Path(__file__).resolve().parent


@dataclasses.dataclass
class Spec:
    """One session's request: its context so far and its turn's limit."""

    prompt: np.ndarray
    max_new_tokens: int


def load_mix(name: str, root: Path = HERE) -> dict:
    return json.loads((root / "traffic" / f"{name}.json").read_text())


def rng_for(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) & (2 ** 64 - 1), stream])


def generate(mix: dict, seed: int, vocab: int) -> List[Spec]:
    """The sessions of one run, in the order they are admitted."""
    lengths = rng_for(seed, 0).permutation(
        np.asarray(mix["context_tokens"], np.int64))
    tokens = rng_for(seed, 1)
    return [Spec(prompt=tokens.integers(0, vocab, size=int(n),
                                        dtype=np.int32),
                 max_new_tokens=int(mix["new_tokens"]))
            for n in lengths]


def pool_blocks(mix: dict, block_size: int) -> int:
    """Blocks that hold every session to the end of its turn, plus the
    engine's trash block: a pool this size never preempts, and the
    window fills it as far as the sessions have come."""
    return 1 + sum(-(-(int(n) + int(mix["new_tokens"])) // block_size)
                   for n in mix["context_tokens"])
