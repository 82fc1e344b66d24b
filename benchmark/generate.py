"""The one traffic generator: token batches for training cells.

Reads a traffic file's parameters (`traffic/<name>.json`) and makes, from
the seed alone, the ring of batches a run drives.  Every seed gives the same
shapes; only the tokens differ.

`source: toy_language` is a copy of the program's
`data/synthetic.learnable_tokens` (the yardstick may not live in the
program): words drawn Zipf-wise from a fixed lexicon of 64 lowercase words,
27 of 256 byte values in use, so a byte-level model's loss falls and a wrong
update shows in it.
"""
from __future__ import annotations

import numpy as np


def toy_language(rng: np.random.Generator, n_tokens: int) -> np.ndarray:
    lex_rng = np.random.default_rng(0)  # the lexicon never changes
    letters = np.arange(ord("a"), ord("z") + 1)
    letter_p = 1.0 / np.arange(1, len(letters) + 1)
    letter_p /= letter_p.sum()
    words = [bytes(lex_rng.choice(letters, size=int(lex_rng.integers(2, 9)),
                                  p=letter_p).tolist()) + b" "
             for _ in range(64)]
    word_p = 1.0 / np.arange(1, len(words) + 1)
    word_p /= word_p.sum()
    out = bytearray()
    while len(out) < n_tokens:
        for i in rng.choice(len(words), size=256, p=word_p):
            out += words[i]
    return np.frombuffer(bytes(out[:n_tokens]), np.uint8)


SOURCES = {"toy_language": toy_language}


def token_batches(traffic: dict, seed: int, batch: int, sequence: int,
                  vocab: int):
    """`ring_batches` pairs (x, y) of int32 [batch, sequence]; y is x moved
    one token on.  Rows are consecutive cuts of one stream, so all differ."""
    draw = SOURCES[traffic["source"]]
    n = int(traffic["ring_batches"])
    rng = np.random.default_rng([int(seed), 1])
    stream = draw(rng, n * batch * (sequence + 1)).astype(np.int32)
    if stream.max() >= vocab:
        raise ValueError("traffic draws tokens outside the vocabulary")
    rows = stream.reshape(n, batch, sequence + 1)
    return [(r[:, :-1], r[:, 1:]) for r in rows]
