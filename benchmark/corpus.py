"""The wordcount cells' input and its reference answer.

A copy of ``bench.make_corpus`` (Europarl-shaped text: Zipf-ranked words
over an 80,000-word vocabulary, ~12% of it carrying attached
punctuation, a tail of >128-byte words), kept here so that a later PR to
``bench.py`` cannot move the yardstick.  Two things differ from the
original, both for the benchmark's sake:

* the VOCABULARY is drawn from the configuration's fixed ``vocab_seed``
  and only the ORDER of the running words from ``--seed``.  The most
  frequent hundred words carry a quarter of the text, so a vocabulary
  redrawn per seed would move the bytes per word — and with them the
  corpus size and the number of waves — from run to run.  Every seed
  gives the same words at the same rates in another order.
* the draw is an alias-method lookup and the assembly a row gather and
  one compress per block, so that 49 M words take seconds, and the
  reference answer falls out of the drawn ids (a ``bincount``) instead
  of a second pass over 300 MB of text.

The reference shares nothing with the engine: it never tokenizes.
``tests/test_benchmark.py`` pins it against
``collections.Counter(text.split())``.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor
from typing import Dict, Tuple

import numpy as np

MAXW = 16                       # vocabulary cell width, bytes
_LETTERS = np.frombuffer(b"abcdefghijklmnopqrstuvwxyz", dtype=np.uint8)
_PUNCT = np.frombuffer(b".,;:!?", dtype=np.uint8)
_BLOCK = 1 << 20                # words assembled at once (~18 MB of cells)
_THREADS = 4


def make_vocabulary(params: dict) -> Tuple[np.ndarray, np.ndarray, list]:
    """``(cells [V, MAXW+1] uint8, lengths [V], tail_words)`` from the
    configuration's ``vocab_seed``.  Same draws in the same order as
    ``bench.make_corpus``, so ``vocab_seed=0`` is that function's
    seed-0 vocabulary.  A cell holds the word's bytes and zeros."""
    V, n_punct = int(params["vocab_size"]), int(params["punct_vocab"])
    rng = np.random.default_rng(int(params["vocab_seed"]))
    n_base = V - n_punct
    lengths = (1 + rng.binomial(12, 0.35, size=V)).astype(np.int32)
    np.minimum(lengths, MAXW - 1, out=lengths)
    cells = np.zeros((V, MAXW + 1), dtype=np.uint8)
    mask = np.arange(MAXW + 1)[None, :] < lengths[:, None]
    cells[mask] = _LETTERS[rng.integers(0, 26, size=int(mask.sum()))]
    base_of = rng.integers(0, n_base, size=n_punct)
    cells[n_base:] = cells[base_of]
    lengths[n_base:] = lengths[base_of]
    cells[np.arange(n_base, V), lengths[n_base:]] = \
        _PUNCT[rng.integers(0, 6, n_punct)]
    lengths[n_base:] += 1
    tail_words = []
    for _ in range(int(params["long_words"])):
        ln = int(rng.integers(140, 200))
        tail_words.append(bytes(_LETTERS[rng.integers(0, 26, ln)]))
    return cells, lengths, tail_words


def _alias_table(p: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Vose's alias method: ``ids = where(u < prob[i], i, alias[i])`` for
    uniform ``i`` and ``u`` draws from *p* exactly."""
    n = p.size
    scaled = (p * n).tolist()
    prob = np.ones(n, dtype=np.float64)
    alias = np.arange(n, dtype=np.int32)
    small = [i for i, s in enumerate(scaled) if s < 1.0]
    large = [i for i, s in enumerate(scaled) if s >= 1.0]
    while small and large:
        s, g = small.pop(), large.pop()
        prob[s], alias[s] = scaled[s], g
        scaled[g] -= 1.0 - scaled[s]
        (small if scaled[g] < 1.0 else large).append(g)
    return prob, alias


def make_corpus(params: dict, seed: int) -> Tuple[bytes, Dict[bytes, int]]:
    """``(text, {word: count})``: *params* is the configuration's
    ``corpus`` group (``n_words``, ``n_lines``, ``vocab_size``,
    ``punct_vocab``, ``long_words``, ``long_repeats``, ``zipf_offset``,
    ``vocab_seed``)."""
    n_words, V = int(params["n_words"]), int(params["vocab_size"])
    cells, lengths, tail_words = make_vocabulary(params)
    repeats = int(params["long_repeats"])
    n_tail = len(tail_words) * repeats
    if n_words <= 2 * n_tail:
        n_tail, tail_words = 0, []
    n_draw = n_words - n_tail

    p = 1.0 / (np.arange(V) + float(params["zipf_offset"]))
    prob, alias = _alias_table(p / p.sum())
    # the separator rides in the cell: rows [0, V) end in a space, rows
    # [V, 2V) in a newline
    table = np.concatenate([cells, cells])
    table[np.arange(V), lengths] = ord(" ")
    table[np.arange(V, 2 * V), lengths] = ord("\n")
    line_every = max(n_words // int(params["n_lines"]), 1)
    starts = range(0, n_draw, _BLOCK)
    rngs = [np.random.default_rng(s) for s in
            np.random.SeedSequence(int(seed)).spawn(len(starts))]

    def block(lo: int, rng) -> Tuple[bytes, np.ndarray]:
        n = min(_BLOCK, n_draw - lo)
        i = rng.integers(0, V, size=n, dtype=np.int32)
        ids = np.where(rng.random(n) < prob[i], i, alias[i])
        count = np.bincount(ids, minlength=V)
        # newline terminators at the reference corpus's line cadence
        ids[(line_every - 1 - lo) % line_every::line_every] += V
        rows = table[ids]                       # [n, MAXW+1]
        return rows[rows != 0].tobytes(), count

    # numpy releases the interpreter lock in every call above
    with ThreadPoolExecutor(max_workers=_THREADS) as pool:
        done = list(pool.map(block, starts, rngs))
    parts = [text for text, _ in done]
    counts = np.sum([count for _, count in done], axis=0)

    reference: Dict[bytes, int] = {}
    words = [cells[v, :lengths[v]].tobytes() for v in range(V)]
    # random short words collide: one byte string, one count
    for word, c in zip(words, counts.tolist()):
        if c:
            reference[word] = reference.get(word, 0) + c
    tail = bytearray()
    for r in range(repeats if n_tail else 0):
        for w in tail_words:
            tail += w + (b"\n" if r % 3 == 2 else b" ")
            reference[w] = reference.get(w, 0) + 1
    parts.append(bytes(tail))
    return b"".join(parts), reference
