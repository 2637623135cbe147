"""One general generator for every traffic mix.

A mix is a JSON file of parameters under ``perfbench/traffic``; its
``kind`` picks one of two shapes of traffic:

* ``backlog`` — offline batch work: a stream of requests that never runs
  dry, admitted first in, first out.  Request ``i`` is a pure function of
  the seed and ``i``.
* ``sessions`` — open-loop multi-turn sessions: sessions arrive at fixed
  times; each asks about one document of a small corpus, question after
  question, every turn's prompt holding the document, the earlier turns
  and the new question.  Sessions share the corpus's documents evenly.

Every seed gets the same multiset of sizes, gaps and think times, in
another order, so two seeds give the same amount of work and load.  Only
the order and the token ids change with the seed.
"""
from __future__ import annotations

import json
import math
from dataclasses import dataclass, field

import numpy as np

_STREAMS = {"prompt": 1, "new": 2, "first": 3, "tokens": 4, "gap": 5,
            "doc": 6, "turns": 7, "question": 8, "answer": 9, "think": 10}


def load(path: str) -> dict:
    with open(path) as f:
        mix = json.load(f)
    if mix.get("kind") not in ("backlog", "sessions"):
        raise ValueError(f"{path}: unknown traffic kind {mix.get('kind')!r}")
    return mix


def rng(seed: int, stream: str, index: int = 0) -> np.random.Generator:
    """An independent generator for one stream and index of one seed."""
    return np.random.default_rng([int(seed), _STREAMS[stream], int(index)])


def spaced(lo: float, hi: float, n: int, integer: bool = True) -> list:
    """``n`` values spread evenly over ``[lo, hi]`` (mid-points of ``n``
    equal bins): the fixed multiset that every seed permutes."""
    vals = [lo + (hi - lo) * (i + 0.5) / n for i in range(n)]
    return [int(round(v)) for v in vals] if integer else vals


def permuted(seed: int, stream: str, values: list, index: int = 0) -> list:
    order = rng(seed, stream, index).permutation(len(values))
    return [values[i] for i in order]


def tokens(seed: int, key: int, n: int, vocab: int) -> list[int]:
    return rng(seed, "tokens", key).integers(0, vocab, size=n).tolist()


# -- backlog ---------------------------------------------------------------------

class Backlog:
    """Offline requests ``(prompt, new_tokens)`` for index 0, 1, 2, ...

    The first ``first`` requests (the cohort that fills every lane at
    once) and then each block of ``block`` requests are one fixed set of
    (prompt length, output length) pairs, which every seed puts in another
    order.  The cohort's output lengths come from
    ``first_cohort_new_tokens``, so the lanes finish staggered from the
    start and the batch is in its steady mix after the first prefills."""

    def __init__(self, mix: dict, seed: int, vocab: int, first: int):
        self.mix, self.seed, self.vocab, self.first = mix, seed, vocab, first
        self.block = int(mix["block"])

    @property
    def max_len(self) -> int:
        return int(self.mix["prompt_tokens"][1] + self.mix["new_tokens"][1])

    def request(self, i: int) -> tuple[list[int], int]:
        if i < self.first:
            shape = pairs(self.mix["prompt_tokens"],
                          self.mix["first_cohort_new_tokens"], self.first)
            plen, new = permuted(self.seed, "first", shape)[i]
        else:
            b, j = divmod(i - self.first, self.block)
            shape = pairs(self.mix["prompt_tokens"], self.mix["new_tokens"],
                          self.block)
            plen, new = permuted(self.seed, "prompt", shape, b)[j]
        return tokens(self.seed, i, plen, self.vocab), new


def pairs(prompt_lo_hi, new_lo_hi, n: int) -> list[tuple[int, int]]:
    """``n`` fixed (prompt length, output length) pairs: both lengths
    spread evenly over their ranges, the ``j``-th prompt length with the
    output length ``j * stride`` along, for a stride prime to ``n``."""
    stride = next(s for s in (3, 5, 7, 11, 13, 17, 19) if math.gcd(s, n) == 1)
    prompts, news = spaced(*prompt_lo_hi, n), spaced(*new_lo_hi, n)
    return [(prompts[j], news[(j * stride) % n]) for j in range(n)]


# -- sessions --------------------------------------------------------------------

@dataclass
class Session:
    sid: int
    arrival_s: float               # after the start of the lead-in
    doc: list[int]
    questions: list[list[int]]
    answers: list[int]             # new tokens of each turn
    think_s: list[float]           # pause after each answer
    served: list[list[int]] = field(default_factory=list)

    def prompt(self, turn: int) -> list[int]:
        """The document, the earlier questions with the answers served
        to them, and this turn's question."""
        out = list(self.doc)
        for k in range(turn):
            out += self.questions[k] + self.served[k]
        return out + self.questions[turn]


def session_count(mix: dict, seconds: float) -> int:
    return int(math.ceil(mix["rate_per_s"] * (mix["lead_in_s"] + seconds))) + 2


def session_max_len(mix: dict) -> int:
    t = mix["turns"][1]
    return int(mix["doc_tokens"][1] + t * mix["question_tokens"][1]
               + t * mix["answer_tokens"][1])


def sessions(mix: dict, seed: int, seconds: float, vocab: int,
             rate_per_s: float | None = None) -> list[Session]:
    """The sessions of one run, in order of arrival.  Inter-arrival gaps
    are the quantiles of an exponential distribution at the mix's rate,
    in one fixed order: every run has the same arrival times, and the
    seed decides which session arrives at each."""
    rate = rate_per_s or mix["rate_per_s"]
    m = session_count(dict(mix, rate_per_s=rate), seconds)
    gaps = [-math.log(1.0 - q) / rate for q in spaced(0.0, 1.0, m, False)]
    gaps = permuted(0, "gap", gaps)
    n_docs = int(mix["documents"])
    doc_tokens = spaced(*mix["doc_tokens"], n_docs)
    corpus = [tokens(seed, 900_000 + d, doc_tokens[d], vocab)
              for d in range(n_docs)]
    docs = permuted(seed, "doc", [s % n_docs for s in range(m)])
    turns = permuted(seed, "turns", spaced(mix["turns"][0] - 0.49,
                                           mix["turns"][1] + 0.49, m))
    n_turns = sum(turns)
    qs = permuted(seed, "question", spaced(*mix["question_tokens"], n_turns))
    ans = permuted(seed, "answer", spaced(*mix["answer_tokens"], n_turns))
    think = permuted(seed, "think", spaced(*mix["think_s"], n_turns, False))
    out, t, k = [], 0.0, 0
    for s in range(m):
        t += gaps[s]
        n = turns[s]
        questions = [tokens(seed, 1_000_000 + 100 * s + j, qs[k + j], vocab)
                     for j in range(n)]
        out.append(Session(sid=s, arrival_s=t,
                           doc=corpus[docs[s]],
                           questions=questions, answers=ans[k:k + n],
                           think_s=think[k:k + n]))
        k += n
    return out
