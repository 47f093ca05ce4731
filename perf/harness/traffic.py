"""The one general traffic generator. A mix is a data file of parameters
(``perf/traffic/<mix>.json``) with a ``kind``:

- ``train_stream``: a pool of seeded batches of token ids, cycled;
- ``closed_loop``: ``clients`` callers, each sending its next request when
  the last one resolved.

Every run seed sees the same set of sizes, drawn once from the mix's own
``population_seed``, in another order and with other token ids: a seed
changes the inputs, never the amount of work.
"""

import math

import numpy as np

SPECIAL_IDS = 3  # pad 0, bos 1, eos 2 are never drawn


def _rng(*words):
    # seeds run up to a little over 2**31; SeedSequence takes any size
    return np.random.default_rng([int(w) & 0xFFFFFFFFFFFF for w in words])


def token_ids(rng, shape, vocab):
    return rng.integers(SPECIAL_IDS, vocab, size=shape, dtype=np.int32)


# ------------------------------------------------------------- training
def train_pool(mix, seed, vocab, chips):
    """``pool_dispatches`` batches ``(ids, labels)``, each the global batch
    of one dispatch: ``per_chip_batch * chips`` rows that all differ."""
    rows = int(mix["per_chip_batch"]) * chips
    rng = _rng(seed, 1)
    return [(token_ids(rng, (rows, int(mix["seq_len"])), vocab),
             token_ids(rng, (rows, int(mix["seq_len"])), vocab))
            for _ in range(int(mix["pool_dispatches"]))]


# -------------------------------------------------------------- serving
def _clipped(x, lo, hi):
    return int(min(max(round(x), lo), hi))


def length_population(mix):
    """The fixed set of ``(source length, output length)`` pairs of a mix."""
    rng = _rng(mix["population_seed"], 2)
    src, out = mix["source_length"], mix["output_length"]
    pairs = []
    for _ in range(int(mix["population"])):
        s = _clipped(rng.lognormal(math.log(src["median"]), src["sigma"]),
                     src["min"], src["max"])
        o = _clipped(s * rng.normal(out["ratio_mean"], out["ratio_sd"]),
                     out["min"], out["max"])
        pairs.append((s, o))
    return pairs


class RequestStream:
    """Request ``i`` of a run: the same for a seed whatever the timing."""

    def __init__(self, mix, seed, vocab):
        self.pairs = length_population(mix)
        self.seed, self.vocab = seed, vocab
        self._orders = {}

    def _order(self, cycle):
        """Each pass through the set of sizes goes in an order of its own: a
        run of long prompts that one order happens to hold does not come
        back every pass and set the tail of a whole window."""
        if cycle not in self._orders:
            self._orders[cycle] = _rng(self.seed, 4, cycle).permutation(
                len(self.pairs))
        return self._orders[cycle]

    def request(self, i):
        cycle, at = divmod(i, len(self.pairs))
        s, o = self.pairs[self._order(cycle)[at]]
        return token_ids(_rng(self.seed, 6, i), (s,), self.vocab), o
