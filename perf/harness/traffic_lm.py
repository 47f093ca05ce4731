"""Traffic of a decoder-only language model (``kind: closed_loop_lm``): the
prompt's length and the reply's are drawn independently (the generator in
``traffic.py`` ties the reply's length to the source's, as translation
does). A fixed population of length pairs from the mix's own
``population_seed``, gone through in a new seeded order each pass, token
ids from the run's seed, as in that generator. One thing differs: the order
of a pass is seeded by the mix and the pass's number, not by the run. A
window of this traffic finishes about one pass of the population (some 65
requests of 64 pairs), so an order of the run's own decided which prompts
queued behind which and how many long replies fell inside the window: the
amount of work. Here a run's seed changes the token ids (and the weights)
and nothing else."""

import math

from . import traffic as gen


def length_population(mix):
    """The fixed set of ``(prompt length, reply length)`` pairs of a mix."""
    rng = gen._rng(mix["population_seed"], 2)

    def draw(d):
        return gen._clipped(rng.lognormal(math.log(d["median"]), d["sigma"]),
                            d["min"], d["max"])

    return [(draw(mix["prompt_length"]), draw(mix["reply_length"]))
            for _ in range(int(mix["population"]))]


class RequestStream(gen.RequestStream):
    """Request ``i`` of a run: the same for a seed whatever the timing."""

    def __init__(self, mix, seed, vocab):
        self.pairs = length_population(mix)
        self.seed, self.vocab = seed, vocab
        self.order_seed = mix["population_seed"]
        self._orders = {}

    def _order(self, cycle):
        """A new order each pass, the same for every run (the base class
        seeds it with the run's seed, which suits a window of many passes)."""
        if cycle not in self._orders:
            self._orders[cycle] = gen._rng(self.order_seed, 4, cycle) \
                .permutation(len(self.pairs))
        return self._orders[cycle]
