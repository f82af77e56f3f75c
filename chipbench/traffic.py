"""Seeded request traffic for the serve drivers, read from a traffic file.

Copied from the program's `launch/loadgen.py` `generate()` (lognormal
prompts, geometric outputs, optional shared prefixes, Poisson arrivals),
changed so that seeds vary the tokens and not the work: every size, gap
and prefix choice comes, in one fixed order, from a generator of its own
that no seed reaches, and the seed draws only the token ids.  A closed
loop's window takes requests from the front of the stream as slots free
up, so any reordering by seed would change which requests, joins and
preemptions fall inside it.

Traffic-file keys (all lengths in tokens):
  prompt_median, prompt_sigma, prompt_min, prompt_max  lognormal prompts
  out_mean, out_min, out_max                           geometric outputs
  shared_prefix_frac, shared_prefix_len, n_prefixes    prefix sharing
  rate                                                 Poisson arrivals/s
                                                       (open loop)
"""
from __future__ import annotations

import dataclasses

import numpy as np

#: seeds the fixed size draws (so no seed changes the work)
_SIZE_SEED = 0x5EED


@dataclasses.dataclass(frozen=True)
class Spec:
    """One request: `due` is seconds after the window opens (open loop)."""
    rid: int
    prompt: np.ndarray
    max_new: int
    due: float = 0.0


def stream(t: dict, seed: int, vocab: int, open_loop: bool):
    """Every request of the traffic for `seed`, in order, without end."""
    sizes = np.random.default_rng(_SIZE_SEED)
    rng = np.random.default_rng([seed, 3])
    prefixes = [rng.integers(0, vocab, t.get("shared_prefix_len", 0))
                for _ in range(t.get("n_prefixes", 0))]
    due, rid = 0.0, 0
    while True:
        x = np.exp(sizes.normal(np.log(t["prompt_median"]),
                                t["prompt_sigma"]))
        n = int(np.clip(round(x), t["prompt_min"], t["prompt_max"]))
        m = int(np.clip(sizes.geometric(1.0 / t["out_mean"]),
                        t["out_min"], t["out_max"]))
        if open_loop:
            due += sizes.exponential(1.0 / t["rate"])
        prompt = rng.integers(0, vocab, n)
        if prefixes and sizes.random() < t.get("shared_prefix_frac", 0.0):
            pre = prefixes[int(sizes.integers(len(prefixes)))][:n - 1]
            prompt[:len(pre)] = pre
        yield Spec(rid=rid, prompt=prompt.astype(np.int32), max_new=m,
                   due=float(due))
        rid += 1
