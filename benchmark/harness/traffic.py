"""The one general generator of traffic. A traffic mix is a file of
parameters, ``benchmark/traffic/<name>.json``; its ``family`` names the
function (``package.module:function``, as a metric's reader is named)
that turns the parameters, a seed and the window's length into inputs. The
families here are general; a later PR's own may live in any module under
the manifest's ``paths``. The same seed gives the same inputs, and every
seed the same set of sizes and arrivals, so that the seed does not change
the work.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, List

import numpy as np

from benchmark.harness import manifest


def fixed_batches(cell, seed: int, seconds: float) -> List[Any]:
    """``distinct_batches`` training batches of ``rows`` x ``seq_len``,
    every row different, in the layout the configuration's reference
    states (``make_batch``)."""
    rng = np.random.default_rng(seed)
    return [cell.reference.make_batch(cell.config, rng, cell.traffic)
            for _ in range(cell.traffic["distinct_batches"])]


@dataclass
class Request:
    """One request of a serving schedule."""

    index: int
    due_s: float            # from the window's start; under 0 in the lead-in
    prompt: List[int]
    max_new_tokens: int


def replayed(cell, seed: int, seconds: float) -> List[Request]:
    """An open-loop schedule replayed from the mix's file: ``requests`` is
    rows of ``[due_s, prompt_tokens, output_tokens]`` in order of their due
    times. Rows due before 0 are the lead-in, which brings the server to a
    steady load before the window opens; rows due at ``seconds`` or later
    are left out. The run's seed draws only the token ids, uniform over
    the vocabulary, so every seed offers requests of the same lengths at
    the same times."""
    rng = np.random.default_rng(seed)
    vocab = cell.reference.vocab_size(cell.config)
    rows = [r for r in cell.traffic["requests"] if r[0] < seconds]
    return [Request(index=i, due_s=float(due),
                    prompt=rng.integers(0, vocab, int(prompt)).tolist(),
                    max_new_tokens=int(output))
            for i, (due, prompt, output) in enumerate(rows)]


def generate(cell, seed: int, seconds: float):
    return manifest.resolve(cell.traffic["family"])(cell, seed, seconds)
