"""What the generators under ``generators/`` share: how sizes are drawn.

A mix is a data file under ``traffic/``: its ``kind`` names the generator
(``generators/<kind>.py``) that reads its parameters, its ``runner`` the cell
runner (``cells/<runner>.py``) that offers the result to the program. The
*sizes* of a run (request lengths, document lengths) and their order are
drawn from the file's ``shape_seed``; ``--seed`` draws the token ids (and, in
the runner, the weights). Runs with different seeds therefore do the same
work at the same moments: with the sizes in an order of the seed's own, six
runs of the closed loop spread by 12% where two runs of one seed agreed to
four digits (PERF.md, PR 25). Where the WEIGHTS decide how much work a step
is (held experts under a seeded router), a training mix names a
``weights_seed`` and ``--seed`` draws the token ids alone
(``cells/pretrain.py::weights_seed``; PERF.md, PR 63).
"""

from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass
class Request:
    prompt: np.ndarray      # int32 token ids
    max_new_tokens: int


def lognormal(rng, n: int, spec: dict, scale: float = 1.0) -> np.ndarray:
    """n whole numbers, log-normal with the given median, clipped (not
    redrawn) to [min, max]; `scale` shrinks all three for a rehearsal."""
    x = np.exp(rng.normal(np.log(spec["median"]), spec["sigma"], size=n))
    lo = max(1, int(round(spec["min"] * scale)))
    hi = max(lo, int(round(spec["max"] * scale)))
    return np.clip(np.rint(x * scale), lo, hi).astype(np.int64)


def request_sizes(mix: dict, n: int, scale: float):
    """(prompt lengths, answer lengths) of n requests, from ``shape_seed``,
    with prompt + answer inside ``max_total_len``."""
    shape = np.random.default_rng(mix["shape_seed"])
    prompts = lognormal(shape, n, mix["prompt_len"], scale)
    answers = lognormal(shape, n, mix["answer_len"], scale)
    limit = max(2, int(round(mix["max_total_len"] * scale)))
    answers = np.maximum(1, np.minimum(answers, limit - prompts))
    return prompts, answers


def tokens(rng, n: int, vocab: int) -> np.ndarray:
    return rng.integers(0, vocab, size=n, dtype=np.int64).astype(np.int32)
