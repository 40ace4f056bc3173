"""``"kind": "closed"``: requests for callers that each send their next
request when the last completes: ``pool_requests`` sizes in a fixed order,
cycled without end."""

from __future__ import annotations

from typing import Iterator

import numpy as np

from perfbench.traffic import Request, request_sizes, tokens


def requests(mix: dict, seed: int, vocab: int,
             scale: float = 1.0) -> Iterator[Request]:
    n = mix["pool_requests"]
    prompts, answers = request_sizes(mix, n, scale)
    rng = np.random.default_rng(seed)
    while True:
        for j in range(n):
            yield Request(tokens(rng, int(prompts[j]), vocab),
                          int(answers[j]))
